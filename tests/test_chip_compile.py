"""Ahead-of-time compiles of the served path for one TPU v5e chip.

The TPU compiler is installed even where no chip is attached: these tests
compile the engine's jitted prefill (one 1024-token bucket) and paged
decode step at llama3.2-3b widths, with depth cut to 2 layers, the
Pallas paged-decode kernel at serving widths, and the served paged
decode at the benchmark's Mistral-Nemo and Qwen3 widths, for a described
v5e chip.  They catch what interpret mode cannot: tiling, VMEM and HBM
limits, and what the compiler makes of the pool (aliased in place, no
``max_len`` gather).  The topology is described inside a fixture, never
at import, so only the worker that runs this file loads the TPU library.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config, load_all
from repro.kernels.paged_attention.kernel import paged_decode_attention
from repro.models import lm
from repro.serving.engine import _jitted_fns, serving_env

HBM_BYTES = 16 * 10**9          # one v5e chip
# serving widths of llama3.2-3b: 4 decode slots x 2048 tokens in 16-token
# blocks (128 per row) over a 1024-block pool
N_SLOTS, BLOCK, ROW_BLOCKS, POOL_BLOCKS = 4, 16, 128, 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described chip's executables are written to the persistent cache
    # but cannot be read back without one: keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def cfg():
    load_all()
    return dataclasses.replace(get_config("llama3.2-3b"), n_layers=2)


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _params(cfg, one_chip):
    return jax.tree_util.tree_map(
        lambda a: _spec(one_chip, a.shape, a.dtype), lm.abstract_params(cfg))


def _fits(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 0 < used < HBM_BYTES, m
    return m


def test_served_prefill_compiles_for_v5e(cfg, one_chip):
    prefill = _jitted_fns(cfg, serving_env(), "tpu")[1]
    compiled = prefill.lower(_params(cfg, one_chip),
                             _spec(one_chip, (1, 1024), jnp.int32),
                             pad_to=1024).compile()
    m = _fits(compiled)
    # the step returns the bucket's K and V for every layer
    kv = 2 * cfg.n_layers * 1024 * cfg.n_kv_heads * cfg.head_dim * 2
    assert m.output_size_in_bytes >= kv


def _paged_decode(cfg, one_chip, n_slots, row_blocks, pool_blocks):
    """The served paged decode step, compiled for v5e, and its pool."""
    paged = _jitted_fns(cfg, serving_env(), "tpu")[2]
    pool = _spec(one_chip, (cfg.n_layers, pool_blocks, BLOCK,
                            cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16)
    row = _spec(one_chip, (n_slots,), jnp.int32)
    compiled = paged.lower(
        _params(cfg, one_chip), _spec(one_chip, (n_slots, 1), jnp.int32),
        pool, pool, _spec(one_chip, (n_slots, row_blocks), jnp.int32),
        row, row, row).compile()
    return compiled, pool


def test_paged_decode_step_compiles_for_v5e(cfg, one_chip):
    compiled, pool = _paged_decode(cfg, one_chip, N_SLOTS, ROW_BLOCKS,
                                   POOL_BLOCKS)
    m = _fits(compiled)
    assert m.output_size_in_bytes >= 2 * pool.size * 2      # both pools


def test_paged_decode_attention_kernel_compiles_for_v5e(cfg, one_chip):
    H, K, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pool = _spec(one_chip, (cfg.n_layers, POOL_BLOCKS, BLOCK, K, dh),
                 jnp.bfloat16)
    compiled = jax.jit(
        lambda *a: paged_decode_attention(*a, interpret=False)).lower(
        _spec(one_chip, (N_SLOTS, H, dh), jnp.bfloat16), pool, pool,
        _spec(one_chip, (), jnp.int32),
        _spec(one_chip, (N_SLOTS, ROW_BLOCKS), jnp.int32),
        _spec(one_chip, (N_SLOTS,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


# the benchmark's engine: 8 slots x 6400 tokens, all resident (8 x 400
# blocks of 16)
BENCH_SLOTS, BENCH_ROW_BLOCKS = 8, 400


@pytest.mark.parametrize("name", ["mistral-nemo-12b", "qwen3-32b"])
def test_served_paged_decode_reads_the_pool_in_place(name, one_chip):
    """At the benchmark's widths the served decode aliases both pools to
    its outputs, attends with the kernel, and materialises no pool-sized
    or ``max_len``-sized buffer: no gather, copy or dynamic-update-slice
    of one is left in the optimised program."""
    load_all()
    cfg = dataclasses.replace(get_config(name), n_layers=2)
    compiled, pool = _paged_decode(cfg, one_chip, BENCH_SLOTS,
                                   BENCH_ROW_BLOCKS,
                                   BENCH_SLOTS * BENCH_ROW_BLOCKS)
    m = _fits(compiled)
    assert m.alias_size_in_bytes >= 2 * pool.size * 2
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    K, dh = cfg.n_kv_heads, cfg.head_dim
    nb, blocks = BENCH_ROW_BLOCKS, BENCH_SLOTS * BENCH_ROW_BLOCKS
    big = [f"{blocks},{BLOCK},{K},{dh}]",              # a layer's pool
           f"{BENCH_SLOTS},{nb},{BLOCK},{K},{dh}]",    # gathered rows
           f"{BENCH_SLOTS},{nb * BLOCK},{K},{dh}]"]    # (B, max_len, K, dh)
    bad = [ln.strip()[:160] for ln in hlo.splitlines()
           if re.search(r"= \S+ (gather|copy|dynamic-update-slice)\(", ln)
           and any(b in ln.split("=")[1].split("(")[0] for b in big)]
    assert not bad, bad
