"""The dense GQA decoder (Mistral-Nemo, Qwen3): shapes ``bench/dims.py``,
plain reference ``bench/reference.py``, bytes ``bench/bytes.py`` and FLOPs
``bench/flops.py``, behind the family interface
(``bench/families/__init__.py``).  The counts read the shapes alone and
ignore ``meta``."""
from __future__ import annotations

from typing import Dict, Iterable

from bench import bytes as _bytes, flops as _flops
from bench.dims import Dims, dims_of
from bench.reference import logits_at

__all__ = ["Dims", "dims_of", "program_config", "layout", "NORMS",
           "logits_at", "decode_bytes", "decode_flops", "prefill_flops"]

NORMS = ("ln1", "ln2", "qnorm", "knorm", "final_norm")


def program_config(spec: dict, d: Dims):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    return ModelConfig(name=spec["name"], family="dense",
                       n_layers=d.n_layers, d_model=d.d_model,
                       n_heads=d.n_heads, n_kv_heads=d.n_kv_heads,
                       head_dim=d.head_dim, d_ff=d.d_ff, vocab=d.vocab,
                       qk_norm=d.qk_norm, rope_theta=d.rope_theta,
                       norm_eps=d.norm_eps,
                       tie_embeddings=d.tie_embeddings)


def layout(d: Dims) -> Dict:
    """Leaf shapes, stacked over layers where a leaf is per layer."""
    L, D, H, K, dh, F = (d.n_layers, d.d_model, d.n_heads, d.n_kv_heads,
                         d.head_dim, d.d_ff)
    attn = {"wq": (L, D, H, dh), "wk": (L, D, K, dh), "wv": (L, D, K, dh),
            "wo": (L, H, dh, D)}
    if d.qk_norm:
        attn["qnorm"] = (L, dh)
        attn["knorm"] = (L, dh)
    tree = {"embed": (d.vocab, D), "final_norm": (D,),
            "layers": {"ln1": (L, D), "ln2": (L, D), "attn": attn,
                       "mlp": {"w1": (L, D, F), "w3": (L, D, F),
                               "w2": (L, F, D)}}}
    if not d.tie_embeddings:
        tree["unembed"] = (D, d.vocab)
    return tree


def decode_bytes(d: Dims, keys: Iterable[int], meta: dict) -> int:
    return _bytes.decode_bytes(d, keys)


def decode_flops(d: Dims, keys: Iterable[int], meta: dict) -> float:
    return _flops.decode_flops(d, keys)


def prefill_flops(d: Dims, start: int, n: int, meta: dict) -> float:
    return _flops.prefill_flops(d, start, n)
