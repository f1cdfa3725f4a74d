"""Pallas TPU kernels for the serving substrate's compute hot spots.

SAGA itself is a scheduler (no kernel-level contribution), but its
substrate's hot loops are exactly the ops the serving stack spends its
FLOPs on.  Four kernels, each with kernel.py (pl.pallas_call + explicit
BlockSpec VMEM tiling), ops.py (jit'd wrapper), ref.py (pure-jnp oracle):

  flash_attention/  prefill: online-softmax tiled causal/GQA/SWA attention
  paged_attention/  decode: block-table-indirected flash decoding
                    (PagedAttention adapted to TPU scalar prefetch)
  rwkv6/            WKV6 data-dependent-decay recurrence (chunked)
  mamba_scan/       selective-SSM scan (chunked)

Every op compiles for the TPU by default; ``interpret=True`` runs the
kernel body on any backend.  All are validated in interpret mode on the CPU
against ref.py across shape/dtype sweeps (tests/test_kernels.py), and the
paged decode kernel is compiled ahead of time for a v5e chip
(tests/test_chip_compile.py).  Only paged_attention is on the served
path: the engine's paged decode attends with it on a TPU
(models.layers.gqa_attention_decode_paged).
"""
