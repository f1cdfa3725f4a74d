"""Chip benchmark of the serving engine: one cell (model configuration x
traffic mix) per run, an open loop of requests driven through the
program's paged ``Engine`` (``harness.py`` says why not through the
runtime).

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, metrics and bounds are listed in ``BENCHMARK.json`` at the root of
the checkout.  Each configuration (``configs/<name>.json``), traffic mix
(``traffic/<name>.json``) and metric (``metrics/<name>.py``) lives in a
file of its own that the harness finds by the name given there.
"""
