"""Run one benchmark cell once and print its result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer ones), ``device`` and, traced,
``breakdown``; ``check`` comes last and holds each number compared with
its limit, which also close standard error.  The run needs a TPU: on any
other platform, or with fewer chips than the cell asks for, it exits
with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def cell_spec(name: str, bench: dict):
    """(cell, metric entries with --trace 0, with --trace 1)."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]

    def applies(m):
        return name in m.get("workloads", [name])

    return (cell, [m for m in bench["end_to_end"] if applies(m)],
            [m for m in bench["per_layer"] if applies(m)])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell, e2e, per_layer = cell_spec(args.workload, bench)

    import jax
    from bench.dims import load_config
    from bench.harness import serve_and_check
    from bench.traffic import load_mix

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found platform "
              f"{devs[0].platform!r}", file=sys.stderr)
        return 2
    if len(devs) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} chips, JAX "
              f"found {len(devs)}", file=sys.stderr)
        return 2

    def log(msg):
        print(f"bench: {msg}", file=sys.stderr, flush=True)

    out = serve_and_check(load_config(cell["config"]),
                          load_mix(cell["traffic"]), seed=args.seed,
                          seconds=args.seconds, trace=bool(args.trace),
                          chips=cell["chips"],
                          metrics=per_layer if args.trace else e2e,
                          t_process=T_PROCESS, log=log)
    for name, c in out["check"].items():
        print(f"check: {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
