"""Readings that set the limit of a cell's comparison (not run by the
benchmark's own runs).

    python3 -m bench.control --workload <cell> --seconds <s> --seeds <a,b,c>

For each seed, in one process, it serves the cell as a run does (a
shorter window at the cell's own load), takes the same sample, and
prints one JSON line with the widest gaps below the float32 reference's
best logit of:

  served     the tokens the program served (the number a run compares);
  control    the tokens that the reference in float8 e4m3 puts first at
             each position of the same streams (the lower precision a
             later change might be tempted to serve).

The limit of ``max_logit_gap`` lies above every ``served`` reading and
below every ``control`` reading."""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def readings(config, mix, seed, seconds, chips, log, device_peaks=None):
    from bench import check, weights as W
    from bench.harness import checked_sample, serve
    t0 = time.perf_counter()
    out, steps, d, devs = serve(config, mix, seed=seed, seconds=seconds,
                                trace=False, chips=chips, metrics=[],
                                t_process=t0, log=log,
                                device_peaks=device_peaks)
    weights = W.make_weights(d, seed, devs[0])
    sample = checked_sample(steps, seed)
    served = check.widest_gap(weights, d, sample)
    control = check.widest_gap(weights, d, sample, control="fp8")
    del weights
    return {"seed": seed, "requests": len(sample),
            "tokens": sum(len(s.served) for s in sample),
            "longest": max((len(s.context) + len(s.served) for s in sample),
                           default=0),
            "failed": out["failed"], "served": served, "control": control,
            "seconds": time.perf_counter() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    import jax
    from bench.dims import load_config
    from bench.run import cell_spec
    from bench.traffic import load_mix

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell, _, _ = cell_spec(args.workload, bench)
    if jax.devices()[0].platform != "tpu":
        print("bench.control: needs a TPU", file=sys.stderr)
        return 2

    def log(msg):
        print(f"bench.control: {msg}", file=sys.stderr, flush=True)

    for seed in (int(s) for s in args.seeds.split(",")):
        r = readings(load_config(cell["config"]), load_mix(cell["traffic"]),
                     seed, args.seconds, cell["chips"], log)
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
