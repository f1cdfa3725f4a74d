"""Find the highest rate a configuration sustains under a mix (not run
by the benchmark's own runs; a cell's ``rate_per_s`` is set from it).

    python3 -m bench.sweep --config <name> --traffic <mix> --rates 2,3,4 \
        --seconds 20 --seed <n>

One process sets up once, then serves the mix at each offered rate for
``--seconds`` and prints one JSON line per rate: requests offered and
finished per second over the window, the requests still waiting or
decoding when it closed, and the tails.  Above the highest sustained
rate the finished rate falls short of the offered one and the backlog
grows with the window."""
from __future__ import annotations

import argparse
import json
import sys


def sweep(config, mix, rates, seconds, seed, log, device_peaks=None):
    import jax
    from bench import weights as W
    from bench.families import family_of
    from bench.harness import (Server, _Compiles, load_reader, percentile,
                               warm_shapes, RunRecord)
    from bench.peaks import peaks
    from bench.traffic import schedule
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving.engine import Engine

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    _Compiles.install()
    dev = jax.devices()[0]
    fam = family_of(config)
    d = fam.dims_of(config)
    e = config["engine"]
    eng = Engine(fam.program_config(config, d),
                 W.make_weights(fam, d, seed, dev),
                 n_slots=e["n_slots"], max_len=e["max_len"],
                 pool_blocks=e["pool_blocks"], block_size=e["block_size"],
                 device=dev)
    warm_shapes(eng, mix, d.vocab, seed)
    ttft, tpot = load_reader("ttft_p90_ms"), load_reader("tpot_p90_ms")
    for rate in rates:
        m = dict(mix, rate_per_s=rate)
        srv = Server(eng, schedule(m, seed, d.vocab, seconds),
                     warm_s=float(m["warm_s"]), seconds=seconds,
                     trace_dir=None)
        srv.run()
        run = RunRecord(family=fam, dims=d,
                        peaks=device_peaks or peaks(dev.device_kind),
                        t_process=0.0, t0=srv.t0, t1=srv.t1,
                        requests=srv.recs, rounds=srv.rounds,
                        prefills=srv.prefills, compiles_window=0)
        pop = run.population
        done_in = [r for r in srv.recs if r.complete
                   and run.in_window(r.token_t[-1])]
        backlog = sum(1 for r in srv.recs if r.arrival < srv.t1 and not (
            r.token_t and r.token_t[-1] <= srv.t1 and r.complete))
        waits = [1e3 * (r.token_t[0] - r.arrival) for r in pop if r.token_t]
        out = {"rate_per_s": rate, "offered_per_s": len(pop) / run.window_s,
               "finished_per_s": len(done_in) / run.window_s,
               "backlog_at_close": backlog,
               "ttft_p50_ms": percentile(waits, 50),
               "ttft_p90_ms": ttft(run), "tpot_p90_ms": tpot(run),
               "rounds": len(srv.rounds)}
        log(json.dumps(out))
        print(json.dumps(out), flush=True)
        for r in srv.recs:
            eng.release_session(r.req.rid)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import jax
    from bench.dims import load_config
    from bench.traffic import load_mix
    if jax.devices()[0].platform != "tpu":
        print("bench.sweep: needs a TPU", file=sys.stderr)
        return 2
    sweep(load_config(args.config), load_mix(args.traffic),
          [float(r) for r in args.rates.split(",")], args.seconds,
          args.seed, lambda m: print(f"bench.sweep: {m}", file=sys.stderr,
                                     flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
