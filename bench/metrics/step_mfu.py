"""Model FLOPs utilisation of the whole served step in the traced part
of the window: FLOPs of every token prefilled and decoded there (from
the configuration's shapes, bench/flops.py) over the traced window's
length times the chips' bf16 peak."""
from bench.flops import decode_flops, prefill_flops


def read(run):
    if run.trace is None:
        return None
    flops = sum(decode_flops(run.dims, r.keys) for r in run.rounds
                if r.keys and run.in_trace(r.start, r.end))
    flops += sum(prefill_flops(run.dims, 0, p.n) for p in run.prefills
                 if run.in_trace(p.start, p.end))
    if flops <= 0:
        return None
    return 100.0 * flops / (run.trace.window_s * run.trace.n_devices
                            * run.peaks["bf16_flops_per_s"])
