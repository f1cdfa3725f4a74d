"""Model FLOPs utilisation of the whole served step in the traced part
of the window: FLOPs of every token prefilled and decoded there
(counted by the configuration's family, ``bench/families``) over the
traced window's length times the chips' bf16 peak."""


def read(run):
    if run.trace is None:
        return None
    fam, d = run.family, run.dims
    flops = sum(fam.decode_flops(d, r.keys, r.meta) for r in run.rounds
                if r.keys and run.in_trace(r.start, r.end))
    flops += sum(fam.prefill_flops(d, 0, p.n, p.meta) for p in run.prefills
                 if run.in_trace(p.start, p.end))
    if flops <= 0:
        return None
    return 100.0 * flops / (run.trace.window_s * run.trace.n_devices
                            * run.peaks["bf16_flops_per_s"])
