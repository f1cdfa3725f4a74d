"""Device microseconds of the prefill programs per prompt token they
prefilled, in the traced part of the window (programs named
``PROGRAM`` in the trace, tokens from the prefills the host dispatched
there)."""
PROGRAM = "prefill"          # jitted prefill of the served engine


def read(run):
    if run.trace is None:
        return None
    dev_s = sum(v for k, v in run.trace.module_s.items() if PROGRAM in k)
    n = sum(p.n for p in run.prefills if run.in_trace(p.start, p.end))
    if dev_s <= 0 or n <= 0:
        return None
    return 1e6 * dev_s / run.trace.n_devices / n
