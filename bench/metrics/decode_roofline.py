"""Share of its roofline that the paged decode program reaches in the
traced part of the window: the least time its rounds need on this chip
(the larger of the bytes they must move over HBM bandwidth and their
model FLOPs over peak; bytes bound it at these shapes) over the device
time of the decode programs in the trace.  Bytes and FLOPs are the
algorithm's (bench/bytes.py, bench/flops.py), from each live row's real
context."""
from bench.bytes import decode_bytes
from bench.flops import decode_flops

PROGRAM = "paged_decode"     # jitted decode step of the served engine


def read(run):
    if run.trace is None:
        return None
    dev_s = sum(v for k, v in run.trace.module_s.items() if PROGRAM in k)
    rounds = [r for r in run.rounds if r.keys and run.in_trace(r.start, r.end)]
    if dev_s <= 0 or not rounds:
        return None
    p = run.peaks
    need = sum(max(decode_bytes(run.dims, r.keys) / p["hbm_bytes_per_s"],
                   decode_flops(run.dims, r.keys) / p["bf16_flops_per_s"])
               for r in rounds)
    return 100.0 * need / (dev_s / run.trace.n_devices)
