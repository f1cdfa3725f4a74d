"""A test-only family: ``dense_gqa``, with every call of the interface
recorded in ``CALLS`` as (function name, positional args)."""
from bench.families import dense_gqa as _dense

Dims = _dense.Dims
NORMS = _dense.NORMS
CALLS = []


def _recorded(name):
    fn = getattr(_dense, name)

    def call(*args, **kw):
        CALLS.append((name, args))
        return fn(*args, **kw)
    return call


dims_of = _recorded("dims_of")
program_config = _recorded("program_config")
layout = _recorded("layout")
logits_at = _recorded("logits_at")
decode_bytes = _recorded("decode_bytes")
decode_flops = _recorded("decode_flops")
prefill_flops = _recorded("prefill_flops")
