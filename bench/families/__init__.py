"""Model families: what the harness must know of a configuration's
architecture, one module per family, ``bench/families/<family>.py``.

A configuration file names its family under ``"family"``; ``family_of``
imports that module by the name, so a new architecture is a new file
here and no registry is edited.  A family module exposes:

``Dims``
    The type of its shapes: a frozen, hashable dataclass (it is a static
    argument of jitted functions) with at least ``vocab``, the bound of
    the token ids the traffic draws.
``dims_of(spec) -> Dims``
    The shapes of a configuration file (``bench/configs/<name>.json``,
    keys as in the model's published ``config.json``); raises on a block
    the family does not describe.
``program_config(spec, d) -> repro.configs.base.ModelConfig``
    The program's configuration to serve.  The only part of a family
    that reads the program.
``layout(d) -> dict``
    Nested dicts of leaf shapes (tuples), stacked over layers where a
    leaf is per layer: the served weights' tree, names and shapes as the
    program's ``lm.init_params`` makes them (the harness checks).
``NORMS``
    Leaf names that ``bench/weights.py`` makes as ones; every other leaf
    is normal with standard deviation 0.02.
``logits_at(weights, d, tokens, rows, *, precision="f32") -> np.ndarray``
    The plain reference: float32 logits ``(len(rows), vocab)`` of the
    forward over ``tokens`` at positions ``rows``, from the weights
    ``bench/weights.py`` makes.  ``precision="fp8"`` is the control, the
    same forward in float8.  It imports nothing of the program.
``decode_bytes(d, keys, meta) -> int``
``decode_flops(d, keys, meta) -> float``
    The least HBM bytes and the model FLOPs of one decode round whose
    live rows attend ``keys`` keys each (their new token included).
``prefill_flops(d, start, n, meta) -> float``
    The model FLOPs of prefilling ``n`` tokens at positions ``start ..
    start + n - 1``.

``meta`` is the meta of the engine's span of that call (``engine.decode``:
``rows``, ``keys``, ``kv_blocks``, ``attn``, ``compiles``;
``engine.prefill``: ``sid``, ``n``, ``pad_to``, ``compiles``) in a traced
run, ``{}`` in an untraced one; a count may read it for what the shapes
alone do not fix (the experts a round hits), and returns a floor that no
correct program undercuts, so a share of a roofline stays at most 100%.
"""
from __future__ import annotations

import importlib
import re
from pathlib import Path
from types import ModuleType
from typing import List

PACKAGE = "bench.families"       # where ``family_of`` looks


def known() -> List[str]:
    """The families ``PACKAGE`` holds."""
    pkg = importlib.import_module(PACKAGE)
    return sorted(p.stem for d in pkg.__path__ for p in Path(d).glob("*.py")
                  if re.fullmatch(r"[A-Za-z]\w*", p.stem))


def family_of(spec: dict) -> ModuleType:
    """The family module a configuration file names."""
    name = spec.get("family")
    if name not in known():
        raise ValueError(f"{spec.get('name')}: unknown family {name!r}; "
                         f"known: {known()}")
    return importlib.import_module(f"{PACKAGE}.{name}")
