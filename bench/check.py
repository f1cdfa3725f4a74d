"""The comparison that decides ``correct``: served greedy tokens against
the plain reference, the configuration's family's ``logits_at``
(``bench/families``).

A served request is its prompt and the tokens it decoded.  The reference
reads the prompt followed by every decoded token but the last, and
scores decoded token ``i`` at position ``len(prompt) - 1 + i``: the
model's own greedy continuation of the prompt.  For each served token
the number is how far the reference's logit of that token lies below the
reference's best logit at the same position; a run reads the widest such
gap over its sample."""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Sequence

import numpy as np

SAMPLE_TOKENS = 320      # served tokens a run compares, at the least


@dataclasses.dataclass
class ServedStep:
    context: List[int]           # the prompt
    served: List[int]            # the tokens decoded after it

    def stream(self):
        """(tokens the reference reads, positions scored)."""
        n, k = len(self.context), len(self.served)
        return self.context + self.served[:-1], list(range(n - 1, n - 1 + k))


def sample(steps: Sequence[ServedStep], rng: np.random.Generator,
           min_tokens: int = SAMPLE_TOKENS) -> List[ServedStep]:
    """The longest request, then requests drawn from ``rng`` until the
    sample holds ``min_tokens`` served tokens (or every request)."""
    if not steps:
        return []
    longest = max(range(len(steps)),
                  key=lambda i: (len(steps[i].context) + len(steps[i].served),
                                 i))
    order = [longest] + [int(i) for i in rng.permutation(len(steps))
                         if i != longest]
    out, n = [], 0
    for i in order:
        if n >= min_tokens:
            break
        out.append(steps[i])
        n += len(steps[i].served)
    return out


def gaps(logits_at: Callable, weights: Dict, d, steps: Sequence[ServedStep],
         *, control: str = "") -> np.ndarray:
    """Per served token of ``steps``, the reference ``logits_at``'s best
    logit minus its logit of the token scored.  With ``control`` (a
    precision of ``logits_at``) the token scored at each position is the
    one the forward at that precision puts first (no decoding: the same
    streams)."""
    out = []
    for st in steps:
        tokens, rows = st.stream()
        ref = logits_at(weights, d, tokens, rows)
        if control:
            pick = logits_at(weights, d, tokens, rows,
                             precision=control).argmax(-1)
        else:
            pick = np.asarray(st.served)
        out.append(ref.max(-1) - ref[np.arange(len(pick)), pick])
    return np.concatenate(out) if out else np.zeros(0, np.float32)


def widest_gap(logits_at: Callable, weights: Dict, d,
               steps: Sequence[ServedStep], *, control: str = "") -> float:
    """The run's number: the widest gap in the sample (infinite where
    the sample is empty or a logit is not finite)."""
    g = gaps(logits_at, weights, d, steps, control=control)
    return float(g.max()) if g.size and np.all(np.isfinite(g)) \
        else float("inf")
