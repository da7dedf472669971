"""The comparison that decides ``correct``.

A served token is judged by the float32 reference run over its request's
prompt followed by every token served for it: the token's gap is how far
its reference logit lies below the reference's best logit at that
position.  A run is correct when the widest gap over a sample of the
requests the window finished stays within the configuration's limit
(``harness.judge``).

The control, and the program's own teacher-forced readings, use the
same positions and read the gap of the token that *they* put first.
"""

from __future__ import annotations

import numpy as np

SAMPLE = 8                       # requests compared per run


def sample(finished: list, seed: int, n: int = SAMPLE) -> list:
    """The longest finished request, plus others drawn from the seed."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: r.rid)
    longest = max(order, key=lambda r: (len(r.prompt) + len(r.output), r.rid))
    rest = [r for r in order if r is not longest]
    rng = np.random.default_rng([seed, 0x5EED])
    pick = rng.permutation(len(rest))[:max(n - 1, 0)]
    return [longest] + [rest[i] for i in sorted(pick)]


def rows_of(req) -> tuple[np.ndarray, list[int]]:
    """The sequence (prompt + served) and the rows whose logits chose
    each served token."""
    seq = np.concatenate([np.asarray(req.prompt, np.int32),
                          np.asarray(req.output, np.int32)])
    p = len(req.prompt)
    return seq, list(range(p - 1, p - 1 + len(req.output)))


def gaps(logits: np.ndarray, tokens) -> np.ndarray:
    """Reference best minus the reference logit of ``tokens``, per row."""
    tokens = np.asarray(tokens, np.int64)
    return logits.max(-1) - logits[np.arange(len(tokens)), tokens]


def chosen_gap(ref_logits: list, chosen: list) -> float:
    """Widest reference gap of the tokens another computation put first
    (``chosen[i]``: one token per row of ``ref_logits[i]``)."""
    return float(max(gaps(lg, c).max() for lg, c in zip(ref_logits, chosen)))
