"""Two more numbers of ``correct`` for a token-sequence policy with routed
experts, beside ``benchmark/check.py``'s five.

Both are read on the learner's own forward (the policy's unroll, at the
weights the run starts from) over the first chunk of the first followed
update, against the reference's forward over the same tokens:

- ``route_flip_share``: the share of (token, expert layer) pairs whose set
  of chosen experts differs. The router picks the top k of 32 float32
  scores; two of them within a rounding error of each other near the k-th
  place flip under bfloat16 operands upstream. So flips are expected, few,
  and counted: a fault (a wrong router, a dropped bias) flips many.
- ``logit_gap``: the largest gap between the two sides' logits, over the
  reference's largest logit, over every token. A flipped choice moves its
  token, and through the operators the tokens after it, by a whole expert;
  so the reference computes WITH the routes the program chose (as it plays
  the actions it is handed) and says beside them what it would have chosen
  at each point, which is what the flips are counted from. The two sides
  then compute one function, and the gap is rounding alone. Logits, not
  sampled tokens: with seeded weights the largest logit changes on rounding.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

NUMBERS = ("logit_gap", "route_flip_share")


def route_flips(program_routes, reference_routes) -> np.ndarray:
    """bool [layers, B, T]: the two sets of chosen experts differ."""
    p = np.sort(np.asarray(program_routes), axis=-1)
    r = np.sort(np.asarray(reference_routes), axis=-1)
    if p.shape != r.shape:
        raise ValueError(f"routes differ in shape: {p.shape} against {r.shape}")
    return (p != r).any(axis=-1)


def compare(program: dict, reference: dict, limits: Dict[str, float]) -> List[dict]:
    """``program``/``reference``: ``logits`` [B, T, ids] and ``routes``
    [layers, B, T, k]. Rows as ``check.compare``'s."""
    flips = route_flips(program["routes"], reference["routes"])
    ref = np.asarray(reference["logits"], np.float32)
    gap = np.abs(np.asarray(program["logits"], np.float32) - ref).max(axis=-1)
    scale = float(np.abs(ref).max())
    worst = float(gap.max()) / scale if np.isfinite(gap).all() else float("inf")
    rows = [
        {"number": "logit_gap", "value": worst,
         "detail": f"median token {float(np.median(gap)) / scale:.5g}; largest "
                   f"reference logit {scale:.5g}; worst token (env, step) "
                   f"{tuple(int(i) for i in np.unravel_index(gap.argmax(), gap.shape))}"},
        {"number": "route_flip_share", "value": float(flips.mean()),
         "detail": "by layer " + " ".join(f"{x:.5f}" for x in flips.mean(axis=(1, 2)))},
    ]
    for row in rows:
        row["limit"] = limits[row["number"]]
        row["ok"] = bool(row["value"] <= row["limit"])
    return rows
