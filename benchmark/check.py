"""The comparison that decides ``correct`` for a training cell.

Both sides give the loss of each followed update, the clipped gradient that
Adam was handed in the first update, the parameters' change over the
followed updates, and the env batch with its frame stacks as each followed
update left them. The reference plays the actions it is told the other side
drew: where they are not what the timed step drew, the two env batches part,
and that is held to 0; of the actions it played it says what share it would
not have drawn itself. The norms are compared leaf by leaf, by the worst
leaf: the gap between the two norms (not the norm of the difference) over
the reference's norm of that leaf or of the median leaf, whichever is
larger, because some gradients (a lone PReLU slope, a bias) are all but
zero.
"""

from __future__ import annotations

import statistics
from typing import Dict, List, Sequence

import numpy as np


def leaf_norms(tree: Dict[str, Dict[str, object]]) -> Dict[str, float]:
    return {
        f"{layer}/{leaf}": float(np.linalg.norm(np.asarray(a, np.float64)))
        for layer, leaves in sorted(tree.items())
        for leaf, a in sorted(leaves.items())
    }


def worst_leaf_gap(program: Dict[str, float], reference: Dict[str, float]):
    """-> (gap, leaf) of the worst leaf."""
    if set(program) != set(reference):
        raise ValueError(
            f"leaves differ: {sorted(set(program) ^ set(reference))}"
        )
    floor = statistics.median(reference.values())
    worst, where = 0.0, ""
    for leaf, ref in reference.items():
        gap = abs(program[leaf] - ref) / max(ref, floor, 1e-30)
        if not np.isfinite(gap):
            return float("inf"), leaf
        if gap >= worst:
            worst, where = gap, leaf
    return worst, where


def loss_gap(program: Sequence[float], reference: Sequence[float]) -> float:
    """Largest gap of a followed update's loss, over the reference's size."""
    gaps = [
        abs(p - r) / max(abs(r), 1e-6) for p, r in zip(program, reference, strict=True)
    ]
    return float(max(gaps)) if all(np.isfinite(gaps)) else float("inf")


def state_mismatch(program: Sequence, reference: Sequence):
    """Share of envs, over the followed updates, that the two sides left in
    different states. Each side: per update ``(env_state, frame_stacks)``,
    ``env_state`` a dict of arrays with the env as leading axis. A missing
    or misshapen leaf counts every env. -> (share, which leaves differed in
    how many envs)."""
    differing, total, where = 0, 0, {}
    for (p_env, p_stack), (r_env, r_stack) in zip(program, reference, strict=True):
        n = len(r_stack)
        bad = np.zeros(n, bool)
        for name, ref in dict(r_env, frames=r_stack).items():
            got = dict(p_env, frames=p_stack).get(name)
            ref = np.asarray(ref)
            if got is None or np.shape(got) != ref.shape:
                leaf_bad = np.ones(n, bool)
            else:
                leaf_bad = (np.asarray(got) != ref).reshape(n, -1).any(axis=1)
            if leaf_bad.any():
                where[name] = where.get(name, 0) + int(leaf_bad.sum())
            bad |= leaf_bad
        differing, total = differing + int(bad.sum()), total + n
    return differing / total, where


def compare(program: dict, reference: dict, limits: Dict[str, float]) -> List[dict]:
    """Each number compared beside its limit.

    ``program``/``reference``: ``losses`` (list), ``first_grad`` and
    ``delta`` (leaf -> norm), ``states`` (per followed update); the
    reference, having played the program's actions, also ``action_margin``
    and ``action_flips``. A row is ``ok`` when value <= limit."""
    parted, parted_leaves = state_mismatch(program["states"], reference["states"])
    grad, grad_leaf = worst_leaf_gap(program["first_grad"], reference["first_grad"])
    delta, delta_leaf = worst_leaf_gap(program["delta"], reference["delta"])
    rows = [
        {"number": "loss_gap", "value": loss_gap(program["losses"], reference["losses"]),
         "detail": f"program {program['losses']} reference {reference['losses']}"},
        {"number": "first_grad_norm_gap", "value": grad, "detail": grad_leaf},
        {"number": "param_delta_norm_gap", "value": delta, "detail": delta_leaf},
        {"number": "state_mismatch_share", "value": parted,
         "detail": f"envs that differ, by leaf: {parted_leaves}"},
        {"number": "action_flip_share", "value": reference["action_flips"],
         "detail": f"widest margin {reference['action_margin']:.6g}"},
    ]
    for row in rows:
        row["limit"] = limits[row["number"]]
        row["ok"] = bool(row["value"] <= row["limit"])
    return rows
