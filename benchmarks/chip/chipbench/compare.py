"""The numbers that decide ``correct``, each against its limit.

Rounds 0 and 1 of a run are compared: round 0 is the first round of the
object the window drives (the same ``run_fedssl`` call, compiled
programs, cohort, batch and shards), round 1 the window's first round,
which starts from what round 0 and its calibration left. The plain
reference (``reference.RoundRef``) replays both from the same seed, and
two numbers are compared for each round i:

``loss_gap.r<i>``
    |program - reference| / |reference| of the round's mean last-step
    client loss.
``update_gap.r<i>``
    The server's update in that round, as the aggregation (and, in
    LW-FedSSL, the calibration) applies it: for each leaf of the online
    model, the norm of (after the round - before it), each side from its
    own state. The number is the worst leaf's |program norm - reference
    norm|, over the larger of that leaf's reference norm and the median
    leaf's. A leaf whose first gradient of the round in the reference is
    under ``STILL`` of the median leaf's is nought to rounding (the last
    projection BatchNorm's bias, which the predictor's BatchNorm cancels)
    and moves under AdamW by round-off alone; such leaves are left out, by
    that rule on the reference's gradient and never by name.
"""
from __future__ import annotations

import numpy as np

STILL = 1e-3


def leaf_paths(tree):
    """{"enc/blocks/attn/wq": host array, ...} for a nested dict/list
    tree of arrays."""
    out = {}

    def walk(node, prefix):
        if isinstance(node, dict):
            for k in sorted(node):
                walk(node[k], prefix + (str(k),))
        elif isinstance(node, (list, tuple)):
            for i, v in enumerate(node):
                walk(v, prefix + (str(i),))
        else:
            out["/".join(prefix)] = node
    walk(tree, ())
    return out


def change_norms(after, before):
    """Per-leaf L2 norm of ``after - before`` in float64."""
    a, b = leaf_paths(after), leaf_paths(before)
    if set(a) != set(b):
        raise ValueError(f"leaf sets differ: {sorted(set(a) ^ set(b))[:6]}")
    return {p: float(np.linalg.norm(np.asarray(a[p], np.float64)
                                    - np.asarray(b[p], np.float64)))
            for p in a}


def update_gap(prog_norms, ref_norms, ref_grads):
    """(gap, worst leaf, leaves compared, leaves left out)."""
    gmed = float(np.median(list(ref_grads.values())))
    used = [p for p, g in ref_grads.items() if g >= STILL * gmed]
    med = float(np.median([ref_norms[p] for p in used]))
    gaps = {p: abs(prog_norms[p] - ref_norms[p]) / max(ref_norms[p], med)
            for p in used}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst, len(used), len(ref_norms) - len(used)


def loss_gap(prog_loss, ref_loss):
    return abs(prog_loss - ref_loss) / abs(ref_loss)


def judge(numbers: dict, limits: dict):
    """(correct, {name: {"value", "limit"}}). A number that is missing or
    not finite fails."""
    checks, ok = {}, True
    for name, limit in limits.items():
        v = numbers.get(name)
        good = v is not None and np.isfinite(v) and v <= limit
        ok = ok and bool(good)
        checks[name] = {"value": v, "limit": limit}
    return ok, checks
