"""The comparisons that decide `correct`, as plain functions of what
the timed path produced and what the plain reference produced."""
from __future__ import annotations

import statistics

import numpy as np


def relative_gap(got: float, want: float) -> float:
    return abs(got - want) / abs(want)


def worst_leaf_gap(got: dict, want: dict, leaves=None) -> float:
    """The widest gap between the program's norm of a leaf and the
    reference's, measured against the reference's norm of that leaf or
    of the median leaf, whichever is larger (some leaves are all but
    zero). A leaf the program lacks reads as norm 0."""
    floor = statistics.median(want.values())
    names = list(want) if leaves is None else list(leaves)
    return max(abs(got.get(k, 0.0) - want[k]) / max(want[k], floor)
               for k in names)


def moved_leaves(ref_grad_norms: dict) -> list:
    """Leaves whose gradient in the reference is not nought to rounding:
    at least a thousandth of the median leaf's. The others move under
    Adam by round-off alone and are left out of the change."""
    floor = 1e-3 * statistics.median(ref_grad_norms.values())
    return [k for k, g in ref_grad_norms.items() if g >= floor]


def training(checks, got: dict, want: dict, limits: dict) -> None:
    """`got` and `want` are {"losses", "grad_norms", "change_norms"} of
    the program's first steps and of the reference following them;
    `got` also has "grad_diff_norms", the norm of each leaf of the
    difference of the two first gradients. The gaps of norms catch a
    step that does other work (half a batch, a state not updated); the
    norm of the difference, against the same yardstick, catches the
    same work done in a lower precision, whose error a norm averages
    away."""
    for i, (a, b) in enumerate(zip(got["losses"], want["losses"]), 1):
        name = f"loss_gap_step{i}"
        if name in limits:
            checks.most(name, relative_gap(a, b), limits[name])
        else:
            checks.note(name, relative_gap(a, b))
    checks.most("grad_norm_gap",
                worst_leaf_gap(got["grad_norms"], want["grad_norms"]),
                limits["grad_norm_gap"])
    floor = statistics.median(want["grad_norms"].values())
    checks.most("grad_diff_gap",
                max(d / max(want["grad_norms"][k], floor)
                    for k, d in got["grad_diff_norms"].items()),
                limits["grad_diff_gap"])
    checks.most("change_norm_gap",
                worst_leaf_gap(got["change_norms"], want["change_norms"],
                               moved_leaves(want["grad_norms"])),
                limits["change_norm_gap"])


def served_gap(ref_logits: np.ndarray, tokens) -> float:
    """The widest gap by which a served token's logit lies below the
    reference's best at its position. `ref_logits` has one row for each
    served token: the reference's logits given everything before it."""
    ref_logits = np.asarray(ref_logits, np.float32)
    tokens = np.asarray(tokens)
    best = ref_logits.max(axis=-1)
    served = ref_logits[np.arange(len(tokens)), tokens]
    return float(np.max(best - served))
