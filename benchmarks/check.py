"""The comparison that decides ``correct``.

The program's first steps (the compiled step and state that the window then
drives, at the window's batch) against the plain float32 reference that
followed the same steps from the same seed.  Three numbers, each with a
limit of its own from the cell's traffic file (``limits``; PERF.md gives the
readings each was set from):

``loss_gap``        the widest |program - reference| over the steps' losses.
                    At seeded weights the loss hardly moves whatever is wrong
                    with the arithmetic; it is there for a step that is fed
                    other rows than it was given.
``grad_norm_gap``   per leaf, the first gradient's norm as the optimizer got
                    it (Adam's first moment after one step, over 1 - b1)
                    against the reference's: |program - reference| over the
                    reference's norm of that leaf or of the median leaf,
                    whichever is larger; the worst leaf.  This is the number
                    that computing below bfloat16 fails.
``delta_norm_gap``  the same measure on the norm of each leaf's change after
                    the steps; there for a step that returns its state
                    unchanged or applies an update twice.  Leaves whose
                    gradient is zero but for rounding (a key bias: softmax
                    does not see it) are left out, since Adam scales that
                    noise up to a full-sized step on either side: a leaf
                    counts as such when the reference's first gradient norm
                    is under a thousandth of the median leaf's.
"""

from __future__ import annotations

import math
import statistics

NUMBERS = ("loss_gap", "grad_norm_gap", "delta_norm_gap")


NOISE_SHARE = 1e-3


def _flat(parties: dict) -> dict:
    return {f"{p}/{k}": v for p, leaves in parties.items() for k, v in leaves.items()}


def noise_leaves(reference_grad_norms: dict) -> set:
    ref = _flat(reference_grad_norms)
    floor = NOISE_SHARE * statistics.median(ref.values())
    return {leaf for leaf, norm in ref.items() if norm < floor}


def worst_leaf_gap(program: dict, reference: dict, skip=frozenset()) -> tuple:
    """(gap, leaf) over ``{party: {leaf: norm}}`` of both sides."""
    ref, got = _flat(reference), _flat(program)
    if ref.keys() != got.keys():
        raise ValueError("the program and the reference hold different leaves: "
                         f"{sorted(ref.keys() ^ got.keys())[:6]}")
    median = statistics.median(ref.values())
    worst, where = 0.0, ""
    for leaf, want in ref.items():
        if leaf in skip:
            continue
        gap = abs(got[leaf] - want) / max(want, median)
        if not gap <= worst:        # a NaN is the worst there is
            worst, where = (gap if gap == gap else math.inf), leaf
    return worst, where


def readings(program: dict, reference: dict) -> dict:
    loss_gap = max(abs(a - b) if math.isfinite(a) else math.inf
                   for got, want in zip(program["losses"], reference["losses"])
                   for a, b in zip(got, want))
    grad, grad_leaf = worst_leaf_gap(program["grad_norms"], reference["grad_norms"])
    delta, delta_leaf = worst_leaf_gap(program["delta_norms"], reference["delta_norms"],
                                       skip=noise_leaves(reference["grad_norms"]))
    return {"loss_gap": (loss_gap, "widest over steps and clients"),
            "grad_norm_gap": (grad, grad_leaf),
            "delta_norm_gap": (delta, delta_leaf)}


def verdict(numbers: dict, limits: dict, log=print) -> bool:
    """Print each number beside its limit; true if every one is within."""
    ok = True
    for name, (value, where) in numbers.items():
        limit = limits[name]
        within = value <= limit
        ok = ok and within
        log(f"check {name} = {value:.6g} (limit {limit:g}) "
            f"{'ok' if within else 'FAILED'} [{where}]")
    return ok
