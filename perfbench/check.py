"""The comparison that decides ``correct``: what the timed path produced
against the plain reference, number by number, each with a limit of its own
from ``limits/<cell>.json`` (set from chip readings; ``PERF.md`` has them).  No JAX.
"""

from __future__ import annotations

import statistics
import sys


def leaf_gaps(program: dict, reference: dict) -> dict:
    """name -> the gap of that leaf, as ``worst_leaf_gap`` measures it."""
    floor = statistics.median(reference.values())
    return {n: abs(program[n] - r) / max(r, floor, 1e-30)
            for n, r in reference.items()}


def worst_leaf_gap(program: dict, reference: dict) -> tuple[float, str]:
    """The widest gap between the program's norm of a leaf and the
    reference's (not the norm of their difference), against the reference's
    norm of that leaf or of the median leaf, whichever is larger: some
    gradients are all but zero."""
    if set(program) != set(reference):
        raise ValueError("leaf names differ: "
                         f"{sorted(set(program) ^ set(reference))[:6]}")
    floor = statistics.median(reference.values())
    worst, where = 0.0, ""
    for name, ref in reference.items():
        gap = abs(program[name] - ref) / max(ref, floor, 1e-30)
        if gap > worst:
            worst, where = gap, name
    return worst, where


def train_numbers(program: dict, reference: dict) -> dict:
    """``{name: value}`` for a training cell: each step's loss against the
    reference's, the first gradient as Adam got it, the parameters' change."""
    out = {}
    for i, (a, b) in enumerate(zip(program["losses"], reference["losses"])):
        out[f"loss_gap_step{i + 1}"] = abs(a - b) / abs(b)
    out["first_grad_gap"], g_leaf = worst_leaf_gap(
        program["first_grad_norm"], reference["first_grad_norm"])
    # The parameters' change is compared by the MEDIAN leaf, not the worst:
    # the key bias of every attention layer has a gradient of exactly zero
    # (a shift of all keys leaves the softmax alone), Adam divides that
    # rounding noise by its own size, and the leaf's change then reads 0.2
    # apart on sound runs (PERF.md, limits).  A step that returns its state
    # unchanged still reads 1 on every leaf.
    change = sorted(leaf_gaps(program["param_change_norm"],
                              reference["param_change_norm"]).items(),
                    key=lambda kv: -kv[1])
    out["param_change_gap"] = statistics.median(v for _, v in change)
    grads = leaf_gaps(program["first_grad_norm"],
                      reference["first_grad_norm"])
    total = lambda d: sum(v * v for v in d.values()) ** 0.5  # noqa: E731
    return out, {"first_grad_leaf": g_leaf,
                 "first_grad_median_leaf_gap": statistics.median(
                     grads.values()),
                 "first_grad_global_gap": abs(
                     total(program["first_grad_norm"])
                     / total(reference["first_grad_norm"]) - 1.0),
                 "param_change_worst_leaves": change[:3]}


def verdict(numbers: dict, limits: dict | None) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})``.  A number with no limit
    cannot pass: a cell is not correct until its limits were set."""
    compared = {}
    ok = bool(numbers)
    for name, value in numbers.items():
        limit = (limits or {}).get(name)
        compared[name] = {"value": value, "limit": limit}
        if limit is None or not (value <= limit):
            ok = False
    return ok, compared


def print_compared(compared: dict, correct: bool) -> None:
    """Every number compared beside its limit, as the last lines on
    standard error."""
    for name, c in compared.items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"check correct = {correct}", file=sys.stderr, flush=True)
