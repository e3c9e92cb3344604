"""The comparison that decides `correct`: the numbers compared, each beside
its limit. Limits are data in the cell's own file, set from readings that
PERF.md gives.
"""
import statistics


def rel_gap(program, reference):
    return abs(program - reference) / max(abs(reference), 1e-30)


def worst_leaf_gap(program, reference, skip=()):
    """The widest gap between the program's norm of a leaf and the
    reference's, measured against the reference's norm of that leaf or of
    the median leaf, whichever is larger (some gradients are all but
    zero). Returns (gap, leaf)."""
    names = [k for k in reference if k not in skip]
    floor = statistics.median(reference[k] for k in names)
    worst, at = 0.0, None
    for k in names:
        g = abs(program[k] - reference[k]) / max(reference[k], floor, 1e-30)
        if g > worst:
            worst, at = g, k
    return worst, at


def idle_leaves(grad_norms):
    """Leaves whose gradient is nought to rounding in the reference (under
    a thousandth of the median leaf's): under Adam they move by round-off
    alone, so their change is not compared."""
    floor = 1e-3 * statistics.median(grad_norms.values())
    return {k for k, v in grad_norms.items() if v < floor}


def train_numbers(program, reference):
    """{name: value} of what a training cell compares. `program` and
    `reference` are {"losses", "grad_norms", "change_norms"}."""
    out = {}
    for i, (p, r) in enumerate(zip(program["losses"], reference["losses"])):
        out[f"loss{i + 1}_gap"] = rel_gap(p, r)
    out["grad_norm_gap"], out["grad_norm_gap_at"] = worst_leaf_gap(
        program["grad_norms"], reference["grad_norms"])
    skip = idle_leaves(reference["grad_norms"])
    out["change_gap"], out["change_gap_at"] = worst_leaf_gap(
        program["change_norms"], reference["change_norms"], skip)
    return out


def judge(numbers, limits):
    """(correct, {name: {"value", "limit"}}): every number that has a limit
    must be at or under it; a number with no limit is shown and not held;
    a limit whose number is missing fails."""
    shown, ok = {}, True
    for name, value in numbers.items():
        if isinstance(value, str) or value is None:
            shown[name] = value
            continue
        limit = limits.get(name)
        shown[name] = {"value": value, "limit": limit}
        if limit is not None and not value <= limit:    # NaN fails too
            ok = False
    for name in limits:
        if name not in numbers:
            shown[name] = {"value": None, "limit": limits[name]}
            ok = False
    if not limits:
        ok = False
    return ok, shown
