"""The comparison that decides ``correct``: every number beside its limit."""
import math
from statistics import median


def leaf_gaps(prog, ref, skip=()):
    """{leaf: gap} between the program's norm of a leaf and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some leaves' norms are all but zero)."""
    floor = median(ref.values())
    return {name: abs(prog[name] - r) / max(r, floor)
            for name, r in ref.items() if name not in skip}


def worst_leaf_gap(prog, ref, skip=()):
    """(widest gap over the leaves, that leaf); NaN counts as the widest."""
    worst, where = 0.0, ""
    for name, gap in leaf_gaps(prog, ref, skip).items():
        if not gap <= worst:
            worst, where = gap, name
    return worst, where


def sum_gap_rms(prog_sums, ref_sums, ref_norms, skip=()):
    """Root mean square over the leaves of (the program's projection of a
    leaf - the reference's) over the reference's norm of that leaf (or of
    the median leaf, whichever is larger). The projection is on one fixed
    random direction of +-1, so this reads the size of the element-wise
    error itself, where a norm moves only with the error's square."""
    floor = median(ref_norms.values())
    sq = [((prog_sums[k] - r) / max(ref_norms[k], floor)) ** 2
          for k, r in ref_sums.items() if k not in skip]
    total = sum(sq)
    return math.sqrt(total / len(sq)) if total == total else math.inf


def idle_gradient_leaves(ref_grad_norms):
    """Leaves whose reference gradient is under a thousandth of the median
    leaf's: under Adam they move by round-off alone, so their change is not
    compared."""
    floor = 1e-3 * median(ref_grad_norms.values())
    return {k for k, v in ref_grad_norms.items() if v < floor}


class Checks:
    """Numbers compared, each with its limit; ``ok`` when none is over."""

    def __init__(self):
        self.rows = []

    def add(self, name, value, limit):
        self.rows.append((name, float(value), float(limit)))

    @property
    def ok(self):
        return bool(self.rows) and all(
            math.isfinite(v) and v <= lim for _, v, lim in self.rows)

    def as_dict(self):
        return {n: {"value": v, "limit": lim} for n, v, lim in self.rows}

    def lines(self):
        return [f"check {n}: {v:.6g} (limit {lim:.6g}) "
                f"{'ok' if math.isfinite(v) and v <= lim else 'OVER'}"
                for n, v, lim in self.rows]
