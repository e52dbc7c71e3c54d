"""The comparison that decides ``correct`` for a training cell.

Both sides start from the same seeded parameters and see the same first
batches. The program's side is what the entry captured while the timed
object ran its first steps through the window's own call; the reference's
side is `reference.follow`. The numbers compared; which of them a cell
judges, and by what limit, is in ``benchmark/limits/<cell>.json``, and
PERF.md section 2 gives the readings each limit was set from:

  loss_<k>       |program's loss - reference's| over the reference's, step k
  first_probs    the first step's softmax outputs: the norm of the two
                 sides' difference over the reference's norm. The forward
                 pass alone, before any update.
  first_grad     the first gradient as the optimizer got it, worked out
                 from the momentum after one step (m1 = -lr (g + wd w0)),
                 by the WORST leaf: the gap between the two sides' NORMS of
                 a leaf, over the reference's norm of that leaf or of the
                 median leaf, whichever is larger. Only where the entry
                 could read the state after one step (one batch to a
                 dispatch).
  first_grad_median   the same gaps, the MEDIAN leaf's
  change         the parameters' change over the steps followed, by the
                 worst leaf, the same measure. Leaves whose first gradient
                 in the reference is under a thousandth of the median
                 leaf's are left out: they move by round-off alone.
  change_median  the same gaps, the median leaf's
"""
import numpy as np

from benchmark import reference


def _norm(x):
    return float(np.linalg.norm(np.asarray(x, np.float32).ravel()))


def _leaf_gaps(prog, ref, skip=()):
    """((worst gap, its leaf), (median gap, None)) of the two sides' leaf
    norms."""
    floor = float(np.median([ref[n] for n in ref]))
    gaps = {}
    for name, want in ref.items():
        if name not in skip:
            gap = abs(prog[name] - want) / max(want, floor, 1e-30)
            gaps[name] = gap if np.isfinite(gap) else float("inf")
    leaf = max(gaps, key=gaps.get)
    return (gaps[leaf], leaf), (float(np.median(list(gaps.values()))), None)


def first_gradient_norms(first_mom, w0, optimizer):
    """{leaf: norm of g} with g = -m1 / lr - wd w0 where decay applies."""
    lr, wd = optimizer["learning_rate"], optimizer["wd"]
    out = {}
    for name, m in first_mom.items():
        g = -np.asarray(m, np.float32) / np.float32(lr)
        if reference.decays(name):
            g = g - np.float32(wd) * np.asarray(w0[name], np.float32)
        out[name] = _norm(g)
    return out


def change_norms(params, w0):
    return {n: _norm(np.asarray(params[n], np.float32)
                     - np.asarray(w0[n], np.float32)) for n in w0}


def numbers(prog, ref, w0, optimizer):
    """{name: (value, worst leaf or None)} of every number compared.
    ``prog`` and ``ref`` hold ``losses``, ``first_probs``, ``first_mom``
    (None where it could not be read) and ``params``; ``w0`` the starting
    parameters."""
    out = {}
    for k, (got, want) in enumerate(zip(prog["losses"], ref["losses"]), 1):
        gap = abs(got - want) / max(abs(want), 1e-6)
        out["loss_%d" % k] = (gap if np.isfinite(gap) else float("inf"),
                              None)
    if prog.get("first_probs") is not None:
        want = np.asarray(ref["first_probs"], np.float32)
        gap = _norm(np.asarray(prog["first_probs"], np.float32) - want) \
            / _norm(want)
        out["first_probs"] = (gap if np.isfinite(gap) else float("inf"), None)
    ref_grad = first_gradient_norms(ref["first_mom"], w0, optimizer)
    if prog.get("first_mom") is not None:
        out["first_grad"], out["first_grad_median"] = _leaf_gaps(
            first_gradient_norms(prog["first_mom"], w0, optimizer), ref_grad)
    floor = 1e-3 * float(np.median(list(ref_grad.values())))
    still = {n for n, g in ref_grad.items() if g < floor}
    out["change"], out["change_median"] = _leaf_gaps(
        change_norms(prog["params"], w0), change_norms(ref["params"], w0),
        skip=still)
    return out


def judge(values, limits):
    """(correct, {name: {"value", "limit"}}, lines) for the numbers
    compared. Every limit names a number that was compared; a number with
    no limit is reported and not judged (PERF.md names each such number)."""
    missing = [n for n in limits if n not in values]
    if missing:
        raise KeyError("limits for numbers that were not compared: %s"
                       % missing)
    correct, table, lines = True, {}, []
    for name, (value, leaf) in values.items():
        limit = limits.get(name)
        ok = limit is None or value <= limit
        correct = correct and ok
        table[name] = {"value": value, "limit": limit}
        lines.append("compared %s = %.6g  limit %s%s%s" % (
            name, value, "none" if limit is None else "%.6g" % limit,
            "" if leaf is None else "  worst leaf %s" % leaf,
            "" if ok else "  FAILED"))
    return correct, table, lines
