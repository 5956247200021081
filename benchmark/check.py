"""The comparison that decides ``correct``: numbers, each beside its
limit."""
from __future__ import annotations

import statistics

import numpy as np


def leaf_norms(tree):
    """{leaf path: l2 norm} of a {name: {weight, bias}} tree."""
    return {f"{name}/{part}": float(np.linalg.norm(np.asarray(v, np.float64)))
            for name, leaf in tree.items() for part, v in leaf.items()}


def worst_leaf_gap(program_norms, reference_norms, skip=()):
    """The largest gap between the program's norm and the reference's over
    the leaves, measured against the reference's norm of that leaf or of
    the median leaf, whichever is larger.  Returns (gap, leaf)."""
    median = statistics.median(reference_norms.values())
    worst, where = 0.0, None
    for leaf, ref in reference_norms.items():
        if leaf in skip:
            continue
        gap = abs(program_norms[leaf] - ref) / max(ref, median)
        if gap > worst or where is None:
            worst, where = gap, leaf
    return worst, where


def leaf_gap_table(program_norms, reference_norms, top=5):
    """The leaves with the widest gaps, for the lines on standard error:
    [(leaf, gap, program norm, reference norm)]."""
    median = statistics.median(reference_norms.values())
    rows = [(leaf, abs(program_norms[leaf] - ref) / max(ref, median),
             program_norms[leaf], ref)
            for leaf, ref in reference_norms.items()]
    return sorted(rows, key=lambda r: -r[1])[:top]


def tree_relative_error(program_tree, reference_tree):
    """The norm of the difference of two {name: {weight, bias}} trees over
    the reference's norm, all leaves taken as one vector."""
    diff = ref = 0.0
    for name, leaf in reference_tree.items():
        for part, r in leaf.items():
            r = np.asarray(r, np.float64)
            diff += float(np.sum(
                (np.asarray(program_tree[name][part], np.float64) - r) ** 2))
            ref += float(np.sum(r ** 2))
    return (diff / ref) ** 0.5


def negligible_gradient_leaves(reference_grad_norms):
    """Leaves whose reference gradient is under a thousandth of the median
    leaf's: they move by round-off alone and are left out of the change."""
    median = statistics.median(reference_grad_norms.values())
    return {leaf for leaf, n in reference_grad_norms.items()
            if n < 1e-3 * median}


def verdict(checks):
    """checks: [(name, value, limit)] -> (correct, {name: {value, limit}})."""
    table, ok = {}, True
    for name, value, limit in checks:
        value = float(value)
        passed = bool(np.isfinite(value)) and value <= limit
        ok = ok and passed
        table[name] = {"value": value, "limit": limit}
    return ok, table
