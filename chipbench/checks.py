"""The comparisons that decide `correct`: what the timed path produced
against the plain reference. Each returns {name: number}; the harness sets
each number beside the limit that the configuration's file states."""
from __future__ import annotations

# a leaf whose first gradient in the reference is under this share of the
# median leaf's moves by round-off alone, and is left out of the change
QUIET_LEAF = 1e-3


def leaf_norms(tree, names):
    import numpy as np
    return np.array([float(np.linalg.norm(
        np.asarray(tree[n], np.float64).ravel())) for n in names])


def _leaf_gaps(got, want, keep=None):
    """|‖got‖ - ‖want‖| of every leaf, against the reference's norm of
    that leaf or of the median leaf, whichever is larger -> (worst,
    median over the leaves)."""
    import numpy as np
    scale = np.maximum(want, np.median(want))
    gap = np.abs(got - want) / scale
    if keep is not None:
        gap = gap[keep]
    return float(gap.max()), float(np.median(gap))


def training(losses, first_grad, change, ref_losses, ref_first_grad,
             ref_change):
    """losses of the first steps; the first gradient as the optimizer got
    it and the parameters' change after those steps, as {leaf: array}."""
    import numpy as np
    names = sorted(ref_first_grad)
    g, g_ref = leaf_norms(first_grad, names), leaf_norms(ref_first_grad, names)
    d, d_ref = leaf_norms(change, names), leaf_norms(ref_change, names)
    moved = g_ref >= QUIET_LEAF * np.median(g_ref)
    out = {}
    out["first_grad_worst_leaf_gap"], out["first_grad_median_leaf_gap"] = \
        _leaf_gaps(g, g_ref)
    out["change_worst_leaf_gap"], out["change_median_leaf_gap"] = \
        _leaf_gaps(d, d_ref, keep=moved)
    return out


def loss_gaps(losses, ref_losses):
    """Relative gap of each step's loss; reported, not compared: neither
    the control nor a fault reads three times what sound runs read (PERF.md
    section 6, PR 23)."""
    return [abs(a - b) / abs(b) for a, b in zip(losses, ref_losses)]


def served(gaps):
    """gaps: for every served token of the sample, how far its logit lies
    below the reference's best at its position. The widest gap swings by
    its nature (an extreme of some 1500 values) and parts a sound run from
    an altered token; the gap that 99% of the tokens stay within is steady
    and parts bf16 from the int8 control."""
    import numpy as np
    return {"served_logit_gap_p99": float(np.quantile(gaps, 0.99)),
            "served_logit_gap": float(np.max(gaps))}
