"""The measures ``correct`` compares training readings by."""
import numpy as np


def leaf_gaps(prog, want, only=None):
    """Per leaf, the gap between the program's norm and the reference's
    (not the norm of their difference), against the reference's norm of that
    leaf or of the median leaf, whichever is larger.  Returns the worst
    leaf's gap and the median leaf's, over the leaves in ``only`` where it is
    given; a gap that is not a number is the worst."""
    med = float(np.median([want[k] for k in want]))
    gaps = [abs(prog[k] - want[k]) / max(want[k], med)
            for k in (want if only is None else only)]
    if any(g != g for g in gaps):
        return float("nan"), float("nan")
    return float(max(gaps)), float(np.median(gaps))


def moving_leaves(grad_norms):
    """Leaves whose reference gradient is not nought to rounding: at least a
    thousandth of the median leaf's.  The others move under Adam by
    round-off alone and are left out of the parameters' change."""
    med = float(np.median(list(grad_norms.values())))
    return [k for k, g in grad_norms.items() if g >= 1e-3 * med]
