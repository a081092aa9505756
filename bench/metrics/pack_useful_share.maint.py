"""Real (column, row group) cells over padded B x R cells, in %.

Summed over every pack `EstimationEngine.estimate` dispatched in the
window; a column's real cells are its row groups (``n_groups``).
"""

import numpy as np


def read(ctx):
    real = padded = 0
    for _, b, r, n_groups in ctx["dispatches"]:
        real += int(np.asarray(n_groups).sum())
        padded += b * r
    if not padded:
        return None
    return 100.0 * real / padded
