"""How late the load generator submitted requests: the 95th percentile
(nearest rank) of submit time − due time over the measured window, which no
profiler runs in, in ms."""
import numpy as np

from lib.cells import nearest_rank


def read(layer):
    late = layer.get("lateness_s")
    if late is None:
        return None
    late = np.asarray(late)[np.isfinite(late)]
    return 1e3 * nearest_rank(late, 95) if late.size else None
