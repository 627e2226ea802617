"""The 95th percentile (nearest rank) of request latency over the measured
window, which no profiler runs in: from each request's due time to the
moment the client holds its answer, a shed or unanswered request counting
+inf; in ms."""
from lib.cells import nearest_rank


def read(layer):
    lat = layer.get("latency_s")
    if lat is None or len(lat) == 0:
        return None
    return 1e3 * nearest_rank(lat, 95)
