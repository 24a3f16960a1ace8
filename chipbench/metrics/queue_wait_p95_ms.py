"""95th percentile of the wait from each request's due time to its
admission, taken from the engine's own ``admit`` event (host clock)."""
from harness.stats import percentile


def read(run):
    waits = [r.admitted - r.due for r in run.requests if r.admitted is not None]
    if not waits:
        return None
    return 1e3 * percentile(waits, 95)
