"""95th percentile, over every request due in the window, of the time
from its due time to its first token on the host; a request that never
finished counts with the time the run waited for it."""
from harness.stats import percentile


def read(run):
    if not run.requests:
        return None
    return 1e3 * percentile([r.ttft_s for r in run.requests], 95)
