"""95th percentile of every gap between consecutive tokens of the
window's requests, as the streaming client saw them on the host."""
from harness.stats import percentile


def read(run):
    gaps = [g for r in run.requests for g in r.gaps_s()]
    if not gaps:
        return None
    return 1e3 * percentile(gaps, 95)
