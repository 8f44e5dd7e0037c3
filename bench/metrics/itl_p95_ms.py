"""itl_p95_ms: 95th percentile over every gap between consecutive output
tokens of the window's requests (all gaps, not per-request means)."""
import numpy as np

from bench.traffic import percentile


def read(run):
    gaps = [np.diff(r.times) for r in run.recs if len(r.times) > 1]
    if not gaps:
        return None
    return percentile(1e3 * np.concatenate(gaps), 95)
