"""Migration: 90th percentile, over the requests a reclaim moved to
another replica, of each one's longest gap between consecutive tokens on
the host clock (the wait its move cost it)."""

from chipbench.stats import percentile


def read(r):
    gaps = [max(b - a for a, b in zip(t.stamps, t.stamps[1:]))
            for t in r.tracked
            if t.sreq is not None and t.sreq.migrations > 0
            and len(t.stamps) > 1]
    p = percentile(gaps, 90)
    return None if p is None else 1000.0 * p
