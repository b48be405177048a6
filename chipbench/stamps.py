"""The program's own request stamps (``ServeRequest.submit_ns``,
``admit_ns``, ``first_token_ns``, ``finish_ns``: host-clock nanoseconds,
0 until set). A program that has no such stamp gives nothing to read."""

from __future__ import annotations

from typing import List, Sequence


def gaps_ms(tracked: Sequence, window_s: float, start: str, end: str
            ) -> List[float]:
    """``end - start`` in ms for every request that arrived in the window
    ``[0, window_s)`` and carries both stamps."""
    out = []
    for t in tracked:
        if t.sreq is None or not 0.0 <= t.req.arrival_s < window_s:
            continue
        a, b = getattr(t.sreq, start, 0), getattr(t.sreq, end, 0)
        if a and b:
            out.append((b - a) / 1e6)
    return out
