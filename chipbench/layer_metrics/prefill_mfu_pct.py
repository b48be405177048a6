"""Whole step, prefill side: the share of the chip's peak over the
window's server steps that prefilled prompt tokens. The sum of each such
step's least time (its model operations over peak FLOP/s, against the
weights read once plus the live K/V over HBM bandwidth, whichever is
larger) over the sum of those steps' wall times on the host clock. The
first token of a request waits on these steps; a prefill kernel taken
off the path leaves this bound in place."""

from chipbench.reference import least_time


def read(r):
    least = wall = 0.0
    for s in r.steps:
        if not s.pieces:
            continue
        rows = len(s.decode_ctx) + s.first_tokens
        fl, by = r.family.step_work(r.dims, s.decode_ctx, s.pieces, rows)
        least += least_time(fl, by, r.peaks)
        wall += s.t1 - s.t0
    return 100.0 * least / wall if wall > 0 else None
