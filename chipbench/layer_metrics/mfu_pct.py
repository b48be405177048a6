"""Whole step: the server step's share of the chip's peak. Over the
window's server steps, the sum of each step's least time (the model
operations of its real tokens over peak FLOP/s, against the weights read
once plus the live K/V over HBM bandwidth, whichever is larger) over the
sum of the steps' wall times on the host clock. A kernel taken off the
path leaves this bound in place."""

from chipbench.reference import least_time


def read(r):
    least = wall = 0.0
    for s in r.steps:
        rows = len(s.decode_ctx) + s.first_tokens
        fl, by = r.family.step_work(r.dims, s.decode_ctx, s.pieces, rows)
        if fl <= 0:
            continue
        least += least_time(fl, by, r.peaks)
        wall += s.t1 - s.t0
    return 100.0 * least / wall if wall > 0 else None
