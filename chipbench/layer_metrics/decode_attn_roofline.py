"""Kernels: the decode attention kernel's share of its roofline. The
least time of the useful work (each live row's K/V up to its context,
plus its q and o, over HBM bandwidth, against the operations over peak,
whichever is larger) over the summed device time of the attention
kernel calls inside the decode program, for the server steps wholly
inside the trace. The work comes from the live contexts the harness
recorded, never from the slots or positions the kernel walks."""

from chipbench import trace as tr
from chipbench.reference import least_time

PROGRAMS = ("jit_decode_fn",)


KERNELS = ("decode_attention_paged",)


def is_kernel(op_name: str) -> bool:
    return tr.kind(op_name) in KERNELS


def read(r):
    span = r.traced_span() if r.trace is not None else None
    if span is None:
        return None
    secs = tr.op_time(r.trace, PROGRAMS, is_kernel, span)
    fl = by = 0.0
    for rec, _ in r.traced:
        f, b = r.family.decode_attn(r.dims, rec.decode_ctx)
        fl, by = fl + f, by + b
    if secs <= 0 or fl <= 0:
        return None
    return 100.0 * least_time(fl, by, r.peaks) / secs
