"""Kernels: the prefill attention kernels' share of their roofline:
causal attention of the real (unpadded) prompt tokens against their
context, for the server steps wholly inside the trace, over the summed
device time of the attention kernel calls inside the prefill programs
(bucketed prefill, chunked prefill into the pool, shared-prefix
suffix)."""

from chipbench import trace as tr
from chipbench.reference import least_time

PROGRAMS = ("jit_prefill_fn", "jit_chunk_paged_fn", "jit_suffix_fn")


KERNELS = ("flash_attention", "chunk_attention_paged")


def is_kernel(op_name: str) -> bool:
    return tr.kind(op_name) in KERNELS


def read(r):
    span = r.traced_span() if r.trace is not None else None
    if span is None:
        return None
    secs = tr.op_time(r.trace, PROGRAMS, is_kernel, span)
    fl = by = 0.0
    for rec, _ in r.traced:
        f, b = r.family.prefill_attn(r.dims, rec.pieces)
        fl, by = fl + f, by + b
    if secs <= 0 or fl <= 0:
        return None
    return 100.0 * least_time(fl, by, r.peaks) / secs
