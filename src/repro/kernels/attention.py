"""GQA attention Pallas TPU kernels: one online-softmax body for every
attention read path of the model.

A C-token chunk of queries at absolute positions ``bases[b] + [0, C)``
attends a KV history under causal (and optional sliding-window) masking.
The KV history is either

* contiguous, ``(B, S, nkv, d)`` (a prompt's fresh K/V, or a linear
  cache), walked in ``block_kv``-token blocks; or
* a block pool, ``(n_blocks, block, nkv, d)`` plus a per-row block table
  ``(B, max_blocks)`` read through scalar prefetch: the BlockSpec index
  map reads ``tbl[b, ik]``, so each grid step DMAs exactly the pool block
  backing virtual positions ``[ik*block, (ik+1)*block)`` of row ``b`` and
  no gathered page view is ever materialized. The paged entry points also
  take the model's stacked pool ``(L, n_blocks, block, nkv, d)`` with a
  ``layer`` index, a scalar-prefetch operand too, and read that layer
  where it lies: the layer loop never slices a layer's pool out.

Four entry points are cases of that one body: ``flash_attention``
(prefill, bases 0, optionally non-causal), ``chunk_attention`` /
``chunk_attention_paged`` (chunked prefill and the prefix-share suffix
path, scalar or per-row bases) and ``decode_attention`` (C=1, bases = the
current token's position). ``decode_attention_paged`` has a kernel of
its own: one grid step per row, whose live pages (its length comes from
its position and block table) a loop copies from the pool in HBM, many
blocks per double-buffered DMA step, so no grid step is spent on a block
past a row's position or on a dead row. A pool the compiler cannot copy
that way (head size not 128, float16, odd bfloat16 KV head count) takes
the block walk of the body above.

Layout rules of the TPU compiler shape the specs. A block's last two dims
must be multiples of (8, 128) or span the whole array, so every block
spans all heads ``(…, nh, d)`` / ``(…, nkv, d)`` and the kernel loads one
head at a time (a strided sublane load), while the token axes stay free
to tile at any size. The output is written head-major and transposed
back by the wrapper. Scalars (bases, block tables) live in SMEM via
scalar prefetch; the running max and denominator live in lane-broadcast
``(rows, 128)`` VMEM scratch, never as scalar stores into VMEM.

The body's grid is ``(B, C/block_q, n_kv_blocks)``; the KV axis is
innermost and sequential. KV blocks no query of the tile can see (past
the causal horizon, before the window) are skipped: their index map
repeats the nearest visible block, so the pipeline issues no new DMA,
and the body is predicated off.

Debug ``probe`` mode (KV sanitizer): an extra ``(B, 1, 128)`` output
carries the max |K|/|V| magnitude seen at *readable* positions; the ops
wrapper checkifies it against ``KV_POISON`` so a stale block-table entry
fires at the op itself instead of only via final byte-identity.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
DECODE_TOKENS = 512      # KV tokens one DMA step of paged decode aims at
DECODE_VMEM = 8 << 20    # VMEM bytes for its double-buffered K/V slabs


def _visible_blocks(base, iq, *, causal: bool, window: Optional[int],
                    block_q: int, block_kv: int, n_kv_blocks: int):
    """(first, last) KV block any query of q-tile ``iq`` can see."""
    lo = base + iq * block_q                  # first query position
    hi = lo + block_q - 1                     # last query position
    last = (jnp.minimum(hi // block_kv, n_kv_blocks - 1) if causal
            else n_kv_blocks - 1)
    first = (jnp.maximum(lo - window + 1, 0) // block_kv
             if window is not None else 0)
    return first, last


def _attn_kernel(*refs, n_prefetch: int, scale: float, causal: bool,
                 window: Optional[int], nh: int, group: int, block_q: int,
                 block_kv: int, n_kv_blocks: int, probe: bool):
    bases_ref = refs[n_prefetch - 1]          # (block table,) bases
    q_ref, k_ref, v_ref, o_ref = refs[n_prefetch:n_prefetch + 4]
    if probe:
        p_ref, m_scr, l_scr, acc_scr = refs[n_prefetch + 4:]
    else:
        p_ref, (m_scr, l_scr, acc_scr) = None, refs[n_prefetch + 4:]
    ib = pl.program_id(0)
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    if probe:
        @pl.when((ik == 0) & (iq == 0))
        def _init_probe():
            p_ref[...] = jnp.zeros_like(p_ref)

    base = bases_ref[ib]
    first, last = _visible_blocks(base, iq, causal=causal, window=window,
                                  block_q=block_q, block_kv=block_kv,
                                  n_kv_blocks=n_kv_blocks)

    @pl.when((ik >= first) & (ik <= last))
    def _step():
        # ik indexes VIRTUAL blocks of this row; in the paged layout the
        # pool block holding them was selected by the index map
        q_pos = (base + iq * block_q
                 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0))
        k_pos = ik * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1)
        mask = (k_pos <= q_pos) if causal else jnp.ones(
            (block_q, block_kv), jnp.bool_)
        if window is not None:
            mask &= k_pos > q_pos - window

        if probe:
            # a key row is readable iff some query of this tile sees it
            kp = ik * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_kv, 1), 0)
            lo = base + iq * block_q
            readable = (kp <= lo + block_q - 1) if causal else kp >= 0
            if window is not None:
                readable &= kp > lo - window
            mag = jnp.zeros((block_kv, 1), jnp.float32)
            for h in range(nh // group):
                for ref in (k_ref, v_ref):
                    mag = jnp.maximum(mag, jnp.max(
                        jnp.abs(ref[:, h, :].astype(jnp.float32)), axis=1,
                        keepdims=True))
            worst = jnp.max(jnp.where(readable, mag, 0.0))
            p_ref[...] = jnp.maximum(p_ref[...], worst)

        for ih in range(nh):
            h = ih // group
            q = q_ref[:, ih, :]                              # (bq, d)
            k = k_ref[:, h, :]                               # (bkv, d)
            v = v_ref[:, h, :]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(mask, s * scale, NEG_INF)
            m_prev = m_scr[ih]                               # (bq, LANES)
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_next)
            p = jnp.where(mask, jnp.exp(s - m_next[:, :1]), 0.0)
            l_scr[ih] = alpha * l_scr[ih] + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[ih] = acc_scr[ih] * alpha[:, :1] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_scr[ih] = m_next

    @pl.when(ik == n_kv_blocks - 1)
    def _out():
        for ih in range(nh):
            denom = jnp.maximum(l_scr[ih][:, :1], 1e-30)
            o_ref[ih] = (acc_scr[ih] / denom).astype(o_ref.dtype)


def _stacked(cache_k: jax.Array, cache_v: jax.Array, layer):
    """(stacked pool K, V, layer index as a (1,) int32 prefetch operand):
    one layer's pool ``(n_blocks, …)`` becomes a stack of one (a free
    reshape), read at layer 0."""
    if layer is None:
        cache_k, cache_v, layer = cache_k[None], cache_v[None], 0
    return cache_k, cache_v, jnp.asarray(layer, jnp.int32).reshape(1)


def _attend(q: jax.Array, k: jax.Array, v: jax.Array, bases,
            block_tbl: Optional[jax.Array], *, name: str, causal: bool,
            window: Optional[int], block_q: int, block_kv: int, probe: bool,
            interpret: bool, layer=None):
    """q: (B,C,nh,d); k/v: contiguous (B,S,nkv,d) when ``block_tbl`` is
    None, else a pool walked through ``block_tbl`` (B, max_blocks): the
    stacked pool (L, n_blocks, block, nkv, d) read at ``layer``, or one
    layer's pool (n_blocks, block, nkv, d) with ``layer`` None. Returns o,
    or (o, probe_max). ``name`` names the kernel's custom call: the
    profiler trace and the benchmark's roofline readers find the kernel
    by it."""
    b, c, nh, d = q.shape
    if block_tbl is not None:
        k, v, layer = _stacked(k, v, layer)
    nkv = k.shape[-2]
    assert nh % nkv == 0, (nh, nkv)
    group = nh // nkv
    # token axes are leading block dims: any size tiles, so a length that a
    # block does not divide becomes one whole-length block
    block_q = min(block_q, c)
    if c % block_q:
        block_q = c
    if block_tbl is None:
        s = k.shape[1]
        block_kv = min(block_kv, s)
        if s % block_kv:
            block_kv = s
        nk = s // block_kv
    else:
        block_kv = k.shape[2]
        nk = block_tbl.shape[1]
    nq = c // block_q
    bases = jnp.asarray(bases, jnp.int32)
    if bases.ndim == 0:
        bases = jnp.broadcast_to(bases, (b,))
    visible = functools.partial(_visible_blocks, causal=causal,
                                window=window, block_q=block_q,
                                block_kv=block_kv, n_kv_blocks=nk)

    def kv_block(ib, iq, ik, bases_ref):
        first, last = visible(bases_ref[ib], iq)
        return jnp.minimum(jnp.maximum(ik, first), last)

    if block_tbl is None:
        prefetch = (bases,)

        def kv_map(ib, iq, ik, bases_ref):
            return (ib, kv_block(ib, iq, ik, bases_ref), 0, 0)
        kv_spec = pl.BlockSpec((None, block_kv, nkv, d), kv_map)
    else:
        prefetch = (block_tbl.astype(jnp.int32), layer, bases)

        def kv_map(ib, iq, ik, tbl_ref, layer_ref, bases_ref):
            return (layer_ref[0], tbl_ref[ib, kv_block(ib, iq, ik, bases_ref)],
                    0, 0, 0)
        kv_spec = pl.BlockSpec((None, None, block_kv, nkv, d), kv_map)

    def q_map(ib, iq, ik, *_):
        return (ib, iq, 0, 0)

    def o_map(ib, iq, ik, *_):
        return (ib, 0, iq, 0)

    def probe_map(ib, iq, ik, *_):
        return (ib, 0, 0)

    # the output is head-major: storing one head's (block_q, d) tile is a
    # plain store, where a strided store into (block_q, nh, d) is refused
    # for some (dtype, head_dim) pairs (bf16 at d=64)
    out_shape = [jax.ShapeDtypeStruct((b, nh, c, d), q.dtype)]
    out_specs = [pl.BlockSpec((None, nh, block_q, d), o_map)]
    if probe:
        out_shape.append(jax.ShapeDtypeStruct((b, 1, LANES), jnp.float32))
        out_specs.append(pl.BlockSpec((None, 1, LANES), probe_map))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b, nq, nk),
        in_specs=[pl.BlockSpec((None, block_q, nh, d), q_map),
                  kv_spec, kv_spec],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((nh, block_q, LANES), jnp.float32),
                        pltpu.VMEM((nh, block_q, LANES), jnp.float32),
                        pltpu.VMEM((nh, block_q, d), jnp.float32)],
    )
    kernel = functools.partial(
        _attn_kernel, n_prefetch=len(prefetch), scale=1.0 / math.sqrt(d),
        causal=causal, window=window, nh=nh, group=group, block_q=block_q,
        block_kv=block_kv, n_kv_blocks=nk, probe=probe)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name=name,
    )(*prefetch, q, k, v)
    o = jnp.swapaxes(out[0], 1, 2)
    return (o, out[1]) if probe else o


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    block_q: int = 128, block_kv: int = 128,
                    interpret: bool = False) -> jax.Array:
    """Prefill self-attention. q: (B,Sq,nh,d), k/v: (B,Sk,nkv,d) ->
    (B,Sq,nh,d); causal queries and keys both start at position 0."""
    return _attend(q, k, v, 0, None, name="flash_attention", causal=causal,
                   window=window, block_q=block_q, block_kv=block_kv,
                   probe=False, interpret=interpret)


def chunk_attention(q: jax.Array, cache_k: jax.Array, cache_v: jax.Array,
                    bases, *, window: Optional[int] = None,
                    block_q: int = 128, block_kv: int = 128,
                    probe: bool = False, interpret: bool = False):
    """q: (B,C,nh,d); cache_k/v: (B,S,nkv,d) with the chunk already
    written; bases scalar or (B,) — row b's queries sit at absolute
    positions ``bases[b] + [0, C)``. Returns o, or (o, probe_max) when
    ``probe`` is armed."""
    return _attend(q, cache_k, cache_v, bases, None, name="chunk_attention",
                   causal=True, window=window, block_q=block_q,
                   block_kv=block_kv, probe=probe, interpret=interpret)


def chunk_attention_paged(q: jax.Array, cache_k: jax.Array,
                          cache_v: jax.Array, block_tbl: jax.Array,
                          bases, *, window: Optional[int] = None,
                          block_q: int = 128, probe: bool = False,
                          interpret: bool = False, layer=None):
    """q: (B,C,nh,d); cache_k/v: the stacked pool (L, n_blocks, block,
    nkv, d) read at ``layer``, or one layer's pool (n_blocks, block, nkv,
    d) with ``layer`` None, the chunk already written; block_tbl: (B,
    max_blocks) int32 pool-block id per virtual block (0 = trash block,
    masked); bases scalar or (B,). Returns o, or (o, probe_max) when
    ``probe`` is armed."""
    return _attend(q, cache_k, cache_v, bases, block_tbl,
                   name="chunk_attention_paged", causal=True,
                   window=window, block_q=block_q, block_kv=0, probe=probe,
                   interpret=interpret, layer=layer)


def decode_attention(q: jax.Array, cache_k: jax.Array, cache_v: jax.Array,
                     pos, *, window: Optional[int] = None,
                     block_kv: int = 128, interpret: bool = False
                     ) -> jax.Array:
    """q: (B,1,nh,d); cache_k/v: (B,S,nkv,d); pos scalar or (B,) — the
    position of the current (already written) token per sequence."""
    return _attend(q, cache_k, cache_v, pos, None, name="decode_attention",
                   causal=True, window=window, block_q=1, block_kv=block_kv,
                   probe=False, interpret=interpret)


def _decode_pages(block: int, nkv: int, d: int, dtype,
                  max_blocks: int) -> int:
    """Pool blocks per DMA step of the paged decode kernel: enough for
    ``DECODE_TOKENS`` tokens, fewer where the K and V slabs, double
    buffered, would pass ``DECODE_VMEM``."""
    page = block * nkv * d * jnp.dtype(dtype).itemsize
    return max(1, min(DECODE_TOKENS // block, DECODE_VMEM // (4 * page),
                      max_blocks))


def _head_slabs(buf, slot, nkv: int, span: int):
    """Buffer ``slot`` of a K or V slab, ``(span * nkv, d)`` with each
    token's nkv head rows together, as per-head ``(span, d)`` tiles in
    head order. bfloat16 heads sit in packed row pairs (nkv is even): one
    strided 32-bit load reads two heads and shifts split them, about
    twice as fast on a v5e as a strided load of single 16-bit rows. The
    split widens each half into the top of a float32, which is exact for
    bfloat16 alone; other dtypes load single rows."""
    if buf.dtype != jnp.bfloat16:
        return [buf[slot, pl.ds(h, span, stride=nkv), :] for h in range(nkv)]
    words = buf.bitcast(jnp.uint32)
    heads = []
    for j in range(nkv // 2):
        u = words[slot, pl.ds(j, span, stride=nkv // 2), :]
        heads += [pltpu.bitcast(bits, jnp.float32).astype(buf.dtype)
                  for bits in (u << 16, u & jnp.uint32(0xFFFF0000))]
    return heads


def _decode_paged_kernel(len_ref, tbl_ref, layer_ref, q_ref, k_hbm, v_hbm,
                         *refs,
                         scale: float, window: Optional[int], nkv: int,
                         block: int, pages: int, probe: bool):
    if probe:
        o_ref, p_ref, kbuf, vbuf, sem, m_scr, l_scr, acc_scr = refs
    else:
        (o_ref, kbuf, vbuf, sem, m_scr, l_scr, acc_scr), p_ref = refs, None
    b = pl.program_id(0)
    layer = layer_ref[0]
    length = len_ref[b]                       # 0: a dead or pending row
    pos = length - 1
    first = (jnp.maximum(pos - window + 1, 0) // block
             if window is not None else 0)
    n_pages = jnp.where(length > 0, pos // block - first + 1, 0)
    n_steps = (n_pages + pages - 1) // pages
    span = pages * block
    page_rows = block * nkv

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    if probe:
        p_ref[...] = jnp.zeros_like(p_ref)

    def dma(i, slot, start: bool):
        """Step ``i``'s copies, one per live pool block of K and of V,
        into buffer ``slot``; pages past the row's last are never read."""
        def page(j, carry):
            # a wait needs only the copy's size and semaphore
            blk = tbl_ref[b, first + i * pages + j] if start else 0
            at = pl.ds(pl.multiple_of(j * page_rows, page_rows), page_rows)
            for hbm, buf in ((k_hbm, kbuf), (v_hbm, vbuf)):
                cp = pltpu.make_async_copy(hbm.at[layer, blk],
                                           buf.at[slot, at],
                                           sem.at[slot])
                if start:
                    cp.start()
                else:
                    cp.wait()
            return carry

        jax.lax.fori_loop(0, jnp.minimum(pages, n_pages - i * pages), page,
                          0)

    def attend(i, slot, last: bool):
        t0 = (first + i * pages) * block
        kpos = t0 + jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
        mask = kpos <= pos
        rows = t0 + jax.lax.broadcasted_iota(jnp.int32, (span, 1), 0)
        if window is not None:
            mask &= kpos > pos - window
        if probe:
            readable = rows <= pos
            if window is not None:
                readable &= rows > pos - window
            mag = jnp.zeros((span, 1), jnp.float32)
        for h, (k, v) in enumerate(zip(_head_slabs(kbuf, slot, nkv, span),
                                       _head_slabs(vbuf, slot, nkv, span))):
            if probe:
                for x in (k, v):
                    mag = jnp.maximum(mag, jnp.max(
                        jnp.abs(x.astype(jnp.float32)), axis=1,
                        keepdims=True))
            if last:
                # the tail past pos holds pages never copied: a masked
                # weight of 0 times a stale NaN would still be NaN
                v = jnp.where(rows <= pos, v, jnp.zeros_like(v))
            q = q_ref[h]                                 # (group, d)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(mask, s * scale, NEG_INF)
            m_prev = m_scr[h]                            # (group, LANES)
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_next)
            p = jnp.where(mask, jnp.exp(s - m_next[:, :1]), 0.0)
            l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha[:, :1] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_scr[h] = m_next
        if probe:
            worst = jnp.max(jnp.where(readable, mag, 0.0))
            p_ref[...] = jnp.maximum(p_ref[...], worst)

    @pl.when(n_steps > 0)
    def _first():
        dma(0, 0, start=True)

    def full_step(i, carry):
        slot = i % 2
        dma(i + 1, 1 - slot, start=True)
        dma(i, slot, start=False)
        attend(i, slot, last=False)
        return carry

    jax.lax.fori_loop(0, n_steps - 1, full_step, 0)

    @pl.when(n_steps > 0)
    def _last():
        i = n_steps - 1
        dma(i, i % 2, start=False)
        attend(i, i % 2, last=True)

    for h in range(nkv):
        denom = jnp.maximum(l_scr[h][:, :1], 1e-30)
        o_ref[h] = (acc_scr[h] / denom).astype(o_ref.dtype)


def decode_attention_paged(q: jax.Array, cache_k: jax.Array,
                           cache_v: jax.Array, block_tbl: jax.Array,
                           pos, *, window: Optional[int] = None,
                           probe: bool = False, interpret: bool = False,
                           layer=None):
    """q: (B,1,nh,d); cache_k/v: the stacked pool (L, n_blocks, block,
    nkv, d) read at ``layer``, or one layer's pool (n_blocks, block, nkv,
    d) with ``layer`` None; block_tbl: (B, max_blocks); pos scalar or (B,)
    — the position of the current (already written) token per sequence.
    In the page loop a row whose table starts at the trash block 0 is dead
    or pending: it reads nothing and its output is 0. Returns o, or (o,
    probe_max) when ``probe`` is armed."""
    b, _, nh, d = q.shape
    block, nkv = cache_k.shape[-3:-1]
    assert nh % nkv == 0, (nh, nkv)
    dt = jnp.dtype(cache_k.dtype)
    if d != LANES or not (dt == jnp.float32
                          or (dt == jnp.bfloat16 and nkv % 2 == 0)):
        # the page loop needs what the TPU compiler takes: DMAs of whole
        # 128-lane rows (not qwen2's head size 64) and strided loads of
        # 32-bit words (float32 rows, or bfloat16 heads in pairs); any
        # other pool (float16, odd bfloat16 KV head counts) keeps the
        # per-block grid walk
        return _attend(q, cache_k, cache_v, pos, block_tbl,
                       name="decode_attention_paged", causal=True,
                       window=window, block_q=1, block_kv=0, probe=probe,
                       interpret=interpret, layer=layer)
    cache_k, cache_v, layer = _stacked(cache_k, cache_v, layer)
    group = nh // nkv
    tbl = block_tbl.astype(jnp.int32)
    pages = _decode_pages(block, nkv, d, cache_k.dtype, tbl.shape[1])
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    lengths = jnp.where(tbl[:, 0] == 0, 0, pos + 1)
    # per KV head, its ``group`` query rows: one (group, d) tile each,
    # and the output in the same head-major shape
    qg = q.reshape(b, nkv, group, d)
    row_spec = pl.BlockSpec((None, nkv, group, d), lambda i, *_: (i, 0, 0, 0))
    out_shape = [jax.ShapeDtypeStruct((b, nkv, group, d), q.dtype)]
    out_specs = [row_spec]
    if probe:
        out_shape.append(jax.ShapeDtypeStruct((b, 1, LANES), jnp.float32))
        out_specs.append(pl.BlockSpec((None, 1, LANES),
                                      lambda i, *_: (i, 0, 0)))
    span = pages * block
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[row_spec, pl.BlockSpec(memory_space=pltpu.HBM),
                  pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((2, span * nkv, d), cache_k.dtype),
                        pltpu.VMEM((2, span * nkv, d), cache_v.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.VMEM((nkv, group, LANES), jnp.float32),
                        pltpu.VMEM((nkv, group, LANES), jnp.float32),
                        pltpu.VMEM((nkv, group, d), jnp.float32)],
    )
    kernel = functools.partial(
        _decode_paged_kernel, scale=1.0 / math.sqrt(d), window=window,
        nkv=nkv, block=block, pages=pages, probe=probe)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="decode_attention_paged",
    )(lengths, tbl, layer, qg,
      *(c.reshape(c.shape[:2] + (block * nkv, d)) for c in (cache_k, cache_v)))
    o = out[0].reshape(b, 1, nh, d)
    return (o, out[1]) if probe else o
