"""Attention: GQA prefill (full / chunked / sliding-window), decode against a
KV cache (linear or ring-buffer), and cross-attention.

The pure-jnp path here is the oracle and the dry-run lowering path; the
Pallas kernels in ``repro.kernels`` are the TPU runtime path, selected via
``use_pallas`` (validated against this code in tests with interpret=True).
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q: (B,Sq,nh,d), k: (B,Sk,nkv,d) -> scores (B,nkv,g,Sq,Sk)."""
    b, sq, nh, d = q.shape
    nkv = k.shape[2]
    g = nh // nkv
    qg = q.reshape(b, sq, nkv, g, d)
    return jnp.einsum("bskgd,btkd->bkgst", qg, k,
                      preferred_element_type=jnp.float32)


def _gqa_out(probs: jax.Array, v: jax.Array) -> jax.Array:
    """probs: (B,nkv,g,Sq,Sk), v: (B,Sk,nkv,d) -> (B,Sq,nh,d)."""
    b, nkv, g, sq, sk = probs.shape
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, sq, nkv * g, v.shape[-1])


def sdpa(q: jax.Array, k: jax.Array, v: jax.Array,
         mask: Optional[jax.Array] = None, scale: Optional[float] = None
         ) -> jax.Array:
    """Grouped-query SDPA. mask broadcastable to (B,1,1,Sq,Sk), True=keep."""
    scale = scale or (1.0 / math.sqrt(q.shape[-1]))
    scores = _gqa_scores(q, k) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return _gqa_out(probs, v)


def causal_mask(sq: int, sk: int, q_offset=0,
                window: Optional[int] = None) -> jax.Array:
    """(1,1,1,Sq,Sk) boolean mask; query i (absolute q_offset+i) sees keys
    j <= q_pos and, with SWA, j > q_pos - window."""
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(sk)[None, :]
    m = kpos <= qpos
    if window is not None:
        m &= kpos > qpos - window
    return m[None, None, None]


def prefill_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, window: Optional[int] = None,
                      chunk_q: int = 0) -> jax.Array:
    """Self-attention over a full prompt.

    chunk_q > 0 processes queries in blocks via lax.map so the (Sq, Sk) score
    matrix never materializes whole — required for the 32k prefill shapes
    (memory O(chunk * Sk) instead of O(Sk^2)).
    """
    b, sq, nh, d = q.shape
    if chunk_q <= 0 or sq <= chunk_q:
        mask = causal_mask(sq, k.shape[1], 0, window) if causal else None
        return sdpa(q, k, v, mask)
    assert sq % chunk_q == 0, (sq, chunk_q)
    n_chunks = sq // chunk_q

    def one_chunk(i):
        qc = jax.lax.dynamic_slice_in_dim(q, i * chunk_q, chunk_q, axis=1)
        mask = causal_mask(chunk_q, k.shape[1], i * chunk_q, window)
        return sdpa(qc, k, v, mask)

    out = jax.lax.map(one_chunk, jnp.arange(n_chunks))
    return jnp.moveaxis(out, 0, 1).reshape(b, sq, nh, d)


# ---------------------------------------------------------------------------
# KV caches
# ---------------------------------------------------------------------------
class KVCache(NamedTuple):
    """Per-layer-stacked KV cache.

    Linear cache: k/v (L,B,S_max,nkv,d), slot i holds position i.
    Ring cache (SWA): S_max = window; slot = pos % window; ``slot_pos``
    (L-independent, (S_max,)) tracks which absolute position a slot holds
    (-1 = empty). ``pos`` is the absolute next-token position (scalar int32).
    """
    k: jax.Array
    v: jax.Array
    pos: jax.Array                 # scalar int32
    slot_pos: Optional[jax.Array]  # (S_max,) int32 or None for linear


def init_kv_cache(n_layers: int, batch: int, s_max: int, n_kv: int, d: int,
                  dtype, window: Optional[int] = None) -> KVCache:
    s_alloc = min(s_max, window) if window else s_max
    shape = (n_layers, batch, s_alloc, n_kv, d)
    slot = (jnp.full((s_alloc,), -1, jnp.int32) if window else None)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                   jnp.zeros((), jnp.int32), slot)


def cache_write_prefill(cache_k: jax.Array, cache_v: jax.Array,
                        k: jax.Array, v: jax.Array,
                        window: Optional[int]) -> Tuple[jax.Array, jax.Array]:
    """Write a full prompt's K/V (B,S,nkv,d) into layer-slice caches
    (B,S_alloc,nkv,d), assuming pos=0 start."""
    s = k.shape[1]
    s_alloc = cache_k.shape[1]
    if window and s > s_alloc:
        k = k[:, -s_alloc:]
        v = v[:, -s_alloc:]
        # ring layout: slot = pos % window for pos in [s-window, s)
        start = s - s_alloc
        slots = (start + jnp.arange(s_alloc)) % s_alloc
        order = jnp.argsort(slots)
        k = jnp.take(k, order, axis=1)
        v = jnp.take(v, order, axis=1)
        return (cache_k.at[:, :].set(k), cache_v.at[:, :].set(v))
    return (jax.lax.dynamic_update_slice_in_dim(cache_k, k, 0, axis=1),
            jax.lax.dynamic_update_slice_in_dim(cache_v, v, 0, axis=1))


def decode_attention(q: jax.Array, cache_k: jax.Array, cache_v: jax.Array,
                     pos: jax.Array, slot_pos: Optional[jax.Array],
                     window: Optional[int] = None) -> jax.Array:
    """One-token attention. q: (B,1,nh,d); cache_k/v: (B,S_alloc,nkv,d);
    ``pos`` is the position of the *current* token (already written).

    pos may be a scalar (uniform batch — serve_step) or a (B,) vector
    (continuous batching — each sequence at its own position). ``window``
    applies SWA masking on *linear* caches (ring caches encode the window in
    slot_pos already)."""
    s_alloc = cache_k.shape[1]
    kpos = jnp.arange(s_alloc)
    if slot_pos is None:
        if pos.ndim == 0:
            valid = (kpos <= pos)[None, :]                  # (1, S)
        else:
            valid = kpos[None, :] <= pos[:, None]           # (B, S)
        if window is not None:
            lo = pos - window
            lo = lo[..., None] if pos.ndim else lo
            valid = valid & (kpos[None, :] > lo)
    else:
        valid = ((slot_pos >= 0) & (slot_pos <= pos))[None, :]
    mask = valid[:, None, None, None, :]
    if cache_k.dtype != q.dtype:      # quantized (f8) KV cache: upcast on read
        cache_k = cache_k.astype(q.dtype)
        cache_v = cache_v.astype(q.dtype)
    return sdpa(q, cache_k, cache_v, mask)


def chunk_attention(q: jax.Array, cache_k: jax.Array, cache_v: jax.Array,
                    q_pos: jax.Array, window: Optional[int] = None
                    ) -> jax.Array:
    """Multi-token attention against a linear cache (chunked prefill).

    q: (B,C,nh,d) — a chunk of C new tokens whose K/V are already written
    into the cache at their absolute positions; q_pos: (B,C) absolute
    position per query. Query i sees cache slots at positions <= q_pos[i]
    (and > q_pos[i] - window under SWA). Generalizes ``decode_attention``
    from C=1 to a whole chunk, which is what bounds head-of-line blocking
    during migration-recompute storms.
    """
    s_alloc = cache_k.shape[1]
    kpos = jnp.arange(s_alloc)
    valid = kpos[None, None, :] <= q_pos[:, :, None]        # (B, C, S)
    if window is not None:
        valid &= kpos[None, None, :] > (q_pos[:, :, None] - window)
    mask = valid[:, None, None, :, :]
    if cache_k.dtype != q.dtype:
        cache_k = cache_k.astype(q.dtype)
        cache_v = cache_v.astype(q.dtype)
    return sdpa(q, cache_k, cache_v, mask)


# ---------------------------------------------------------------------------
# Paged (block-table) KV cache — reference path
#
# The pool holds ``n_blocks`` fixed-size token blocks per layer:
# ``cache_k/v: (n_blocks, block, nkv, d)`` (a per-layer slice of the stacked
# ``(L, n_blocks, block, nkv, d)`` engine pool). ``block_tbl: (B, max_blocks)``
# maps slot-virtual position t to pool block ``block_tbl[b, t // block]`` at
# offset ``t % block``; unallocated entries point at the reserved trash block
# 0, whose contents position masking keeps invisible. These are the pure-jnp
# oracles for the Pallas paged kernels in ``repro.kernels.attention``.
# ---------------------------------------------------------------------------
def _gather_pages(cache_k: jax.Array, cache_v: jax.Array,
                  block_tbl: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Materialize each row's virtual KV view: (B, max_blocks*block, nkv, d)."""
    b, mb = block_tbl.shape
    blk = cache_k.shape[1]
    pk = jnp.take(cache_k, block_tbl, axis=0)     # (B, MB, blk, nkv, d)
    pv = jnp.take(cache_v, block_tbl, axis=0)
    shape = (b, mb * blk) + cache_k.shape[2:]
    return pk.reshape(shape), pv.reshape(shape)


def _poison_probe(pk: jax.Array, pv: jax.Array, readable: jax.Array) -> None:
    """Device-side KV sanitizer probe: assert no *readable* (mask-valid)
    gathered position carries freed-block poison. The caller's dispatch
    must be ``checkify``-transformed (the engine arms this only alongside
    the sanitizer); positions hidden by masking are exempt — a reused
    block legitimately holds poison past its written prefix."""
    from jax.experimental import checkify
    from repro.serving.kv_blocks import KV_POISON
    mag = jnp.maximum(jnp.max(jnp.abs(pk.astype(jnp.float32)), axis=(-2, -1)),
                      jnp.max(jnp.abs(pv.astype(jnp.float32)), axis=(-2, -1)))
    worst = jnp.max(jnp.where(readable, mag, 0.0))
    checkify.check(worst < KV_POISON,
                   "poisoned KV block read through the block table "
                   "(max readable |kv| = {m})", m=worst)


def decode_attention_paged(q: jax.Array, cache_k: jax.Array,
                           cache_v: jax.Array, block_tbl: jax.Array,
                           pos: jax.Array, window: Optional[int] = None,
                           probe: bool = False) -> jax.Array:
    """Block-table ``decode_attention``. q: (B,1,nh,d); cache_k/v:
    (n_blocks, block, nkv, d); pos scalar or (B,), position of the current
    (already written) token."""
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (q.shape[0],))
    pk, pv = _gather_pages(cache_k, cache_v, block_tbl)
    kpos = jnp.arange(pk.shape[1])
    valid = kpos[None, :] <= pos[:, None]
    if window is not None:
        valid &= kpos[None, :] > (pos[:, None] - window)
    if probe:
        _poison_probe(pk, pv, valid)
    mask = valid[:, None, None, None, :]
    if pk.dtype != q.dtype:
        pk, pv = pk.astype(q.dtype), pv.astype(q.dtype)
    return sdpa(q, pk, pv, mask)


def chunk_attention_paged(q: jax.Array, cache_k: jax.Array,
                          cache_v: jax.Array, block_tbl: jax.Array,
                          q_pos: jax.Array, window: Optional[int] = None,
                          probe: bool = False) -> jax.Array:
    """Block-table ``chunk_attention``: (B,C) queries at absolute positions
    ``q_pos`` against each row's gathered pages."""
    pk, pv = _gather_pages(cache_k, cache_v, block_tbl)
    kpos = jnp.arange(pk.shape[1])
    valid = kpos[None, None, :] <= q_pos[:, :, None]        # (B, C, S)
    if window is not None:
        valid &= kpos[None, None, :] > (q_pos[:, :, None] - window)
    if probe:
        _poison_probe(pk, pv, jnp.any(valid, axis=1))
    mask = valid[:, None, None, :, :]
    if pk.dtype != q.dtype:
        pk, pv = pk.astype(q.dtype), pv.astype(q.dtype)
    return sdpa(q, pk, pv, mask)


def cache_write_token_paged(cache_k: jax.Array, cache_v: jax.Array,
                            k: jax.Array, v: jax.Array, pos: jax.Array,
                            block_tbl: jax.Array
                            ) -> Tuple[jax.Array, jax.Array]:
    """Write one token's K/V (B,1,nkv,d) at per-row virtual position ``pos``
    through the block table. Dead/frozen rows whose table entry is the trash
    block write garbage there (never read)."""
    blk = cache_k.shape[1]
    k = k.astype(cache_k.dtype)
    v = v.astype(cache_v.dtype)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (k.shape[0],))
    dest = jnp.take_along_axis(block_tbl, (pos // blk)[:, None],
                               axis=1)[:, 0]                 # (B,)
    off = pos % blk
    return cache_k.at[dest, off].set(k[:, 0]), cache_v.at[dest, off].set(
        v[:, 0])


def cache_write_chunk_paged(cache_k: jax.Array, cache_v: jax.Array,
                            k: jax.Array, v: jax.Array, base: jax.Array,
                            block_tbl: jax.Array,
                            lens: Optional[jax.Array] = None
                            ) -> Tuple[jax.Array, jax.Array]:
    """Write a C-token chunk's K/V (B,C,nkv,d) at virtual positions
    [base, base+C) through the block table. ``base`` may be per-row (B,) —
    the prefix-sharing suffix path, where each row starts at its own
    shared-prefix boundary — and ``lens`` (B,) masks each row's columns
    past its real length into the trash block (pad rows/columns)."""
    blk = cache_k.shape[1]
    ar = jnp.arange(k.shape[1])                              # (C,)
    if jnp.ndim(base) == 0:
        t = base + ar                                        # (C,)
        dest = jnp.take(block_tbl, t // blk, axis=1)         # (B, C)
    else:
        t = base[:, None] + ar[None, :]                      # (B, C)
        # clamp: masked pad columns may index past the table width
        t = jnp.minimum(t, block_tbl.shape[1] * blk - 1)
        dest = jnp.take_along_axis(block_tbl, t // blk, axis=1)
    off = t % blk                                            # broadcasts
    if lens is not None:
        dest = jnp.where(ar[None, :] < lens[:, None], dest, 0)
    return (cache_k.at[dest, off].set(k.astype(cache_k.dtype)),
            cache_v.at[dest, off].set(v.astype(cache_v.dtype)))


def cache_write_token(cache_k: jax.Array, cache_v: jax.Array, k: jax.Array,
                      v: jax.Array, pos: jax.Array,
                      slot_pos: Optional[jax.Array]):
    """Write one token's K/V (B,1,nkv,d) at position ``pos``.

    Returns (cache_k, cache_v, slot_pos'). Ring caches write at pos % window
    (scalar pos only); per-sequence (B,) pos scatters row-wise into linear
    caches (continuous batching).
    """
    s_alloc = cache_k.shape[1]
    k = k.astype(cache_k.dtype)       # quantized caches: downcast on write
    v = v.astype(cache_v.dtype)
    if pos.ndim == 1:
        assert slot_pos is None, "per-slot pos requires a linear cache"
        rows = jnp.arange(cache_k.shape[0])
        slot = jnp.minimum(pos, s_alloc - 1)
        ck = cache_k.at[rows, slot].set(k[:, 0])
        cv = cache_v.at[rows, slot].set(v[:, 0])
        return ck, cv, None
    slot = pos % s_alloc if slot_pos is not None else jnp.minimum(
        pos, s_alloc - 1)
    ck = jax.lax.dynamic_update_slice_in_dim(cache_k, k, slot, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache_v, v, slot, axis=1)
    if slot_pos is not None:
        slot_pos = slot_pos.at[slot].set(pos)
    return ck, cv, slot_pos
