"""Jit'd dispatch wrappers for the Pallas kernels.

On the CPU backend (tests) kernels run in interpret mode — the kernel body
executes in Python for correctness validation. On any other backend they
compile for the device, and a case the kernels do not cover raises
instead of quietly running a jnp oracle in their place. Models call these
through ``use_pallas=True``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax

from repro.kernels import attention as _attn
from repro.kernels import ssd_scan as _ssd


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _check_probe(out, probe: bool):
    """Discharge a kernel's sanitizer probe output: checkify the max
    readable |K|/|V| magnitude against the freed-block poison sentinel.
    The surrounding dispatch (engine jit) is checkify-transformed whenever
    the probe is armed."""
    if not probe:
        return out
    import jax.numpy as jnp
    from jax.experimental import checkify
    from repro.serving.kv_blocks import KV_POISON
    o, pmax = out
    worst = jnp.max(pmax)
    checkify.check(worst < KV_POISON,
                   "poisoned KV block read through the block table "
                   "(max readable |kv| = {m})", m=worst)
    return o


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_kv"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, block_q: int = 128,
                    block_kv: int = 128):
    return _attn.flash_attention(q, k, v, causal=causal, window=window,
                                 block_q=block_q, block_kv=block_kv,
                                 interpret=_interpret())


def decode_attention(q, cache_k, cache_v, pos, slot_pos=None, *,
                     window: Optional[int] = None, block_kv: int = 128):
    """Matches models.attention.decode_attention's signature. The kernel
    serves linear caches; ring caches (``slot_pos``) have no kernel and
    run the jnp path on the CPU backend only."""
    if slot_pos is not None:
        if not _interpret():
            raise NotImplementedError(
                "no Pallas kernel for ring (sliding-window slot) caches; "
                "allocate a linear cache (ring=False)")
        from repro.models.attention import decode_attention as jref
        return jref(q, cache_k, cache_v, pos, slot_pos, window=window)
    return _decode_jit(q, cache_k, cache_v, pos, window=window,
                       block_kv=block_kv)


@functools.partial(jax.jit, static_argnames=("window", "block_kv"))
def _decode_jit(q, cache_k, cache_v, pos, *, window, block_kv):
    return _attn.decode_attention(q, cache_k, cache_v, pos, window=window,
                                  block_kv=block_kv, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("window", "probe"))
def decode_attention_paged(q, cache_k, cache_v, block_tbl, pos, *,
                           window: Optional[int] = None,
                           probe: bool = False, layer=None):
    """Block-pool decode kernel; matches
    models.attention.decode_attention_paged's signature."""
    out = _attn.decode_attention_paged(q, cache_k, cache_v, block_tbl, pos,
                                       window=window, probe=probe,
                                       interpret=_interpret(), layer=layer)
    return _check_probe(out, probe)


@functools.partial(jax.jit, static_argnames=("window", "block_q",
                                             "block_kv"))
def chunk_attention(q, cache_k, cache_v, bases, *,
                    window: Optional[int] = None, block_q: int = 128,
                    block_kv: int = 128):
    """Flash chunk kernel against a linear cache. ``bases`` is scalar or
    (B,): row b's C queries sit at absolute positions ``bases[b]+[0,C)``."""
    return _attn.chunk_attention(q, cache_k, cache_v, bases, window=window,
                                 block_q=block_q, block_kv=block_kv,
                                 interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("window", "block_q", "probe"))
def chunk_attention_paged(q, cache_k, cache_v, block_tbl, bases, *,
                          window: Optional[int] = None, block_q: int = 128,
                          probe: bool = False, layer=None):
    """Flash chunk kernel against the block pool, walking the block table
    via scalar prefetch — no gathered page view is materialized. Covers
    the engine chunk path (scalar base) and the prefix-share suffix path
    (per-row bases)."""
    out = _attn.chunk_attention_paged(q, cache_k, cache_v, block_tbl, bases,
                                      window=window, block_q=block_q,
                                      probe=probe, interpret=_interpret(),
                                      layer=layer)
    return _check_probe(out, probe)


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, a, b, c, chunk: int = 64) -> Tuple[jax.Array, jax.Array]:
    return _ssd.ssd_scan(x, dt, a, b, c, chunk=chunk,
                         interpret=_interpret())
