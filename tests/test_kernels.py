"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret=True executes the kernel bodies on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref as kref
from repro.kernels.attention import (chunk_attention, chunk_attention_paged,
                                     decode_attention, decode_attention_paged,
                                     flash_attention)
from repro.kernels.ssd_scan import ssd_scan
from repro.models import attention as mattn

RNG = np.random.RandomState(42)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,sq,sk,nh,nkv,d,causal,window",
    [
        (1, 128, 128, 4, 4, 64, True, None),     # MHA causal
        (2, 256, 256, 4, 2, 64, True, None),     # GQA
        (1, 128, 128, 6, 6, 64, True, 32),       # SWA
        (2, 128, 256, 8, 2, 128, False, None),   # cross-ish, d=128
        (1, 384, 384, 2, 1, 32, True, None),     # odd head_dim/backup
    ])
def test_flash_attention_sweep(b, sq, sk, nh, nkv, d, causal, window, dtype):
    q = jnp.asarray(RNG.randn(b, sq, nh, d), dtype)
    k = jnp.asarray(RNG.randn(b, sk, nkv, d), dtype)
    v = jnp.asarray(RNG.randn(b, sk, nkv, d), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          interpret=True)
    ref = kref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,nh,nkv,d,window,vecpos",
    [
        (2, 256, 4, 2, 64, None, False),
        (3, 128, 6, 6, 64, 32, True),
        (2, 256, 8, 2, 128, None, True),
        (1, 512, 2, 1, 32, None, False),
    ])
def test_decode_attention_sweep(b, s, nh, nkv, d, window, vecpos, dtype):
    q = jnp.asarray(RNG.randn(b, 1, nh, d), dtype)
    ck = jnp.asarray(RNG.randn(b, s, nkv, d), dtype)
    cv = jnp.asarray(RNG.randn(b, s, nkv, d), dtype)
    pos = (jnp.asarray(RNG.randint(1, s, (b,)), jnp.int32) if vecpos
           else jnp.asarray(s - 1, jnp.int32))
    out = decode_attention(q, ck, cv, pos, window=window, interpret=True)
    ref = kref.decode_attention_ref(q, ck, cv, pos, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,c,s,nh,nkv,d,window,vecbase",
    [
        (2, 128, 256, 4, 4, 64, None, False),    # MHA, scalar base
        (2, 128, 256, 4, 2, 64, None, True),     # GQA, per-row bases
        (1, 128, 256, 6, 6, 64, 32, False),      # SWA
        (2, 256, 512, 8, 2, 128, None, True),    # GQA, d=128, 2 q-tiles
        (1, 64, 128, 2, 1, 32, None, False),     # sub-tile chunk
        (2, 96, 160, 4, 2, 64, None, True),      # lengths no block divides
    ])
def test_chunk_attention_sweep(b, c, s, nh, nkv, d, window, vecbase, dtype):
    """Flash chunk kernel (linear cache) == jnp chunk oracle across
    GQA/MHA/windowed x scalar-base/per-row-bases."""
    q = jnp.asarray(RNG.randn(b, c, nh, d), dtype)
    ck = jnp.asarray(RNG.randn(b, s, nkv, d), dtype)
    cv = jnp.asarray(RNG.randn(b, s, nkv, d), dtype)
    bases = (jnp.asarray(RNG.randint(0, s - c + 1, (b,)), jnp.int32)
             if vecbase else jnp.asarray(s - c, jnp.int32))
    out = chunk_attention(q, ck, cv, bases, window=window, interpret=True)
    q_pos = (jnp.broadcast_to(bases, (b,))[:, None]
             + jnp.arange(c)[None]).astype(jnp.int32)
    ref = mattn.chunk_attention(q, ck, cv, q_pos, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def _pool(b, mb, block, nkv, d, dtype):
    """Random block pool + per-row table of distinct pool blocks (block 0
    reserved as trash, never mapped here)."""
    n_blocks = 1 + b * mb
    pk = jnp.asarray(RNG.randn(n_blocks, block, nkv, d), dtype)
    pv = jnp.asarray(RNG.randn(n_blocks, block, nkv, d), dtype)
    tbl = jnp.asarray(RNG.permutation(b * mb).reshape(b, mb) + 1, jnp.int32)
    return pk, pv, tbl


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,c,nh,nkv,d,window,vecbase",
    [
        (2, 128, 4, 4, 64, None, False),         # MHA, scalar base
        (2, 128, 4, 2, 64, None, True),          # GQA, per-row bases
        (1, 128, 6, 6, 64, 32, False),           # SWA
        (2, 64, 8, 2, 128, None, True),          # GQA, d=128, sub-tile
    ])
def test_chunk_attention_paged_sweep(b, c, nh, nkv, d, window, vecbase,
                                     dtype):
    """Flash chunk kernel walking the block pool via scalar-prefetched
    block tables == jnp paged oracle (which gathers a page view)."""
    block, mb = 64, 4                            # virtual length 256
    pk, pv, tbl = _pool(b, mb, block, nkv, d, dtype)
    s_virt = block * mb
    q = jnp.asarray(RNG.randn(b, c, nh, d), dtype)
    bases = (jnp.asarray(RNG.randint(0, s_virt - c + 1, (b,)), jnp.int32)
             if vecbase else jnp.asarray(s_virt - c, jnp.int32))
    out = chunk_attention_paged(q, pk, pv, tbl, bases, window=window,
                                interpret=True)
    q_pos = (jnp.broadcast_to(bases, (b,))[:, None]
             + jnp.arange(c)[None]).astype(jnp.int32)
    ref = mattn.chunk_attention_paged(q, pk, pv, tbl, q_pos, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_chunk_paged_probe_flags_readable_poison():
    """The kernel's sanitizer probe reports max |K|/|V| over mask-readable
    positions only: poison in a readable block trips the KV_POISON
    threshold, poison parked beyond every query's causal horizon stays
    invisible."""
    from repro.serving.kv_blocks import KV_POISON
    b, c, nh, nkv, d, block, mb = 1, 64, 4, 2, 32, 64, 4
    pk, pv, tbl = _pool(b, mb, block, nkv, d, jnp.float32)
    q = jnp.asarray(RNG.randn(b, c, nh, d), jnp.float32)
    bases = jnp.asarray(0, jnp.int32)        # queries cover block 0 only
    poisoned_hot = pk.at[tbl[0, 0]].set(KV_POISON)
    _, pmax = chunk_attention_paged(q, poisoned_hot, pv, tbl, bases,
                                    probe=True, interpret=True)
    assert float(jnp.max(pmax)) >= KV_POISON
    poisoned_cold = pk.at[tbl[0, 3]].set(KV_POISON)   # unreadable tail
    out, pmax = chunk_attention_paged(q, poisoned_cold, pv, tbl, bases,
                                      probe=True, interpret=True)
    assert float(jnp.max(pmax)) < KV_POISON
    clean = chunk_attention_paged(q, pk, pv, tbl, bases, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(clean),
                               atol=2e-5, rtol=2e-5)


def test_decode_paged_probe_flags_readable_poison():
    """Same probe contract on the paged decode kernel (C=1)."""
    from repro.serving.kv_blocks import KV_POISON
    b, nh, nkv, d, block, mb = 2, 4, 2, 32, 16, 4
    pk, pv, tbl = _pool(b, mb, block, nkv, d, jnp.float32)
    q = jnp.asarray(RNG.randn(b, 1, nh, d), jnp.float32)
    pos = jnp.asarray([block - 1, block * mb - 1], jnp.int32)
    poisoned = pv.at[tbl[0, 2]].set(-KV_POISON)  # row 0 can't read blk 2
    _, pmax = decode_attention_paged(q, pk, poisoned, tbl, pos,
                                     probe=True, interpret=True)
    assert float(jnp.max(pmax)) < KV_POISON
    poisoned = pv.at[tbl[1, 2]].set(-KV_POISON)  # row 1 reads everything
    _, pmax = decode_attention_paged(q, pk, poisoned, tbl, pos,
                                     probe=True, interpret=True)
    assert float(jnp.max(pmax)) >= KV_POISON


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,nh,hd,n,chunk",
    [
        (2, 128, 4, 16, 32, 32),
        (1, 100, 8, 64, 128, 64),    # non-multiple seq (padding path)
        (2, 64, 2, 32, 64, 64),      # single chunk
    ])
def test_ssd_scan_sweep(b, s, nh, hd, n, chunk, dtype):
    x = jnp.asarray(RNG.randn(b, s, nh, hd) * 0.5, dtype)
    dt = jnp.asarray(np.abs(RNG.randn(b, s, nh)) * 0.1 + 0.01, jnp.float32)
    a = jnp.asarray(-np.abs(RNG.randn(nh)) - 0.1, jnp.float32)
    bm = jnp.asarray(RNG.randn(b, s, n) * 0.3, dtype)
    cm = jnp.asarray(RNG.randn(b, s, n) * 0.3, dtype)
    y, h = ssd_scan(x, dt, a, bm, cm, chunk=chunk, interpret=True)
    yr, hr = kref.ssd_scan_ref(x, dt, a, bm, cm, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               **_tol(dtype))


def test_ssd_chunked_matches_sequential():
    """Chunked SSD algorithm == O(S) sequential recurrence (independent
    second oracle)."""
    b, s, nh, hd, n = 2, 48, 3, 8, 16
    x = jnp.asarray(RNG.randn(b, s, nh, hd) * 0.5, jnp.float32)
    dt = jnp.asarray(np.abs(RNG.randn(b, s, nh)) * 0.1 + 0.01, jnp.float32)
    a = jnp.asarray(-np.abs(RNG.randn(nh)) - 0.1, jnp.float32)
    bm = jnp.asarray(RNG.randn(b, s, n) * 0.3, jnp.float32)
    cm = jnp.asarray(RNG.randn(b, s, n) * 0.3, jnp.float32)
    yc, hc = kref.ssd_scan_ref(x, dt, a, bm, cm, chunk=16)
    ys, hs = kref.ssd_scan_sequential_ref(x, dt, a, bm, cm)
    np.testing.assert_allclose(np.asarray(yc), np.asarray(ys), atol=1e-5)
    np.testing.assert_allclose(np.asarray(hc), np.asarray(hs), atol=1e-5)


def test_model_pallas_path_matches_jnp_path():
    """LM with use_pallas=True (interpret) == pure-jnp path end to end."""
    from repro.configs import get_config
    from repro.models import build_model
    cfg = get_config("internlm2-1.8b").reduced()
    mj = build_model(cfg, remat=False, attn_chunk=0)
    mp = build_model(cfg, remat=False, attn_chunk=0, use_pallas=True)
    params = mj.init(jax.random.PRNGKey(0))
    toks = jnp.asarray(RNG.randint(0, cfg.vocab, (2, 16)), jnp.int32)
    # pallas flash kernel needs block-divisible seq: 16 % block(16 cap) ok
    lj, cj = mj.prefill(params, {"tokens": toks}, max_len=24)
    lp, cp = mp.prefill(params, {"tokens": toks}, max_len=24)
    np.testing.assert_allclose(np.asarray(lj), np.asarray(lp), atol=2e-3,
                               rtol=1e-2)


def test_ring_cache_raises_off_the_cpu(monkeypatch):
    """Ring caches have no kernel: off the CPU backend the dispatch
    raises rather than running the jnp oracle in the kernel's place."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    q = jnp.zeros((1, 1, 2, 32), jnp.float32)
    ck = jnp.zeros((1, 16, 1, 32), jnp.float32)
    with pytest.raises(NotImplementedError, match="ring"):
        ops.decode_attention(q, ck, ck, jnp.int32(3),
                             jnp.zeros((1, 16), jnp.int32), window=8)
