"""Paged block-KV cache: block manager, paged-vs-contig equivalence across
attention configs, Pallas block-table kernel, fragmentation/backpressure,
block-granular KV migration through the tensor store, and the kv_restore
recovery branch."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import attention as attn
from repro.models import build_model
from repro.serving import Engine, GlobalServer, ServeRequest, TensorStore
from repro.serving.kv_blocks import BlockManager


def _params_for(cfg):
    m = build_model(cfg, remat=False, attn_chunk=0)
    return m.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("internlm2-1.8b").reduced()
    return cfg, _params_for(cfg)


# -- block manager -------------------------------------------------------------

def test_block_manager_alloc_free_roundtrip():
    bm = BlockManager(n_blocks=9, block_size=4, max_slots=4,
                      max_blocks_per_slot=6)
    assert bm.blocks_free() == 8                  # block 0 reserved
    assert bm.alloc(0, 10)                        # 3 blocks
    assert bm.alloc(1, 4)                         # 1 block
    assert bm.blocks_in_use() == 4
    assert bm.frag_tokens() == (3 * 4 - 10) + 0
    assert 0 not in bm.slot_blocks(0)             # trash never handed out
    assert (bm.table[0, :3] > 0).all() and bm.table[0, 3] == 0
    assert not bm.alloc(2, 100)                   # exceeds per-slot width
    assert not bm.alloc(2, 17)                    # 5 blocks > 4 free
    assert bm.blocks_in_use() == 4                # failed allocs take nothing
    assert bm.free(0) == 3
    assert (bm.table[0] == 0).all()
    assert bm.alloc(2, 17)                        # fits after the free
    bm.free_all()
    assert bm.blocks_in_use() == 0 and bm.check_no_leak()


# -- paged vs contig equivalence matrix ----------------------------------------

def _cfg_matrix():
    gqa = get_config("internlm2-1.8b").reduced()
    mha = dataclasses.replace(gqa, n_kv_heads=gqa.n_heads)
    swa = get_config("h2o-danube-3-4b").reduced()  # window=8 when reduced
    assert swa.swa_window
    return [("gqa", gqa), ("mha", mha), ("windowed", swa)]


@pytest.mark.parametrize("name,cfg", _cfg_matrix())
def test_paged_matches_contig(name, cfg):
    """Greedy outputs are byte-identical between kv_layout='paged' and
    'contig' on staggered mixed-length admissions."""
    params = _params_for(cfg)
    outs = {}
    for layout in ("contig", "paged"):
        eng = Engine(cfg, params, max_batch=4, max_len=64,
                     kv_layout=layout, block_size=8)
        rs = [ServeRequest(prompt=list(range(1, 4 + 3 * i)),
                           max_new_tokens=5 + i) for i in range(5)]
        eng.admit_many(rs[:3])
        eng.step()
        eng.admit_many(rs[3:])
        eng.drain()
        outs[layout] = [list(r.generated) for r in rs]
    assert outs["paged"] == outs["contig"]


def test_paged_chunked_prefill_matches_contig(setup):
    cfg, params = setup
    prompt = list(range(1, 42))

    def gen(layout):
        eng = Engine(cfg, params, max_batch=2, max_len=64,
                     prefill_chunk=8, kv_layout=layout)
        r = ServeRequest(prompt=prompt, max_new_tokens=6)
        eng.admit(r)
        eng.drain()
        return list(r.generated)
    assert gen("paged") == gen("contig")


def test_paged_pallas_kernel_matches_jnp(setup):
    """use_pallas routes decode through the block-table gather kernel
    (interpret mode on CPU); tokens must match the jnp paged engine."""
    cfg, params = setup

    def gen(**kw):
        eng = Engine(cfg, params, max_batch=2, max_len=64,
                     kv_layout="paged", **kw)
        r = ServeRequest(prompt=[3, 14, 15, 9, 2], max_new_tokens=4)
        eng.admit(r)
        eng.drain()
        return list(r.generated)
    assert gen(use_pallas=True) == gen()


def test_paged_page_loop_kernel_matches_jnp():
    """At head_dim 128 decode takes the kernel's per-row page loop (the
    reduced configs' head_dim 16 takes the block walk): staggered
    admissions leave dead and pending rows beside live ones, and the
    greedy tokens still match the jnp paged engine."""
    cfg = dataclasses.replace(get_config("internlm2-1.8b").reduced(),
                              head_dim=128)
    params = _params_for(cfg)

    def gen(**kw):
        eng = Engine(cfg, params, max_batch=3, max_len=96,
                     kv_layout="paged", block_size=8, **kw)
        rs = [ServeRequest(prompt=list(range(1, 5 + 9 * i)),
                           max_new_tokens=4 + 3 * i) for i in range(3)]
        eng.admit_many(rs[:2])
        eng.step()
        assert eng.admit_many(rs[2:])
        eng.drain()
        return [list(r.generated) for r in rs]
    out = gen(use_pallas=True)
    assert [len(g) for g in out] == [4, 7, 10]
    assert out == gen()


def test_model_prefill_and_chunk_into_paged_cache(setup):
    """Model-level threading: prefill/prefill_chunk write through block
    tables; a paged decode after either matches the contig decode."""
    cfg, params = setup
    model = build_model(cfg, remat=False, attn_chunk=0)
    toks = jnp.asarray([list(range(1, 18)), list(range(21, 38))], jnp.int32)
    b, s = toks.shape
    logits_ref, cache_ref = model.prefill(params, {"tokens": toks},
                                          max_len=32, ring=False)
    bm = BlockManager(2 * b * 4 + 1, 8, b, 4)
    for row in range(b):
        assert bm.alloc(row, 32)
    paged = model.init_cache(b, 32, vector_pos=True, kv_layout="paged",
                             n_blocks=bm.n_blocks, block_size=8)
    paged["block_tbl"] = jnp.asarray(bm.table)
    logits_pg, cache_pg = model.prefill(params, {"tokens": toks},
                                        cache=paged)
    # tolerances: the paged path gathers pages before attending, so XLA's
    # reduction/fusion order differs from the contig path at float32 noise
    np.testing.assert_allclose(np.asarray(logits_ref), np.asarray(logits_pg),
                               rtol=1e-4, atol=1e-6)
    nxt = jnp.asarray([[7], [9]], jnp.int32)
    lr, _ = model.decode_step(params, cache_ref, nxt)
    cache_pg["pos"] = jnp.full((b,), s, jnp.int32)
    lp, _ = model.decode_step(params, cache_pg, nxt)
    np.testing.assert_allclose(np.asarray(lr), np.asarray(lp), rtol=1e-4,
                               atol=1e-6)
    # chunked prefill through the same tables reproduces the full prefill
    paged2 = model.init_cache(b, 32, vector_pos=True, kv_layout="paged",
                              n_blocks=bm.n_blocks, block_size=8)
    paged2["block_tbl"] = jnp.asarray(bm.table)
    cache_c = paged2
    for base in range(0, s, 8):
        end = min(base + 8, s)
        pad = jnp.zeros((b, 8), jnp.int32).at[:, :end - base].set(
            toks[:, base:end])
        last = jnp.full((b,), min(7, s - 1 - base), jnp.int32)
        logits_c, cache_c = model.prefill_chunk(params, cache_c, pad,
                                                jnp.asarray(base, jnp.int32),
                                                last_pos=last)
    np.testing.assert_allclose(np.asarray(logits_ref), np.asarray(logits_c),
                               rtol=1e-4, atol=1e-6)


def test_attention_paged_refs_match_contig():
    """Direct oracle check: gather-based paged attention equals contiguous
    attention on a randomly permuted block pool."""
    rng = np.random.RandomState(1)
    b, nh, nkv, d, bs, mb = 3, 4, 2, 16, 8, 4
    nb = b * mb + 2
    pool_k = jnp.asarray(rng.randn(nb, bs, nkv, d), jnp.float32)
    pool_v = jnp.asarray(rng.randn(nb, bs, nkv, d), jnp.float32)
    tbl = jnp.asarray(
        rng.permutation(np.arange(1, nb))[:b * mb].reshape(b, mb), jnp.int32)
    ck = jnp.take(pool_k, tbl, axis=0).reshape(b, mb * bs, nkv, d)
    cv = jnp.take(pool_v, tbl, axis=0).reshape(b, mb * bs, nkv, d)
    pos = jnp.asarray([5, 17, 30], jnp.int32)
    q = jnp.asarray(rng.randn(b, 1, nh, d), jnp.float32)
    for window in (None, 8):
        ref = attn.decode_attention(q, ck, cv, pos, None, window=window)
        out = attn.decode_attention_paged(q, pool_k, pool_v, tbl, pos,
                                          window=window)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   rtol=1e-6)
    qc = jnp.asarray(rng.randn(b, 5, nh, d), jnp.float32)
    qp = jnp.broadcast_to(6 + jnp.arange(5)[None], (b, 5))
    ref = attn.chunk_attention(qc, ck, cv, qp)
    out = attn.chunk_attention_paged(qc, pool_k, pool_v, tbl, qp)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=1e-6)


# -- the layer loop carries the stacked pool -----------------------------------

def _layer_slice_run(model, params, cache, x, layer_fn):
    """The paged layer loop with each layer's pool in the scan's xs/ys
    (sliced out, written, stacked back), as it ran before the pool moved
    into the carry: the reference the carried pool has to reproduce."""
    def body(h, xs):
        p_l, ck, cv = xs
        h, ck, cv = layer_fn(p_l, h, ck, cv)
        return h, (ck, cv)
    x, (k, v) = jax.lax.scan(body, x, (params["layers"], cache["k"],
                                       cache["v"]))
    return model.norm(x, params["final_norm"]), k, v


@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("program", ["decode", "chunk", "suffix"])
def test_carried_pool_writes_only_new_rows(program, use_pallas):
    """One paged decode step, engine-direct chunk or prefix suffix on a
    4-layer pool of distinct random contents: every layer's pool equals
    the old one bitwise except at the rows written, ``(l, dest, off)``
    for each written row in every layer (masked columns into the trash
    block 0), those rows changed, and logits and pools match the
    per-layer-slice loop the step ran before."""
    cfg = get_config("internlm2-1.8b").reduced()
    model = build_model(cfg, remat=False, attn_chunk=0,
                        use_pallas=use_pallas)
    params = model.init(jax.random.PRNGKey(0))
    b, max_len, blk = 3, 64, 8
    mb = max_len // blk
    rng = np.random.RandomState(11)
    cache = model.init_cache(b, max_len, vector_pos=True, kv_layout="paged",
                             n_blocks=b * mb + 1, block_size=blk)
    for key in ("k", "v"):
        cache[key] = jnp.asarray(rng.randn(*cache[key].shape),
                                 cache[key].dtype)
    tbl = rng.permutation(np.arange(1, b * mb + 1)).reshape(b, mb)
    cache["block_tbl"] = jnp.asarray(tbl, jnp.int32)
    rows = np.arange(b)
    written = np.zeros(cache["k"].shape[:3], bool)      # (L, blocks, blk)
    emb = params["embed"]["tok"]
    if program == "decode":
        pos = np.array([3, 17, 40])
        cache["pos"] = jnp.asarray(pos, jnp.int32)
        tokens = jnp.asarray(rng.randint(0, cfg.vocab, (b, 1)), jnp.int32)
        logits, new = model.decode_step(params, cache, tokens)
        pos_j = cache["pos"]
        h, ref_k, ref_v = _layer_slice_run(
            model, params, cache, jnp.take(emb, tokens, axis=0),
            lambda p_l, h, ck, cv: model._dense_layer_decode_paged(
                p_l, h, pos_j, ck, cv, None, cache["block_tbl"]))
        ref_logits = model.logits(params, h)
        written[:, tbl[rows, pos // blk], pos % blk] = True
    else:
        c = 8
        # a chunk row that finished writes nothing; a suffix row is real
        lens = np.array([8, 5, 0 if program == "chunk" else 1])
        bases = (np.array([16, 16, 16]) if program == "chunk"
                 else np.array([16, 9, 30]))
        tokens = jnp.asarray(rng.randint(0, cfg.vocab, (b, c)), jnp.int32)
        lens_j = jnp.asarray(lens, jnp.int32)
        last = jnp.asarray(np.maximum(lens - 1, 0), jnp.int32)
        if program == "chunk":
            base_j = jnp.asarray(16, jnp.int32)
            logits, new = model.prefill_chunk(
                params, cache, tokens, base_j, last_pos=last,
                block_tbl=cache["block_tbl"], lens=lens_j)
        else:
            base_j = jnp.asarray(bases, jnp.int32)
            logits, new = model.prefill_suffix(
                params, cache, tokens, base_j, cache["block_tbl"], lens_j)
        q_pos = jnp.asarray(bases[:, None] + np.arange(c)[None], jnp.int32)
        h, ref_k, ref_v = _layer_slice_run(
            model, params, cache, jnp.take(emb, tokens, axis=0),
            lambda p_l, h, ck, cv: model._dense_layer_chunk(
                p_l, h, q_pos, ck, cv, base_j,
                block_tbl=cache["block_tbl"], lens=lens_j))
        ref_logits = model.logits(params, h[rows, last][:, None])[:, 0]
        t = bases[:, None] + np.arange(c)[None]
        dest = np.where(np.arange(c)[None] < lens[:, None],
                        tbl[rows[:, None], t // blk], 0)
        written[:, dest, t % blk] = True
    assert written[:, 1:].any() and not written.all()
    for old, out, ref in ((cache["k"], new["k"], ref_k),
                          (cache["v"], new["v"], ref_v)):
        old, out = np.asarray(old), np.asarray(out)
        np.testing.assert_array_equal(out[~written], old[~written])
        live = written.copy()
        live[:, 0] = False                    # trash rows: any value
        assert (out[live] != old[live]).any(axis=(-2, -1)).all()
        np.testing.assert_allclose(out[live], np.asarray(ref)[live],
                                   rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=1e-5, atol=1e-6)


# -- fragmentation / backpressure ----------------------------------------------

def test_admit_finish_churn_never_leaks_blocks(setup):
    cfg, params = setup
    eng = Engine(cfg, params, max_batch=4, max_len=64, kv_layout="paged",
                 block_size=8)
    rng = np.random.RandomState(3)
    for _ in range(6):
        rs = [ServeRequest(
            prompt=rng.randint(0, cfg.vocab, rng.randint(3, 40)).tolist(),
            max_new_tokens=int(rng.randint(1, 6))) for _ in range(4)]
        eng.admit_many(rs)
        eng.drain()
        assert all(r.done for r in rs)
    assert eng.bm.blocks_in_use() == 0
    assert eng.bm.check_no_leak()
    assert eng.stats.alloc_failures == 0


def test_block_exhaustion_backpressures_admission(setup):
    """A pool smaller than the slot capacity refuses admissions instead of
    overflowing; freed blocks let the queue drain later."""
    cfg, params = setup
    # 5 non-trash blocks of 8 tokens = 40 tokens shared by 4 slots
    eng = Engine(cfg, params, max_batch=4, max_len=64, kv_layout="paged",
                 block_size=8, n_blocks=6)
    rs = [ServeRequest(prompt=list(range(1, 15)), max_new_tokens=2)
          for _ in range(4)]                      # 16 tokens -> 2 blocks each
    admitted = eng.admit_many(rs)
    assert len(admitted) == 2                     # 3rd would need a 3rd pair
    # skip-ahead admission tries (and refuses) BOTH remaining requests
    assert eng.stats.alloc_failures == 2
    eng.drain()
    assert eng.bm.blocks_in_use() == 0
    assert len(eng.admit_many(rs[2:])) == 2       # backpressure released
    eng.drain()
    assert all(r.done for r in rs)


# -- KV migration through the tensor store -------------------------------------

def _serve(cfg, params, interrupt_round, prompts, n_new, **server_kw):
    srv = GlobalServer(cfg, TensorStore(), max_batch=2, max_len=64,
                       **server_kw)
    srv.add_pipeline(params, ["inst-A", "inst-B"])
    srv.add_pipeline(params, ["inst-C"])
    reqs = [ServeRequest(prompt=list(p), max_new_tokens=n_new)
            for p in prompts]
    for r in reqs:
        srv.submit(r)
    rounds = 0
    while srv.pending() and rounds < 10_000:
        if rounds == interrupt_round:
            srv.interrupt_instance("inst-A")
        srv.step()
        srv.tick()
        rounds += 1
    return srv, reqs


PROMPTS = [[5, 17, 42, 7, 99], [1, 2, 3], [9, 8, 7, 6], [4, 4, 4]]


def test_kv_migration_byte_identical_no_reprefill(setup):
    """An interrupted run that migrates KV blocks through the store matches
    the uninterrupted run byte-for-byte, with the migrated requests
    re-admitted via attach (kv_imports) instead of recompute."""
    cfg, params = setup
    _, ref = _serve(cfg, params, -1, PROMPTS, 12)
    srv, out = _serve(cfg, params, 4, PROMPTS, 12, use_kv_migration=True)
    kinds = [k for _, k, _ in srv.events]
    assert kinds.count("kv_publish") >= 1
    assert kinds.count("kv_attach") == kinds.count("kv_publish")
    assert sum(p.engine.stats.kv_imports for p in srv.pipelines) \
        == kinds.count("kv_attach")
    assert sum(r.migrations for r in out) >= 1
    for r_ref, r_out in zip(ref, out):
        assert r_out.done
        assert list(r_out.generated) == list(r_ref.generated)
    # consumed payloads must not pin store memory
    assert not [k for k in srv.store._store if k[0] == "__kv__"]


def test_kv_migration_recompute_fallback_on_contig(setup):
    """Contig engines publish nothing; migration falls back to the §5.1
    recompute path and stays byte-identical."""
    cfg, params = setup
    _, ref = _serve(cfg, params, -1, PROMPTS, 12,
                    engine_kw={"kv_layout": "contig"})
    srv, out = _serve(cfg, params, 4, PROMPTS, 12, use_kv_migration=True,
                      engine_kw={"kv_layout": "contig"})
    assert not [k for _, k, _ in srv.events if k == "kv_publish"]
    assert sum(p.engine.stats.kv_imports for p in srv.pipelines) == 0
    assert sum(r.migrations for r in out) >= 1
    for r_ref, r_out in zip(ref, out):
        assert list(r_out.generated) == list(r_ref.generated)


def test_kv_migration_with_pending_chunked_prefill(setup):
    """Slots mid-chunked-prefill have incomplete KV: they are excluded from
    publication and recompute instead — outputs still byte-identical."""
    cfg, params = setup
    prompts = [[5, 17, 42, 7, 99, 3, 1, 2, 8, 11] * 3, [1, 2, 3, 4, 5, 6]]
    _, ref = _serve(cfg, params, -1, prompts, 10)
    srv, out = _serve(cfg, params, 1, prompts, 10, use_kv_migration=True,
                      prefill_chunk=8)
    assert sum(r.migrations for r in out) >= 1
    for r_ref, r_out in zip(ref, out):
        assert r_out.done
        assert list(r_out.generated) == list(r_ref.generated)


# -- batched chunked prefill (pending groups) ----------------------------------

def test_pending_group_single_dispatch_per_step(setup):
    """Pendings admitted together advance as ONE chunk dispatch per step
    (not one per request), and outputs match solo runs."""
    cfg, params = setup
    eng = Engine(cfg, params, max_batch=4, max_len=64, prefill_chunk=8)
    longs = [ServeRequest(prompt=list(range(1 + i, 41 + i)),
                          max_new_tokens=4) for i in range(3)]
    eng.admit_many(longs)
    assert len(eng._pending) == 1 and len(eng._pending[0].members) == 3
    before = eng.stats.prefill_chunks
    eng.step()
    assert eng.stats.prefill_chunks == before + 1     # one fused dispatch
    eng.drain()
    for r in longs:
        solo = Engine(cfg, params, max_batch=2, max_len=64)
        r2 = ServeRequest(prompt=list(r.prompt), max_new_tokens=4)
        solo.admit(r2)
        solo.drain()
        assert list(r.generated) == list(r2.generated)


def test_pending_group_mixed_lengths_finish_independently(setup):
    """Members with different context lengths leave the group as they
    finish; stragglers keep prefilling."""
    cfg, params = setup
    eng = Engine(cfg, params, max_batch=4, max_len=64, prefill_chunk=8)
    short = ServeRequest(prompt=list(range(1, 13)), max_new_tokens=3)
    long = ServeRequest(prompt=list(range(1, 41)), max_new_tokens=3)
    eng.admit_many([short, long])
    eng.step()
    eng.step()                        # base=16: short done, long pending
    assert short.generated and not long.generated
    eng.drain()
    for r in (short, long):
        solo = Engine(cfg, params, max_batch=2, max_len=64)
        r2 = ServeRequest(prompt=list(r.prompt), max_new_tokens=3)
        solo.admit(r2)
        solo.drain()
        assert list(r.generated) == list(r2.generated)


# -- tensor store: LRU budget + accounting -------------------------------------

def _arr(n_bytes):
    return {"w": jnp.zeros((n_bytes // 4,), jnp.float32)}


def test_store_evict_to_lru_respects_refcounts():
    store = TensorStore()
    store.put("m", "a", _arr(400))
    store.put("m", "b", _arr(400))
    store.put("m", "c", _arr(400))
    store.attach("m", "a")                    # pin a
    store.attach("m", "b")
    store.detach("m", "b")                    # b unreferenced, recently used
    assert store.resident_bytes() == 1200
    freed = store.evict_to(900)
    assert freed == 400
    assert not store.contains("m", "c")       # LRU victim: c (never touched)
    assert store.contains("m", "a") and store.contains("m", "b")
    # a referenced key is never evicted, even when the budget is unmeetable
    store.take("m", "b")
    assert store.evict_to(0) == 0
    assert store.contains("m", "a")
    assert store.check_consistent()


def test_store_budget_enforced_on_insert():
    store = TensorStore(budget_bytes=1000)
    store.put("kv", "r1", _arr(400))
    store.put("kv", "r2", _arr(400))
    store.put("kv", "r3", _arr(400))          # evicts r1 (LRU)
    assert store.resident_bytes() <= 1000
    assert not store.contains("kv", "r1")
    assert store.contains("kv", "r3")


def test_store_accounting_agrees_across_put_and_load_paths():
    """Regression: ``put`` and ``load`` must register keys identically so
    resident_bytes/refcount never drift between the paths."""
    store = TensorStore()
    store.put("m", "pre", _arr(400))          # preloaded params
    assert store.refcount("m", "pre") == 0    # resident but unreferenced
    params, _ = store.load("m", "pre", lambda: _arr(9999))
    assert params["w"].nbytes == 400          # resident key: no loader call
    assert store.refcount("m", "pre") == 1
    assert store.loads[-1].cold is False and store.loads[-1].wall_s == 0.0
    store.load("m", "cold", lambda: _arr(800))
    assert store.refcount("m", "cold") == 1
    assert store.resident_bytes() == 1200
    assert store.check_consistent()
    store.detach("m", "cold")
    store.evict_unreferenced()
    assert store.resident_bytes() == 400      # "pre" still attached once
    assert store.contains("m", "pre") and not store.contains("m", "cold")


def test_store_attach_missing_key_raises():
    with pytest.raises(KeyError):
        TensorStore().attach("m", "nope")


# -- recovery: kv_restore branch -----------------------------------------------

def test_decide_prefers_kv_restore_when_store_holds_blocks():
    from repro.cluster.recovery import decide
    from repro.core import populate_cluster
    from repro.hw import AWS_INSTANCES, effective, paper_cluster
    spec = get_config("llama-3.1-70b").to_modelspec()
    insts = {n: dataclasses.replace(i, device=effective(i.device))
             for n, i in AWS_INSTANCES.items()}
    plan = populate_cluster(spec, paper_cluster(), insts, 763, 232,
                            beam_k=1)
    p = plan.pipelines[0]
    base = decide(spec, p, ctx=4096, remaining_grace_s=120.0,
                  policy="hybrid", efficiency=0.05, chunk=16)
    held = decide(spec, p, ctx=4096, remaining_grace_s=120.0,
                  policy="hybrid", efficiency=0.05, chunk=16,
                  store_has_kv=True)
    assert base.mechanism != "kv_restore"     # nothing resident: unchanged
    assert held.mechanism == "kv_restore"
    assert held.kv_restore_s < held.recompute_s
    assert held.kv_restore_s < held.transfer_s
    # and the default decision surface is untouched
    assert base.recompute_s == held.recompute_s
    assert base.transfer_s == held.transfer_s
