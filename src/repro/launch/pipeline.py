"""Pipeline-parallel serving across pods (the paper's technique on TPU).

The paper's core data-plane mechanism is PP across heterogeneous instances
with **uneven layer partitioning** chosen by the DP optimizer (§2.3, §4.2).
On TPU the pipeline boundary is the inter-pod DCN: we run a GPipe-style
microbatched decode step as ``jax.shard_map`` manual over the ``pod`` axis
(auto/GSPMD over ``data``/``model``), hidden states hopping stages via
``lax.ppermute``.

Uneven splits: stages may own different layer counts, but shard_map needs
equal per-pod shapes — stage parameter stacks are therefore padded to
``lmax = max(split)`` with inactive layers masked to identity. The split
itself comes from the same estimator the placement optimizer uses
(``pp_layer_split``), so heterogeneous pod profiles yield the paper's
asymmetric partitioning.

Supported families: dense / moe / vlm decode (full-attention KV caches).
SSM/hybrid/SWA/enc-dec fall back to DP-over-pods (see DESIGN.md §4).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.configs.shapes import ShapeSpec
from repro.models import build_model, input_specs
from repro.sharding import rules as R


def pp_supported(cfg: ArchConfig) -> bool:
    return (cfg.family in ("dense", "moe", "vlm") and cfg.swa_window is None
            and not cfg.is_encdec)


def _shard_map(f, mesh, axis_names, in_specs, out_specs):
    """Partial-auto ``jax.shard_map``: manual over ``axis_names``,
    GSPMD-auto over the rest. The replication check is off (ppermute over
    uneven pipeline stages is not replication-checkable)."""
    return jax.shard_map(f, mesh=mesh, axis_names=set(axis_names),
                         in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


# ---------------------------------------------------------------------------
# layer split (uneven, estimator-driven)
# ---------------------------------------------------------------------------
def pp_layer_split(cfg: ArchConfig, n_stages: int,
                   pod_flops: Optional[Sequence[float]] = None,
                   s_in: int = 32768, s_out: int = 1,
                   batch: int = 128) -> List[int]:
    """Balance per-stage decode latency across (possibly heterogeneous)
    pods. ``pod_flops`` are relative effective FLOP/s per pod (None =>
    homogeneous => near-even split)."""
    from repro.core.roofline import layer_latency
    from repro.hw.profiles import TPU_V5E, effective
    spec = cfg.to_modelspec()
    n = spec.n_layers
    if pod_flops is None:
        pod_flops = [1.0] * n_stages
    devs = [dataclasses.replace(effective(TPU_V5E),
                                flops_bf16=effective(TPU_V5E).flops_bf16 * f,
                                mem_bw=effective(TPU_V5E).mem_bw * f)
            for f in pod_flops]
    lat = [[layer_latency(spec.layers[i], d, "decode", batch, s_in, s_out,
                          16, spec.dtype_bytes) for i in range(n)]
           for d in devs]
    prefix = [[0.0] * (n + 1) for _ in range(n_stages)]
    for s in range(n_stages):
        for i in range(n):
            prefix[s][i + 1] = prefix[s][i] + lat[s][i]
    INF = math.inf
    dp = [[INF] * (n + 1) for _ in range(n_stages + 1)]
    cut = [[0] * (n + 1) for _ in range(n_stages + 1)]
    dp[0][0] = 0.0
    for s in range(1, n_stages + 1):
        for i in range(s, n + 1):
            for j in range(s - 1, i):
                seg = prefix[s - 1][i] - prefix[s - 1][j]
                v = max(dp[s - 1][j], seg)
                if v < dp[s][i]:
                    dp[s][i], cut[s][i] = v, j
    split, i = [], n
    for s in range(n_stages, 0, -1):
        j = cut[s][i]
        split.append(i - j)
        i = j
    return list(reversed(split))


# ---------------------------------------------------------------------------
# parameter / cache packing
# ---------------------------------------------------------------------------
def _pack_stacked(leaf_sds, split: Sequence[int]):
    """(L, ...) -> (n_stages, lmax, ...) shape (SDS only)."""
    lmax = max(split)
    return jax.ShapeDtypeStruct((len(split), lmax) + tuple(leaf_sds.shape[1:]),
                                leaf_sds.dtype)


def pack_pp_params(params: Dict, split: Sequence[int]) -> Dict:
    """Concrete packing (tests / real execution): pad each stage to lmax."""
    lmax = max(split)
    offs = np.cumsum([0] + list(split))

    def pack(leaf):
        stages = []
        for s, n in enumerate(split):
            sl = leaf[offs[s]:offs[s] + n]
            pad = [(0, lmax - n)] + [(0, 0)] * (leaf.ndim - 1)
            stages.append(jnp.pad(sl, pad))
        return jnp.stack(stages)

    out = dict(params)
    out["layers"] = jax.tree.map(pack, params["layers"])
    mask = np.zeros((len(split), lmax), np.bool_)
    for s, n in enumerate(split):
        mask[s, :n] = True
    out["pp_mask"] = jnp.asarray(mask)
    return out


def _pp_param_sds(model, split) -> Tuple[Dict, Dict]:
    """(SDS tree, logical-name tree) for PP-packed params."""
    shapes = model.param_shapes()
    specs = model.param_specs()
    shapes = dict(shapes)
    specs = dict(specs)
    shapes["layers"] = jax.tree.map(lambda s: _pack_stacked(s, split),
                                    shapes["layers"])
    specs["layers"] = jax.tree.map(
        lambda names: ("pp_stage",) + tuple(names),
        specs["layers"],
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))
    shapes["pp_mask"] = jax.ShapeDtypeStruct((len(split), max(split)),
                                             jnp.bool_)
    specs["pp_mask"] = ("pp_stage", None)
    return shapes, specs


# ---------------------------------------------------------------------------
# the PP serve step
# ---------------------------------------------------------------------------
def build_pp_serve_step(cfg: ArchConfig, shape: ShapeSpec, mesh: Mesh,
                        rules: Dict, n_microbatches: Optional[int] = None,
                        pod_flops: Optional[Sequence[float]] = None,
                        kv_cache_dtype: Optional[str] = None):
    from repro.launch.steps import BuiltStep  # circular-free at call time
    assert pp_supported(cfg), f"PP serve unsupported for {cfg.name}"
    n_stages = mesh.shape["pod"]
    split = pp_layer_split(cfg, n_stages, pod_flops=pod_flops,
                           s_in=shape.seq_len, batch=shape.global_batch)
    lmax = max(split)
    b, s_max = shape.global_batch, shape.seq_len
    m = n_microbatches or (min(2 * n_stages, b) if b >= 2 * n_stages else 1)
    assert b % m == 0, (b, m)
    mb = b // m

    rules = dict(rules)
    rules["pp_stage"] = ("pod",)
    rules["batch"] = ("data",)           # pod is used by PP, not DP
    model = build_model(cfg, sharder=R.Sharder(mesh=None), remat=False)
    pshapes, pspecs = _pp_param_sds(model, split)

    # cache: (n_stages, lmax, M, mb, S, nkv, hd)
    kv_dt = model.dtype
    if kv_cache_dtype:
        kv_dt = {"float8_e4m3fn": jnp.float8_e4m3fn,
                 "float8_e5m2": jnp.float8_e5m2}[kv_cache_dtype]
    kv_sds = jax.ShapeDtypeStruct(
        (n_stages, lmax, m, mb, s_max, cfg.n_kv_heads, cfg.hd), kv_dt)
    cache_sds = {"k": kv_sds, "v": kv_sds,
                 "pos": jax.ShapeDtypeStruct((), jnp.int32)}
    cache_specs = {"k": ("pp_stage", "layers", None, "batch", "cache_seq",
                         "kv_heads", "head_dim"),
                   "v": ("pp_stage", "layers", None, "batch", "cache_seq",
                         "kv_heads", "head_dim"),
                   "pos": ()}
    tok_sds = jax.ShapeDtypeStruct((b, 1), jnp.int32)

    def _stage_apply(trunk, mask, x, ck_s, cv_s, pos):
        """Run this pod's (padded) layer stack on one microbatch."""
        def layer(h, xs):
            p_l, ck_l, cv_l, active = xs
            h2, ck2, cv2, _ = model._dense_layer_decode(p_l, h, pos, ck_l,
                                                        cv_l, None)
            h = jnp.where(active, h2, h)
            ck2 = jnp.where(active, ck2, ck_l)
            cv2 = jnp.where(active, cv2, cv_l)
            return h, (ck2, cv2)
        h, (ck_n, cv_n) = jax.lax.scan(layer, x, (trunk, ck_s, cv_s, mask))
        return h, ck_n, cv_n

    def _body(params, cache_k, cache_v, tokens_m, pos, stage_id):
        """shard_map body: manual over pod; tokens_m: (M, mb, 1)."""
        trunk = jax.tree.map(lambda a: a[0], params["layers"])   # strip pod
        mask = params["pp_mask"][0]
        ck, cv = cache_k[0], cache_v[0]            # (lmax, M, mb, S, nkv, hd)
        # the stage index arrives as a pod-sharded input rather than
        # lax.axis_index: axis_index lowers to a PartitionId HLO that the
        # SPMD partitioner rejects under partial-auto shard_map on older JAX
        p_idx = stage_id[0]
        last = n_stages - 1
        h_dim = cfg.d_model
        recv = jnp.zeros((mb, 1, h_dim), model.dtype)
        outs = jnp.zeros((m, mb), jnp.int32)

        def tick(t, carry):
            recv, outs, ck, cv = carry
            rel = t - p_idx
            mb_i = jnp.clip(rel, 0, m - 1)
            valid = (rel >= 0) & (rel < m)
            toks = jax.lax.dynamic_index_in_dim(tokens_m, mb_i, axis=0,
                                                keepdims=False)
            x0 = jnp.take(params["embed"]["tok"], toks, axis=0)
            x = jnp.where(p_idx == 0, x0, recv)
            ck_s = jax.lax.dynamic_index_in_dim(ck, mb_i, axis=1,
                                                keepdims=False)
            cv_s = jax.lax.dynamic_index_in_dim(cv, mb_i, axis=1,
                                                keepdims=False)
            h, ck_n, cv_n = _stage_apply(trunk, mask, x, ck_s, cv_s, pos)
            ck_n = jnp.where(valid, ck_n, ck_s)
            cv_n = jnp.where(valid, cv_n, cv_s)
            ck = jax.lax.dynamic_update_index_in_dim(ck, ck_n, mb_i, axis=1)
            cv = jax.lax.dynamic_update_index_in_dim(cv, cv_n, mb_i, axis=1)
            # last stage: norm + logits + greedy token
            hn = model.norm(h, params["final_norm"])
            logits = (hn @ params["embed"]["tok"].T
                      if cfg.tie_embeddings else hn @ params["lm_head"])
            tok = jnp.argmax(logits[..., :cfg.vocab], axis=-1)[:, 0]
            write = jnp.where(valid & (p_idx == last), tok.astype(jnp.int32),
                              jax.lax.dynamic_index_in_dim(outs, mb_i, 0,
                                                           keepdims=False))
            outs = jax.lax.dynamic_update_index_in_dim(outs, write, mb_i,
                                                       axis=0)
            recv = jax.lax.ppermute(
                h, "pod", [(i, i + 1) for i in range(n_stages - 1)])
            return recv, outs, ck, cv

        recv, outs, ck, cv = jax.lax.fori_loop(
            0, m + n_stages - 1, tick, (recv, outs, ck, cv))
        outs = jax.lax.psum(
            jnp.where(p_idx == last, outs, jnp.zeros_like(outs)), "pod")
        return outs, ck[None], cv[None]

    def serve_step(params, cache, tokens):
        pod_sharded = {"layers": params["layers"],
                       "pp_mask": params["pp_mask"]}
        rest = {k: v for k, v in params.items()
                if k not in ("layers", "pp_mask")}
        tokens_m = tokens.reshape(m, mb, 1)

        def body_with_rest(pod_part, rest_part, ck, cv, toks, pos, sid):
            return _body({**pod_part, **rest_part}, ck, cv, toks, pos, sid)

        smapped = _shard_map(
            body_with_rest, mesh, ("pod",),
            in_specs=(jax.tree.map(lambda _: P("pod"), pod_sharded),
                      jax.tree.map(lambda _: P(), rest),
                      P("pod"), P("pod"), P(), P(), P("pod")),
            out_specs=(P(), P("pod"), P("pod")))
        stage_ids = jnp.arange(n_stages, dtype=jnp.int32)
        outs, ck, cv = smapped(pod_sharded, rest, cache["k"], cache["v"],
                               tokens_m, cache["pos"], stage_ids)
        new_cache = {"k": ck, "v": cv, "pos": cache["pos"] + 1}
        return outs.reshape(b, 1), new_cache

    param_sh = R.tree_shardings(pspecs, pshapes, mesh, rules)
    cache_sh = R.tree_shardings(cache_specs, cache_sds, mesh, rules)
    tok_sh = NamedSharding(mesh, P())
    return BuiltStep(
        fn=serve_step,
        args_sds=(pshapes, cache_sds, tok_sds),
        in_shardings=(param_sh, cache_sh, tok_sh),
        donate_argnums=(1,),
        trip_hints=(m + n_stages - 1, lmax),
        meta={"rules": rules, "pp_split": split, "n_microbatches": m})
