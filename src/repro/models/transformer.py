"""Decoder-only LM covering the dense / MoE / SSM / hybrid / VLM families.

One functional model class, schema-driven params, three entry points:

  * ``loss(params, batch)``                  — training objective
  * ``prefill(params, inputs)``              — prompt -> (last logits, cache)
  * ``decode_step(params, cache, tokens)``   — one token with a KV cache

Layers are scanned (``lax.scan`` over stacked params) so the HLO stays small
for 64-80-layer configs; training remats each layer. Zamba2's hybrid trunk
scans groups of (period Mamba2 layers + one shared-attention application).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models import ffn as ffn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.common import (ParamDef, apply_m_rope, apply_rope,
                                 dtype_of, init_params, make_norm,
                                 norm_schema, schema_shapes, schema_specs,
                                 stack_schema)
from repro.sharding.rules import Sharder


class LM:
    def __init__(self, cfg: ArchConfig, sharder: Optional[Sharder] = None,
                 use_pallas: bool = False, attn_chunk: int = 512,
                 ssd_chunk: int = 128, remat: bool = True,
                 moe_capacity_factor: float = 1.25,
                 remat_policy: Optional[str] = None,
                 kv_probe: bool = False):
        self.cfg = cfg
        self.sharder = sharder or Sharder(mesh=None)
        self.use_pallas = use_pallas
        # device-side KV sanitizer probe: paged gathers checkify readable
        # |K|/|V| against KV_POISON. Requires the caller's dispatch to be
        # checkify-transformed (the engine arms it with the sanitizer).
        self.kv_probe = kv_probe
        self.attn_chunk = attn_chunk
        self.ssd_chunk = ssd_chunk
        self.remat = remat
        self.remat_policy = remat_policy
        self.moe_capacity_factor = moe_capacity_factor
        self.dtype = dtype_of(cfg.dtype)
        self.norm = make_norm(cfg.norm)
        self._schema = self._build_schema()

    # ------------------------------------------------------------------ #
    # schema / params
    # ------------------------------------------------------------------ #
    def _attn_schema(self, in_dim: Optional[int] = None) -> Dict:
        c = self.cfg
        d_in = in_dim or c.d_model
        s = {
            "wq": ParamDef((d_in, c.n_heads * c.hd), ("embed", "heads")),
            "wk": ParamDef((d_in, c.n_kv_heads * c.hd),
                           ("embed", "kv_heads")),
            "wv": ParamDef((d_in, c.n_kv_heads * c.hd),
                           ("embed", "kv_heads")),
            "wo": ParamDef((c.n_heads * c.hd, c.d_model),
                           ("heads", "embed")),
        }
        if c.qkv_bias:
            s["bq"] = ParamDef((c.n_heads * c.hd,), ("heads",), "zeros")
            s["bk"] = ParamDef((c.n_kv_heads * c.hd,), ("kv_heads",), "zeros")
            s["bv"] = ParamDef((c.n_kv_heads * c.hd,), ("kv_heads",), "zeros")
        if c.o_bias:
            s["bo"] = ParamDef((c.d_model,), ("embed",), "zeros")
        return s

    def _dense_layer_schema(self) -> Dict:
        c = self.cfg
        s = {
            "ln_attn": norm_schema(c.norm, c.d_model),
            "attn": self._attn_schema(),
            "ln_mlp": norm_schema(c.norm, c.d_model),
        }
        if c.n_experts > 0:
            s["moe"] = moe_mod.moe_schema(c.d_model, c.d_ff, c.n_experts,
                                          c.gated_ffn)
        else:
            s["mlp"] = ffn_mod.ffn_schema(c.d_model, c.d_ff, c.gated_ffn,
                                          c.mlp_bias)
        return s

    def _mamba_layer_schema(self) -> Dict:
        c = self.cfg
        return {
            "ln": norm_schema(c.norm, c.d_model),
            "mixer": ssm_mod.mamba2_schema(c.d_model, c.d_inner, c.ssm_state,
                                           c.ssm_heads, c.conv_width),
        }

    def _shared_block_schema(self) -> Dict:
        """Zamba2 shared transformer block: attention over concat(x, x0)."""
        c = self.cfg
        return {
            "ln_attn": norm_schema(c.norm, 2 * c.d_model),
            "attn": self._attn_schema(in_dim=2 * c.d_model),
            "ln_mlp": norm_schema(c.norm, c.d_model),
            "mlp": ffn_mod.ffn_schema(c.d_model, c.d_ff, c.gated_ffn,
                                      c.mlp_bias),
        }

    def _build_schema(self) -> Dict:
        c = self.cfg
        s: Dict[str, Any] = {
            "embed": {"tok": ParamDef((c.padded_vocab, c.d_model),
                                      ("vocab", "embed"))},
            "final_norm": norm_schema(c.norm, c.d_model),
        }
        if c.family == "ssm":
            s["layers"] = stack_schema(self._mamba_layer_schema(), c.n_layers)
        elif c.family == "hybrid":
            s["layers"] = stack_schema(self._mamba_layer_schema(), c.n_layers)
            s["shared"] = self._shared_block_schema()
        else:
            s["layers"] = stack_schema(self._dense_layer_schema(), c.n_layers)
        if not c.tie_embeddings:
            s["lm_head"] = ParamDef((c.d_model, c.padded_vocab),
                                    ("embed", "vocab"))
        return s

    def init(self, key: jax.Array) -> Dict:
        return init_params(self._schema, key, self.dtype)

    def param_specs(self) -> Dict:
        return schema_specs(self._schema)

    def param_shapes(self) -> Dict:
        return schema_shapes(self._schema, self.dtype)

    def param_count(self) -> int:
        from repro.models.common import param_count
        return param_count(self._schema)

    # ------------------------------------------------------------------ #
    # embedding / logits
    # ------------------------------------------------------------------ #
    def embed(self, params: Dict, inputs: Dict) -> jax.Array:
        if "embeds" in inputs:               # stubbed VLM/audio frontend
            return inputs["embeds"].astype(self.dtype)
        return jnp.take(params["embed"]["tok"], inputs["tokens"], axis=0)

    def logits(self, params: Dict, x: jax.Array) -> jax.Array:
        if self.cfg.tie_embeddings:
            out = x @ params["embed"]["tok"].T
        else:
            out = x @ params["lm_head"]
        return self.sharder.constrain(out, "batch", "seq", "vocab")

    # ------------------------------------------------------------------ #
    # attention layer bodies
    # ------------------------------------------------------------------ #
    def _qkv(self, p: Dict, x: jax.Array, positions, x_kv=None):
        c = self.cfg
        sh = self.sharder
        xk = x if x_kv is None else x_kv
        q = x @ p["wq"]
        k = xk @ p["wk"]
        v = xk @ p["wv"]
        if "bq" in p:
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
        b, s = x.shape[0], x.shape[1]
        q = q.reshape(b, s, c.n_heads, c.hd)
        k = k.reshape(b, xk.shape[1], c.n_kv_heads, c.hd)
        v = v.reshape(b, xk.shape[1], c.n_kv_heads, c.hd)
        if positions is not None:
            if c.m_rope:
                q = apply_m_rope(q, positions, c.rope_theta, c.mrope_sections)
                k = apply_m_rope(k, positions, c.rope_theta, c.mrope_sections)
            else:
                pos2 = positions[0] if positions.ndim == 3 else positions
                q = apply_rope(q, pos2, c.rope_theta)
                k = apply_rope(k, pos2, c.rope_theta)
        q = sh.constrain(q, "batch", "seq", "heads", "head_dim")
        k = sh.constrain(k, "batch", "seq", "kv_heads", "head_dim")
        v = sh.constrain(v, "batch", "seq", "kv_heads", "head_dim")
        return q, k, v

    def _attn_full(self, p: Dict, x: jax.Array, positions) -> Tuple[
            jax.Array, jax.Array, jax.Array]:
        """Full-sequence causal attention; returns (out, k, v)."""
        c = self.cfg
        q, k, v = self._qkv(p, x, positions)
        if self.use_pallas:
            from repro.kernels import ops as kops
            o = kops.flash_attention(q, k, v, causal=True,
                                     window=c.swa_window)
        else:
            o = attn.prefill_attention(q, k, v, causal=True,
                                       window=c.swa_window,
                                       chunk_q=self.attn_chunk)
        o = o.reshape(x.shape[0], x.shape[1], c.n_heads * c.hd)
        o = o @ p["wo"]
        if "bo" in p:
            o = o + p["bo"]
        return self.sharder.constrain(o, "batch", "seq", None), k, v

    def _attn_decode(self, p: Dict, x: jax.Array, pos, cache_k, cache_v,
                     slot_pos):
        """One-token attention against the cache slice of this layer."""
        c = self.cfg
        positions = self._decode_positions(pos, x.shape[0])
        q, k, v = self._qkv(p, x, positions)
        ck, cv, slot_new = attn.cache_write_token(cache_k, cache_v, k, v,
                                                  pos, slot_pos)
        # linear caches apply SWA via masking; ring caches encode it in
        # slot_pos already
        window = self.cfg.swa_window if slot_new is None else None
        if self.use_pallas:
            from repro.kernels import ops as kops
            o = kops.decode_attention(q, ck, cv, pos, slot_new, window=window)
        else:
            o = attn.decode_attention(q, ck, cv, pos, slot_new, window=window)
        o = o.reshape(x.shape[0], 1, c.n_heads * c.hd)
        o = o @ p["wo"]
        if "bo" in p:
            o = o + p["bo"]
        return o, ck, cv, slot_new

    def _attn_decode_paged(self, p: Dict, x: jax.Array, pos, cache_k,
                           cache_v, layer, block_tbl):
        """One-token attention against layer ``layer`` of the stacked block
        pool: write the token through the block table, attend over the
        row's pages."""
        c = self.cfg
        positions = self._decode_positions(pos, x.shape[0])
        q, k, v = self._qkv(p, x, positions)
        ck, cv = attn.cache_write_token_paged(cache_k, cache_v, k, v, pos,
                                              block_tbl, layer=layer)
        if self.use_pallas:
            from repro.kernels import ops as kops
            o = kops.decode_attention_paged(q, ck, cv, block_tbl, pos,
                                            window=c.swa_window,
                                            probe=self.kv_probe, layer=layer)
        else:
            o = attn.decode_attention_paged(q, ck, cv, block_tbl, pos,
                                            window=c.swa_window,
                                            probe=self.kv_probe, layer=layer)
        o = o.reshape(x.shape[0], 1, c.n_heads * c.hd)
        o = o @ p["wo"]
        if "bo" in p:
            o = o + p["bo"]
        return o, ck, cv

    def _decode_positions(self, pos, batch):
        c = self.cfg
        if pos.ndim == 0:
            p2 = jnp.broadcast_to(pos[None, None], (batch, 1))
        else:
            p2 = pos[:, None]                      # per-sequence positions
        if c.m_rope:
            return jnp.broadcast_to(p2[None], (3, batch, 1))
        return p2

    # ------------------------------------------------------------------ #
    # layer bodies (per family)
    # ------------------------------------------------------------------ #
    def _dense_layer_fwd(self, p: Dict, x: jax.Array, positions,
                         collect_kv: bool):
        c = self.cfg
        h = self.norm(x, p["ln_attn"])
        a, k, v = self._attn_full(p["attn"], h, positions)
        # names for remat policies: saving post-collective block outputs
        # keeps the forward TP all-reduces out of the rematerialized bwd
        a = jax.ad_checkpoint.checkpoint_name(a, "block_out")
        x = x + a
        h = self.norm(x, p["ln_mlp"])
        aux = jnp.zeros((), jnp.float32)
        if c.n_experts > 0:
            m, aux = moe_mod.moe_apply(
                p["moe"], h, c.moe_top_k, c.act, c.gated_ffn,
                capacity_factor=self.moe_capacity_factor,
                sharder=self.sharder)
        else:
            m = ffn_mod.ffn_apply(p["mlp"], h, c.act, c.gated_ffn,
                                  sharder=self.sharder)
        m = jax.ad_checkpoint.checkpoint_name(m, "block_out")
        x = self.sharder.constrain(x + m, "batch", "seq", None)
        if collect_kv:
            return x, (k, v, aux)
        return x, aux

    def _mlp_or_moe(self, p: Dict, h: jax.Array) -> jax.Array:
        c = self.cfg
        if c.n_experts > 0:
            m, _ = moe_mod.moe_apply(
                p["moe"], h, c.moe_top_k, c.act, c.gated_ffn,
                capacity_factor=self.moe_capacity_factor,
                sharder=self.sharder)
            return m
        return ffn_mod.ffn_apply(p["mlp"], h, c.act, c.gated_ffn,
                                 sharder=self.sharder)

    def _dense_layer_decode(self, p: Dict, x, pos, ck, cv, slot_pos):
        h = self.norm(x, p["ln_attn"])
        a, ck, cv, slot_new = self._attn_decode(p["attn"], h, pos, ck, cv,
                                                slot_pos)
        x = x + a
        h = self.norm(x, p["ln_mlp"])
        return x + self._mlp_or_moe(p, h), ck, cv, slot_new

    def _dense_layer_decode_paged(self, p: Dict, x, pos, ck, cv, layer,
                                  block_tbl):
        h = self.norm(x, p["ln_attn"])
        a, ck, cv = self._attn_decode_paged(p["attn"], h, pos, ck, cv, layer,
                                            block_tbl)
        x = x + a
        h = self.norm(x, p["ln_mlp"])
        return x + self._mlp_or_moe(p, h), ck, cv

    def _dense_layer_chunk(self, p: Dict, x, q_pos, ck, cv, base,
                           block_tbl=None, lens=None, layer=None):
        """Chunked-prefill layer body: C new tokens against a linear cache.

        Writes the chunk's K/V at [base, base+C) and attends every query
        against the whole cache under per-query position masking — the
        C-token generalization of ``_dense_layer_decode``. With
        ``block_tbl`` ``ck``/``cv`` are the stacked block pool, written and
        read at ``layer`` through the table; per-row ``base``/``lens`` (the
        prefix-sharing suffix path) route each row to its own boundary and
        mask pad columns into the trash block.
        """
        c = self.cfg
        h = self.norm(x, p["ln_attn"])
        positions = q_pos
        if c.m_rope:
            positions = jnp.broadcast_to(q_pos[None], (3,) + q_pos.shape)
        q, k, v = self._qkv(p["attn"], h, positions)
        # under use_pallas the flash chunk kernel walks the block table by
        # scalar prefetch (q_pos is base + arange by construction, so the
        # kernel takes the bases instead of the dense position grid)
        if block_tbl is not None:
            ck, cv = attn.cache_write_chunk_paged(ck, cv, k, v, base,
                                                  block_tbl, lens=lens,
                                                  layer=layer)
            if self.use_pallas:
                from repro.kernels import ops as kops
                o = kops.chunk_attention_paged(q, ck, cv, block_tbl, base,
                                               window=c.swa_window,
                                               probe=self.kv_probe,
                                               layer=layer)
            else:
                o = attn.chunk_attention_paged(q, ck, cv, block_tbl, q_pos,
                                               window=c.swa_window,
                                               probe=self.kv_probe,
                                               layer=layer)
        else:
            assert lens is None, "column masking requires the paged path"
            ck = jax.lax.dynamic_update_slice_in_dim(
                ck, k.astype(ck.dtype), base, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cv, v.astype(cv.dtype), base, axis=1)
            if self.use_pallas:
                from repro.kernels import ops as kops
                o = kops.chunk_attention(q, ck, cv, base,
                                         window=c.swa_window)
            else:
                o = attn.chunk_attention(q, ck, cv, q_pos,
                                         window=c.swa_window)
        o = o.reshape(x.shape[0], x.shape[1], c.n_heads * c.hd) @ p["attn"]["wo"]
        if "bo" in p["attn"]:
            o = o + p["attn"]["bo"]
        x = x + self.sharder.constrain(o, "batch", "seq", None)
        h = self.norm(x, p["ln_mlp"])
        x = x + self._mlp_or_moe(p, h)
        return self.sharder.constrain(x, "batch", "seq", None), ck, cv

    def _mamba_layer_fwd(self, p: Dict, x: jax.Array):
        c = self.cfg
        h = self.norm(x, p["ln"])
        y, st = ssm_mod.mamba2_prefill(
            p["mixer"], h, c.d_inner, c.ssm_state, c.ssm_heads,
            c.ssm_head_dim, chunk=self.ssd_chunk, use_kernel=self.use_pallas)
        return x + y, st

    def _mamba_layer_step(self, p: Dict, x, conv, ssd):
        c = self.cfg
        h = self.norm(x, p["ln"])
        y, st = ssm_mod.mamba2_step(
            p["mixer"], h, ssm_mod.SSMState(conv, ssd), c.d_inner,
            c.ssm_state, c.ssm_heads, c.ssm_head_dim)
        return x + y, st.conv, st.ssd

    def _shared_block_fwd(self, p: Dict, x, x0, positions, collect_kv: bool):
        """Zamba2 shared block on concat(x, x0)."""
        cat = jnp.concatenate([x, x0], axis=-1)
        h = self.norm(cat, p["ln_attn"])
        a, k, v = self._attn_full(p["attn"], h, positions)
        x = x + a
        h = self.norm(x, p["ln_mlp"])
        m = ffn_mod.ffn_apply(p["mlp"], h, self.cfg.act, self.cfg.gated_ffn,
                              sharder=self.sharder)
        x = x + m
        if collect_kv:
            return x, (k, v)
        return x

    def _shared_block_decode(self, p: Dict, x, x0, pos, ck, cv):
        cat = jnp.concatenate([x, x0], axis=-1)
        h = self.norm(cat, p["ln_attn"])
        a, ck, cv, _ = self._attn_decode(p["attn"], h, pos, ck, cv, None)
        x = x + a
        h = self.norm(x, p["ln_mlp"])
        m = ffn_mod.ffn_apply(p["mlp"], h, self.cfg.act, self.cfg.gated_ffn,
                              sharder=self.sharder)
        return x + m, ck, cv

    # ------------------------------------------------------------------ #
    # trunk runners
    # ------------------------------------------------------------------ #
    def _remat(self, body):
        if not self.remat:
            return body
        if self.remat_policy == "save_block_out":
            pol = jax.checkpoint_policies.save_only_these_names("block_out")
            return jax.checkpoint(body, policy=pol)
        return jax.checkpoint(body)

    def _run_trunk_full(self, params: Dict, x: jax.Array, positions,
                        collect_kv: bool):
        """Full-sequence pass over all layers (train / prefill).

        Returns (x, per-layer aux dict). For dense: aux has k/v stacks when
        collect_kv; for ssm/hybrid: conv/ssd state stacks (+ shared kv).
        """
        c = self.cfg
        fam = c.family
        if fam in ("ssm",):
            def body(h, p_l):
                h, st = self._mamba_layer_fwd(p_l, h)
                return h, st
            body = self._remat(body)
            x, states = jax.lax.scan(body, x, params["layers"])
            return x, {"conv": states.conv, "ssd": states.ssd}
        if fam == "hybrid":
            return self._run_hybrid_full(params, x, positions, collect_kv)

        def body(h, p_l):
            out = self._dense_layer_fwd(p_l, h, positions, collect_kv)
            return out
        body = self._remat(body)
        x, ys = jax.lax.scan(body, x, params["layers"])
        if collect_kv:
            k, v, aux = ys
            return x, {"k": k, "v": v, "aux": jnp.sum(aux)}
        return x, {"aux": jnp.sum(ys)}

    def _scan_pool(self, params: Dict, x: jax.Array, cache: Dict, layer_fn):
        """Run ``layer_fn(p_l, h, pool_k, pool_v, l) -> (h, pool_k,
        pool_v)`` over the layers with the stacked K/V pool in the loop's
        carry, not in its xs/ys: each layer scatters only its new rows into
        the carried (donated) pool, in place, and the kernels read layer
        ``l`` where it lies, so no layer's pool is sliced out or stacked
        back. Returns (x, pool_k, pool_v)."""
        def body(carry, xs):
            h, pk, pv = carry
            p_l, layer = xs
            return layer_fn(p_l, h, pk, pv, layer), None
        layers = jnp.arange(self.cfg.n_layers, dtype=jnp.int32)
        (x, pk, pv), _ = jax.lax.scan(body, (x, cache["k"], cache["v"]),
                                      (params["layers"], layers))
        return x, pk, pv

    def _run_hybrid_full(self, params: Dict, x: jax.Array, positions,
                         collect_kv: bool):
        c = self.cfg
        period = c.hybrid_period
        n_groups = c.n_layers // period
        assert n_groups * period == c.n_layers, (c.n_layers, period)
        trunk = jax.tree.map(
            lambda a: a.reshape((n_groups, period) + a.shape[1:]),
            params["layers"])
        x0 = x

        def group_body(h, p_group):
            def inner(hh, p_l):
                hh, st = self._mamba_layer_fwd(p_l, hh)
                return hh, st
            h, states = jax.lax.scan(inner, h, p_group)
            out = self._shared_block_fwd(params["shared"], h, x0, positions,
                                         collect_kv)
            if collect_kv:
                h, (k, v) = out
                return h, (states, k, v)
            return out, (states,)
        group_body = self._remat(group_body)
        x, ys = jax.lax.scan(group_body, x, trunk)
        states = ys[0]
        conv = states.conv.reshape((c.n_layers,) + states.conv.shape[2:])
        ssd = states.ssd.reshape((c.n_layers,) + states.ssd.shape[2:])
        aux = {"conv": conv, "ssd": ssd}
        if collect_kv:
            aux["ak"], aux["av"] = ys[1], ys[2]
        return x, aux

    # ------------------------------------------------------------------ #
    # public: loss / prefill / decode
    # ------------------------------------------------------------------ #
    def loss(self, params: Dict, batch: Dict) -> jax.Array:
        """Causal-LM cross entropy (mean over mask), + MoE aux loss."""
        c = self.cfg
        x = self.embed(params, batch)
        x = self.sharder.constrain(x, "batch", "seq", None)
        positions = batch.get("positions")
        if positions is None:
            b, s = x.shape[0], x.shape[1]
            positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            if c.m_rope:
                positions = jnp.broadcast_to(positions[None], (3, b, s))
        x, aux = self._run_trunk_full(params, x, positions, collect_kv=False)
        x = self.norm(x, params["final_norm"])
        logits = self.logits(params, x).astype(jnp.float32)
        # mask padded vocab columns
        if c.padded_vocab != c.vocab:
            pad_mask = jnp.arange(c.padded_vocab) < c.vocab
            logits = jnp.where(pad_mask[None, None, :], logits, -1e30)
        targets = batch["targets"]
        mask = batch.get("mask")
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        if mask is not None:
            nll = nll * mask
            denom = jnp.maximum(jnp.sum(mask), 1.0)
        else:
            denom = nll.size
        loss = jnp.sum(nll) / denom
        aux_w = 0.01 if c.n_experts > 0 else 0.0
        moe_aux = aux.get("aux", jnp.zeros((), jnp.float32))
        return loss + aux_w * moe_aux / max(1, c.n_layers)

    def init_cache(self, batch: int, max_len: int, ring: bool = True,
                   vector_pos: bool = False, kv_layout: str = "contig",
                   n_blocks: int = 0, block_size: int = 16) -> Dict:
        """Zero cache (also mirrors the dry-run ShapeDtypeStruct layout).

        ring=False allocates SWA archs a full-length linear cache (window
        masking instead of ring slots) — required for continuous batching
        with per-sequence positions (vector_pos).

        kv_layout="paged" allocates the KV as a pool of ``n_blocks``
        ``block_size``-token blocks (L, n_blocks, block, nkv, d) shared by
        all rows, plus a per-row ``block_tbl`` (batch, ceil(max_len/block))
        mapping virtual positions to pool blocks (entry 0 = reserved trash
        block). Attention families only; SWA applies via window masking on
        virtual positions (no ring)."""
        c = self.cfg
        pos0 = (jnp.zeros((batch,), jnp.int32) if vector_pos
                else jnp.zeros((), jnp.int32))
        cache: Dict[str, Any] = {"pos": pos0}
        if kv_layout == "paged":
            if c.family in ("ssm", "hybrid"):
                raise ValueError("paged KV requires attention caches")
            max_blocks = -(-max_len // block_size)
            if n_blocks <= 0:
                n_blocks = batch * max_blocks + 1       # capacity == contig
            cache["k"] = jnp.zeros(
                (c.n_layers, n_blocks, block_size, c.n_kv_heads, c.hd),
                self.dtype)
            cache["v"] = jnp.zeros_like(cache["k"])
            cache["block_tbl"] = jnp.zeros((batch, max_blocks), jnp.int32)
            return cache
        if c.family in ("ssm", "hybrid"):
            conv_ch = c.d_inner + 2 * c.ssm_state
            cache["conv"] = jnp.zeros(
                (c.n_layers, batch, c.conv_width - 1, conv_ch), self.dtype)
            cache["ssd"] = jnp.zeros(
                (c.n_layers, batch, c.ssm_heads, c.ssm_head_dim,
                 c.ssm_state), jnp.float32)
            if c.family == "hybrid":
                n_apps = len(c.shared_attn_positions())
                cache["ak"] = jnp.zeros(
                    (n_apps, batch, max_len, c.n_kv_heads, c.hd), self.dtype)
                cache["av"] = jnp.zeros_like(cache["ak"])
        else:
            s_alloc = (min(max_len, c.swa_window)
                       if (c.swa_window and ring) else max_len)
            cache["k"] = jnp.zeros(
                (c.n_layers, batch, s_alloc, c.n_kv_heads, c.hd), self.dtype)
            cache["v"] = jnp.zeros_like(cache["k"])
            if c.swa_window and ring:
                cache["slot_pos"] = jnp.full((s_alloc,), -1, jnp.int32)
        return cache

    def cache_specs(self) -> Dict:
        """Logical sharding names for cache entries (same tree structure)."""
        c = self.cfg
        specs: Dict[str, Any] = {"pos": ()}
        if c.family in ("ssm", "hybrid"):
            specs["conv"] = ("layers", "batch", None, "ssm_inner")
            specs["ssd"] = ("layers", "batch", "ssm_heads", None, None)
            if c.family == "hybrid":
                specs["ak"] = ("stack", "batch", "cache_seq",
                               "kv_heads", "head_dim")
                specs["av"] = specs["ak"]
        else:
            specs["k"] = ("layers", "batch", "cache_seq", "kv_heads",
                          "head_dim")
            specs["v"] = specs["k"]
            if c.swa_window:
                specs["slot_pos"] = (None,)
        return specs

    def prefill(self, params: Dict, inputs: Dict,
                max_len: Optional[int] = None, ring: bool = True,
                last_pos: Optional[jax.Array] = None,
                cache: Optional[Dict] = None) -> Tuple[jax.Array, Dict]:
        """Prompt -> (last-position logits (B, Vpad), filled cache).

        The returned cache is allocated at ``max_len`` (>= prompt length).
        ring=False gives SWA archs a linear full-length cache (engine mode).
        last_pos (B,) reads logits at a per-row position instead of the
        final one — the right-padded batched-prefill case, where row i's
        real prompt ends at last_pos[i] (causality keeps pad columns from
        leaking into real rows). A *paged* ``cache`` (from
        ``init_cache(kv_layout="paged")`` with allocated block tables)
        receives the prompt K/V through its block tables instead of a fresh
        contiguous allocation.
        """
        c = self.cfg
        x = self.embed(params, inputs)
        b, s = x.shape[0], x.shape[1]
        max_len = max_len or s
        positions = inputs.get("positions")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            if c.m_rope:
                positions = jnp.broadcast_to(positions[None], (3, b, s))
        x, aux = self._run_trunk_full(params, x, positions, collect_kv=True)
        x = self.norm(x, params["final_norm"])
        if last_pos is None:
            last = x[:, -1:, :]
        else:
            last = x[jnp.arange(b), last_pos][:, None, :]
        logits = self.logits(params, last)[:, 0, :]
        if cache is not None and "block_tbl" in cache:
            return logits, self._write_prefill_paged(cache, aux, s)
        cache = self.init_cache(b, max_len, ring=ring)
        cache["pos"] = jnp.array(s, jnp.int32)
        window = c.swa_window if ring else None
        if c.family in ("ssm", "hybrid"):
            cache["conv"] = aux["conv"].astype(self.dtype)
            cache["ssd"] = aux["ssd"]
            if c.family == "hybrid":
                cache["ak"], _ = _write_prefill_stacked(
                    cache["ak"], aux["ak"], None)
                cache["av"], _ = _write_prefill_stacked(
                    cache["av"], aux["av"], None)
        else:
            slot = cache.get("slot_pos")
            cache["k"], slot_new = _write_prefill_stacked(
                cache["k"], aux["k"], window, s)
            cache["v"], _ = _write_prefill_stacked(
                cache["v"], aux["v"], window, s)
            if slot is not None:
                cache["slot_pos"] = slot_new
        return logits, cache

    def _write_prefill_paged(self, cache: Dict, aux: Dict, s: int) -> Dict:
        """Scatter stacked prefill K/V (L,B,S,nkv,d) into the block pool
        through each row's block table."""
        tbl = cache["block_tbl"]
        blk = cache["k"].shape[2]
        t = jnp.arange(s)
        dest = jnp.take(tbl, t // blk, axis=1)               # (B, S)
        off = t % blk                                        # broadcasts
        out = dict(cache)
        out["k"] = cache["k"].at[:, dest, off].set(
            aux["k"].astype(cache["k"].dtype))
        out["v"] = cache["v"].at[:, dest, off].set(
            aux["v"].astype(cache["v"].dtype))
        out["pos"] = jnp.broadcast_to(
            jnp.array(s, jnp.int32), cache["pos"].shape)
        return out

    def prefill_chunk(self, params: Dict, cache: Dict, tokens: jax.Array,
                      base: jax.Array,
                      last_pos: Optional[jax.Array] = None,
                      block_tbl: Optional[jax.Array] = None,
                      lens: Optional[jax.Array] = None
                      ) -> Tuple[jax.Array, Dict]:
        """Incremental prefill: extend a cache with a C-token chunk
        starting at absolute position ``base``.

        tokens: (B, C) int32; base: scalar int32. Chunk K/V land at cache
        positions [base, base+C); queries attend the whole prefix under
        per-position masks, so running this over consecutive chunks is
        mathematically identical to one full prefill — that is what lets
        migration recompute interleave with live decode without a
        head-of-line stall. Attention families only (SSM state would need
        carried recurrence).

        Two destinations: a private cache (linear, or paged through the
        cache's own ``block_tbl``), or — when ``block_tbl`` is passed —
        the ENGINE's pool, with each of the B rows routed through its own
        table row so chunks land directly in the owning slot's blocks (no
        transient cache, no terminal scatter). In that engine-direct mode
        ``lens`` masks each row's columns >= lens into the trash block
        (rows that finished mid-group stop writing) and the per-SLOT
        ``pos`` update is the caller's, like ``prefill_suffix``.
        Returns (logits at ``last_pos`` (default: last chunk column),
        updated cache).
        """
        c = self.cfg
        assert c.family not in ("ssm", "hybrid"), \
            "chunked prefill requires attention caches"
        assert "slot_pos" not in cache, "chunked prefill needs a linear cache"
        x = jnp.take(params["embed"]["tok"], tokens, axis=0)
        b, cl = tokens.shape
        q_pos = base + jnp.broadcast_to(jnp.arange(cl)[None], (b, cl))
        direct = block_tbl is not None
        tbl = block_tbl if direct else cache.get("block_tbl")

        if tbl is None:
            def body(h, xs):
                p_l, ck, cv = xs
                h, ck, cv = self._dense_layer_chunk(p_l, h, q_pos, ck, cv,
                                                    base)
                return h, (ck, cv)
            x, (ck, cv) = jax.lax.scan(
                body, x, (params["layers"], cache["k"], cache["v"]))
        else:
            x, ck, cv = self._scan_pool(
                params, x, cache,
                lambda p_l, h, pk, pv, layer: self._dense_layer_chunk(
                    p_l, h, q_pos, pk, pv, base, block_tbl=tbl, lens=lens,
                    layer=layer))
        new_cache = dict(cache)
        new_cache["k"], new_cache["v"] = ck, cv
        if not direct:
            new_cache["pos"] = jnp.broadcast_to(base + cl, cache["pos"].shape
                                                ).astype(jnp.int32)
        x = self.norm(x, params["final_norm"])
        if last_pos is None:
            last = x[:, -1:, :]
        else:
            last = x[jnp.arange(b), last_pos][:, None, :]
        logits = self.logits(params, last)[:, 0, :]
        return logits, new_cache

    def prefill_suffix(self, params: Dict, cache: Dict, tokens: jax.Array,
                       bases: jax.Array, block_tbl: jax.Array,
                       lens: jax.Array) -> Tuple[jax.Array, Dict]:
        """Prefix-sharing suffix prefill: each row's first ``bases[i]``
        tokens are already RESIDENT in the paged pool (shared-prefix blocks
        mapped through ``block_tbl``), so only the divergent suffix is
        computed — rows' queries sit at absolute positions
        [bases, bases+lens) and attend the shared prefix through the block
        table; suffix K/V writes land from each row's own boundary, with
        columns past ``lens`` routed to the trash block (pad rows repeat
        row 0, so duplicate writes agree). Because prefix activations are
        causally independent of the suffix, this reproduces a full
        prefill's K/V and logits exactly. Attention families with a paged
        cache only. Returns (logits at each row's last real suffix token,
        cache with k/v updated — the caller owns the ``pos`` update, which
        is per-SLOT, not per-row).
        """
        c = self.cfg
        assert c.family not in ("ssm", "hybrid") and not c.is_encdec, \
            "suffix prefill requires attention-family KV caches"
        x = jnp.take(params["embed"]["tok"], tokens, axis=0)
        b, cl = tokens.shape
        q_pos = bases[:, None] + jnp.arange(cl)[None, :]
        x, ck, cv = self._scan_pool(
            params, x, cache,
            lambda p_l, h, pk, pv, layer: self._dense_layer_chunk(
                p_l, h, q_pos, pk, pv, bases, block_tbl=block_tbl, lens=lens,
                layer=layer))
        new_cache = dict(cache)
        new_cache["k"], new_cache["v"] = ck, cv
        x = self.norm(x, params["final_norm"])
        last = x[jnp.arange(b), lens - 1][:, None, :]
        logits = self.logits(params, last)[:, 0, :]
        return logits, new_cache

    def decode_step(self, params: Dict, cache: Dict, tokens: jax.Array
                    ) -> Tuple[jax.Array, Dict]:
        """One new token for every sequence. tokens: (B, 1) int32 (or
        embeds (B, 1, H) under a stubbed frontend)."""
        c = self.cfg
        if tokens.ndim == 3:
            x = tokens.astype(self.dtype)
        else:
            x = jnp.take(params["embed"]["tok"], tokens, axis=0)
        pos = cache["pos"]
        new_cache = dict(cache)
        if c.family == "ssm":
            def body(h, xs):
                p_l, conv, ssd = xs
                h, conv, ssd = self._mamba_layer_step(p_l, h, conv, ssd)
                return h, (conv, ssd)
            x, (conv, ssd) = jax.lax.scan(
                body, x, (params["layers"], cache["conv"], cache["ssd"]))
            new_cache["conv"], new_cache["ssd"] = conv, ssd
        elif c.family == "hybrid":
            x, new_cache = self._decode_hybrid(params, x, cache, new_cache,
                                               pos)
        elif "block_tbl" in cache:
            tbl = cache["block_tbl"]
            x, new_cache["k"], new_cache["v"] = self._scan_pool(
                params, x, cache,
                lambda p_l, h, pk, pv, layer: self._dense_layer_decode_paged(
                    p_l, h, pos, pk, pv, layer, tbl))
        else:
            slot = cache.get("slot_pos")

            def body(h, xs):
                p_l, ck, cv = xs
                h, ck, cv, slot_new = self._dense_layer_decode(
                    p_l, h, pos, ck, cv, slot)
                return h, (ck, cv, slot_new)
            x, (ck, cv, slots) = jax.lax.scan(
                body, x, (params["layers"], cache["k"], cache["v"]))
            new_cache["k"], new_cache["v"] = ck, cv
            if slot is not None:
                new_cache["slot_pos"] = slots[0]
        x = self.norm(x, params["final_norm"])
        logits = self.logits(params, x)
        new_cache["pos"] = pos + 1
        return logits, new_cache

    def _decode_hybrid(self, params, x, cache, new_cache, pos):
        c = self.cfg
        period = c.hybrid_period
        n_groups = c.n_layers // period
        trunk = jax.tree.map(
            lambda a: a.reshape((n_groups, period) + a.shape[1:]),
            params["layers"])
        conv = cache["conv"].reshape((n_groups, period)
                                     + cache["conv"].shape[1:])
        ssd = cache["ssd"].reshape((n_groups, period) + cache["ssd"].shape[1:])
        x0 = x

        def group_body(h, xs):
            p_group, conv_g, ssd_g, ak, av = xs

            def inner(hh, ys):
                p_l, cv_l, sd_l = ys
                hh, cv_l, sd_l = self._mamba_layer_step(p_l, hh, cv_l, sd_l)
                return hh, (cv_l, sd_l)
            h, (conv_g, ssd_g) = jax.lax.scan(inner, h,
                                              (p_group, conv_g, ssd_g))
            h, ak, av = self._shared_block_decode(params["shared"], h, x0,
                                                  pos, ak, av)
            return h, (conv_g, ssd_g, ak, av)

        x, (conv2, ssd2, ak, av) = jax.lax.scan(
            group_body, x, (trunk, conv, ssd, cache["ak"], cache["av"]))
        new_cache["conv"] = conv2.reshape(cache["conv"].shape)
        new_cache["ssd"] = ssd2.reshape(cache["ssd"].shape)
        new_cache["ak"], new_cache["av"] = ak, av
        return x, new_cache

    def sample_greedy(self, logits: jax.Array) -> jax.Array:
        """Greedy next token over the un-padded vocab."""
        return jnp.argmax(logits[..., :self.cfg.vocab], axis=-1)


def _write_prefill_stacked(cache, kv, window, s: Optional[int] = None):
    """Write stacked per-layer prefill K/V (L,B,S,nkv,d) into cache
    (L,B,S_alloc,nkv,d); returns (cache, slot_pos or None)."""
    s_alloc = cache.shape[2]
    s_in = kv.shape[2]
    if window and s_in > s_alloc:
        start = s_in - s_alloc
        kv = kv[:, :, -s_alloc:]
        slots = (start + jnp.arange(s_alloc)) % s_alloc
        order = jnp.argsort(slots)
        kv = jnp.take(kv, order, axis=2)
        # after reorder, ring slot j holds absolute position start + order[j]
        slot_pos = (start + order).astype(jnp.int32)
        return cache.at[:, :, :].set(kv.astype(cache.dtype)), slot_pos
    out = jax.lax.dynamic_update_slice_in_dim(
        cache, kv.astype(cache.dtype), 0, axis=2)
    if window:
        slot_pos = jnp.where(jnp.arange(s_alloc) < s_in,
                             jnp.arange(s_alloc), -1)
        return out, slot_pos
    return out, None
