"""The dense decoder family: its plain float32 reference, its weights and
its counts of useful work.

The family: token embedding, then per layer RMSNorm -> grouped-query
attention (optional QKV bias, rotary embedding with ``rope_theta`` on the
two halves of each head) -> residual -> RMSNorm -> SwiGLU
(``silu(x W_gate) * (x W_up) W_down``) -> residual; a final RMSNorm and
the output head (the embedding transposed when tied). The shared parts
(the seed's weights, the float32 matmul and the float8 control, the gap
arithmetic) are ``chipbench/reference.py``.

Counts of useful work, computed from the model's dimensions and the live
contexts the harness recorded, never from the padded shapes a kernel
walks. A multiply-add counts as 2 operations. Attention work of one
query at position ``p`` that sees ``k`` keys (``p + 1`` here):
``4 * n_heads * head_dim * k`` operations (scores and the weighted sum).
Its least traffic is one read of the K and V it sees and one read and
write of its q and o rows.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import dtype_bytes, init_weights, mm, served_gaps


@dataclasses.dataclass(frozen=True)
class Dims:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    vocab_pad: int = 128
    qkv_bias: bool = False
    tied: bool = False
    rope_theta: float = 10000.0
    eps: float = 1e-5
    dtype: str = "bfloat16"

    @property
    def padded_vocab(self) -> int:
        return -(-self.vocab // self.vocab_pad) * self.vocab_pad

    @classmethod
    def from_config(cls, c: Dict) -> "Dims":
        """Dims from a ``chipbench/configs/<config>.json`` file."""
        return cls(n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   n_heads=c["num_attention_heads"],
                   n_kv_heads=c["num_key_value_heads"],
                   head_dim=c["head_dim"], d_ff=c["intermediate_size"],
                   vocab=c["vocab_size"], qkv_bias=bool(c.get("qkv_bias")),
                   tied=bool(c["tie_word_embeddings"]),
                   rope_theta=float(c["rope_theta"]),
                   eps=float(c["rms_norm_eps"]), dtype=c["torch_dtype"])

    @classmethod
    def from_program(cls, cfg) -> "Dims":
        """Dims of a program registry entry (the rehearsal's reduced one)."""
        return cls(n_layers=cfg.n_layers, d_model=cfg.d_model,
                   n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                   head_dim=cfg.hd, d_ff=cfg.d_ff, vocab=cfg.vocab,
                   vocab_pad=cfg.vocab_pad_multiple, qkv_bias=cfg.qkv_bias,
                   tied=cfg.tie_embeddings, rope_theta=cfg.rope_theta,
                   dtype=cfg.dtype)

    # Which keys a query sees: the forward's mask and the counts of work
    # both ask these, so a family whose queries see fewer keys overrides
    # them in a subclass and keeps the rest of this module.
    def visible(self, pos: jax.Array) -> jax.Array:
        """(S, S): may the query at ``pos[i]`` see the key at ``pos[j]``."""
        return pos[:, None] >= pos[None, :]

    def keys_seen(self, q0: int, nq: int) -> float:
        """Keys the queries at ``q0 .. q0+nq-1`` see, summed: ``p + 1``
        each."""
        return nq * (q0 + 1) + nq * (nq - 1) / 2.0

    def keys_read(self, q0: int, nq: int) -> int:
        """Positions whose K/V one of those queries sees."""
        return q0 + nq


def check_program(cfg, config: Dict) -> List[str]:
    """The keys where the program's registry entry ``cfg`` differs from
    the configuration file."""
    want = {"n_layers": config["num_hidden_layers"],
            "d_model": config["hidden_size"],
            "n_heads": config["num_attention_heads"],
            "n_kv_heads": config["num_key_value_heads"],
            "hd": config["head_dim"], "d_ff": config["intermediate_size"],
            "vocab": config["vocab_size"],
            "qkv_bias": bool(config.get("qkv_bias")),
            "tie_embeddings": config["tie_word_embeddings"],
            "rope_theta": config["rope_theta"], "dtype": config["torch_dtype"]}
    return [k for k, v in want.items() if getattr(cfg, k) != v]


def weight_tree(dm: Dims) -> Dict:
    """Nested dict of (shape, init) for every tensor; layers stacked."""
    L, d, f = dm.n_layers, dm.d_model, dm.d_ff
    q, kv = dm.n_heads * dm.head_dim, dm.n_kv_heads * dm.head_dim
    attn = {"wq": ((L, d, q), "normal"), "wk": ((L, d, kv), "normal"),
            "wv": ((L, d, kv), "normal"), "wo": ((L, q, d), "normal")}
    if dm.qkv_bias:
        attn.update(bq=((L, q), "zeros"), bk=((L, kv), "zeros"),
                    bv=((L, kv), "zeros"))
    tree = {"embed": {"tok": ((dm.padded_vocab, d), "normal")},
            "final_norm": {"w": ((d,), "ones")},
            "layers": {"attn": attn,
                       "ln_attn": {"w": ((L, d), "ones")},
                       "ln_mlp": {"w": ((L, d), "ones")},
                       "mlp": {"w_up": ((L, d, f), "normal"),
                               "w_down": ((L, f, d), "normal"),
                               "w_gate": ((L, d, f), "normal")}}}
    if not dm.tied:
        tree["lm_head"] = ((d, dm.padded_vocab), "normal")
    return tree


def make_weights(dm: Dims, key: jax.Array) -> Dict:
    """The seed's weights in the served dtype (call under ``jax.jit``)."""
    return init_weights(weight_tree(dm), dm.dtype, key)


# -- forward -----------------------------------------------------------------
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x: (S, n, hd); rotate the two halves of each head."""
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(dm: Dims, quant, x, lw):
    S = x.shape[0]
    nh, nkv, hd = dm.n_heads, dm.n_kv_heads, dm.head_dim
    a = lw["attn"]
    f32 = lambda t: t.astype(jnp.float32)          # noqa: E731
    h = _rms(x, f32(lw["ln_attn"]["w"]), dm.eps)
    q = mm("sd,dk->sk", h, a["wq"], quant)
    k = mm("sd,dk->sk", h, a["wk"], quant)
    v = mm("sd,dk->sk", h, a["wv"], quant)
    if dm.qkv_bias:
        q, k, v = q + f32(a["bq"]), k + f32(a["bk"]), v + f32(a["bv"])
    pos = jnp.arange(S)
    q = _rope(q.reshape(S, nh, hd), pos, dm.rope_theta)
    k = _rope(k.reshape(S, nkv, hd), pos, dm.rope_theta)
    v = v.reshape(S, nkv, hd)
    g = nh // nkv
    qg = q.reshape(S, nkv, g, hd)
    s = mm("skgd,tkd->kgst", qg, k, quant) / np.sqrt(hd)
    s = jnp.where(dm.visible(pos), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = mm("kgst,tkd->skgd", p, v, quant).reshape(S, nh * hd)
    x = x + mm("sk,kd->sd", o, a["wo"], quant)
    m = lw["mlp"]
    h = _rms(x, f32(lw["ln_mlp"]["w"]), dm.eps)
    up = mm("sd,df->sf", h, m["w_up"], quant)
    gate = mm("sd,df->sf", h, m["w_gate"], quant)
    return x + mm("sf,fd->sd", jax.nn.silu(gate) * up, m["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("dm", "quant"))
def hidden(w: Dict, tokens: jax.Array, dm: Dims, quant=None) -> jax.Array:
    """Final-norm hidden states (S, d) of one sequence; each query sees
    the keys ``dm.visible`` allows."""
    with jax.default_matmul_precision("highest"):
        x = jnp.take(w["embed"]["tok"], tokens, axis=0).astype(jnp.float32)

        def body(x, lw):
            return _layer(dm, quant, x, lw), None
        x, _ = jax.lax.scan(body, x, w["layers"])
        return _rms(x, w["final_norm"]["w"].astype(jnp.float32), dm.eps)


@functools.partial(jax.jit, static_argnames=("dm", "quant"))
def logits(w: Dict, h: jax.Array, dm: Dims, quant=None) -> jax.Array:
    """(rows, vocab) float32 logits of hidden rows ``h`` (rows, d)."""
    with jax.default_matmul_precision("highest"):
        head = w["embed"]["tok"].T if dm.tied else w["lm_head"]
        return mm("sd,dv->sv", h, head, quant)[:, :dm.vocab]


def gaps(w: Dict, dm: Dims, prompt: Sequence[int], served: Sequence[int],
         quant: Optional[str] = None) -> Dict[str, np.ndarray]:
    """``reference.served_gaps`` of this family's forward."""
    return served_gaps(hidden, logits, w, dm, prompt, served, quant)


# -- counts of useful work -----------------------------------------------------
def kv_bytes_per_token(dm: Dims) -> int:
    return dm.n_layers * 2 * dm.n_kv_heads * dm.head_dim * dtype_bytes(dm)


def matmul_params(dm: Dims) -> int:
    """Weights a token multiplies through in the layers (the output head
    is ``head_params``)."""
    d, f = dm.d_model, dm.d_ff
    q, kv = dm.n_heads * dm.head_dim, dm.n_kv_heads * dm.head_dim
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * f
    return dm.n_layers * per_layer


def head_params(dm: Dims) -> int:
    return dm.d_model * dm.vocab


def weight_bytes(dm: Dims) -> int:
    """Bytes of every weight a step reads once: the layers and the output
    head (the embedding rows a step gathers are a few kilobytes)."""
    return (matmul_params(dm) + head_params(dm)) * dtype_bytes(dm)


def attn_flops(dm: Dims, q0: int, nq: int) -> float:
    """``nq`` queries at positions ``q0 .. q0+nq-1``, all layers."""
    return (4.0 * dm.n_layers * dm.n_heads * dm.head_dim
            * dm.keys_seen(q0, nq))


def attn_bytes(dm: Dims, q0: int, nq: int) -> float:
    """K/V of the positions the queries see read once, q and o rows
    once."""
    qo = 2 * nq * dm.n_layers * dm.n_heads * dm.head_dim * dtype_bytes(dm)
    return dm.keys_read(q0, nq) * kv_bytes_per_token(dm) + qo


def decode_attn(dm: Dims, ctxs: Iterable[int]) -> Tuple[float, float]:
    """(operations, bytes) of one decode dispatch whose live rows attend
    ``ctx`` keys each (the new token's position is ``ctx - 1``)."""
    fl = by = 0.0
    for c in ctxs:
        fl += attn_flops(dm, c - 1, 1)
        by += attn_bytes(dm, c - 1, 1)
    return fl, by


def prefill_attn(dm: Dims, pieces: Iterable[Tuple[int, int]]
                 ) -> Tuple[float, float]:
    """(operations, bytes) of prefill pieces ``(q0, nq)``."""
    fl = by = 0.0
    for q0, nq in pieces:
        fl += attn_flops(dm, q0, nq)
        by += attn_bytes(dm, q0, nq)
    return fl, by


def step_work(dm: Dims, ctxs: Iterable[int],
              pieces: Iterable[Tuple[int, int]], logit_rows: int
              ) -> Tuple[float, float]:
    """(operations, bytes) of one server step: decode rows with contexts
    ``ctxs``, prefill pieces ``(q0, nq)``, and ``logit_rows`` rows through
    the output head. Weights are read once per step."""
    ctxs, pieces = list(ctxs), list(pieces)
    tokens = len(ctxs) + sum(nq for _, nq in pieces)
    if tokens == 0:
        return 0.0, 0.0
    fl = 2.0 * matmul_params(dm) * tokens + 2.0 * head_params(dm) * logit_rows
    da, db = decode_attn(dm, ctxs)
    pa, pb = prefill_attn(dm, pieces)
    # new K/V rows written once
    by = weight_bytes(dm) + db + pb + tokens * kv_bytes_per_token(dm)
    return fl + da + pa, by
