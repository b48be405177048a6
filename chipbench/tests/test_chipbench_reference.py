"""The plain float32 reference against the program's own model at the
reduced size on the CPU: the same weights from the same seed, and the
same logits, for both configurations."""

import numpy as np
import pytest

import _paths  # noqa: F401

import jax
import jax.numpy as jnp

from chipbench import reference
from chipbench.families import dense
from repro.configs import get_config
from repro.models import build_model


def dims_of(cfg):
    return dense.Dims(
        n_layers=cfg.n_layers, d_model=cfg.d_model, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd, d_ff=cfg.d_ff,
        vocab=cfg.vocab, vocab_pad=cfg.vocab_pad_multiple,
        qkv_bias=cfg.qkv_bias, tied=cfg.tie_embeddings,
        rope_theta=cfg.rope_theta, dtype=cfg.dtype)


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "qwen2-0.5b"])
def test_reference_matches_the_model(arch):
    cfg = get_config(arch).reduced()
    dm = dims_of(cfg)
    key = reference.param_key(2 ** 35 + 11)
    model = build_model(cfg)
    params = model.init(key)
    w = dense.make_weights(dm, key)
    flat_p = jax.tree_util.tree_leaves_with_path(params)
    flat_w = dict(jax.tree_util.tree_leaves_with_path(w))
    assert len(flat_p) == len(flat_w)
    for path, x in flat_p:
        np.testing.assert_array_equal(np.asarray(x), np.asarray(flat_w[path]))
    if cfg.qkv_bias:              # exercise the bias path with nonzero values
        lay = params["layers"]["attn"]
        for b in ("bq", "bk", "bv"):
            lay[b] = 0.1 * jnp.ones_like(lay[b])
            w["layers"]["attn"][b] = lay[b]
    toks = np.random.default_rng(0).integers(1, cfg.vocab, 37).astype(np.int32)
    for n in (9, 37):
        want, _ = model.prefill(params, {"tokens": jnp.asarray(toks[None, :n])})
        h = dense.hidden(w, jnp.asarray(toks[:n]), dm)
        got = dense.logits(w, h[n - 1:n], dm)
        np.testing.assert_allclose(np.asarray(got[0]),
                                   np.asarray(want[0, :cfg.vocab]),
                                   rtol=2e-4, atol=2e-5)


def test_gaps_are_zero_for_the_reference_greedy_tokens():
    cfg = get_config("qwen2-0.5b").reduced()
    dm = dims_of(cfg)
    w = dense.make_weights(dm, reference.param_key(3))
    prompt = list(range(1, 20))
    served = []
    for _ in range(5):
        h = dense.hidden(w, jnp.asarray(prompt + served, jnp.int32), dm)
        served.append(int(np.argmax(dense.logits(w, h[-1:], dm)[0])))
    g = dense.gaps(w, dm, prompt, served, quant="fp8")
    assert g["gap_sd"].shape == (5,) and np.all(g["gap_sd"] < 1e-4)
    assert np.all(g["control_gap_sd"] >= 0)
    wrong = [(served[0] + 1) % cfg.vocab] + served[1:]
    assert dense.gaps(w, dm, prompt, wrong)["gap_sd"][0] > 0
