"""The control of the check: the plain reference put in the program's
place, once in float32 (sound) and once in float8 (the precision step
below the served bfloat16). The float8 control must fail one of the
cell's limits. Run at qwen2-0.5b's published widths with 8 layers and a
32768-row slice of the vocabulary, a size a test run can hold; against
the limits of internlm2-chat, the cell every benchmark keeps. Float8
reads a widest gap of 0.60 and a mean of 0.070 here (both over their
limits), and far more at the cells' full depth on the chip."""

import json
import os

import numpy as np

import _paths  # noqa: F401

import jax
import jax.numpy as jnp

from chipbench import check, reference
from chipbench.families import dense

CELL = os.path.join(_paths.ROOT, "chipbench", "cells", "internlm2-chat.json")
DIMS = dense.Dims(n_layers=8, d_model=896, n_heads=14, n_kv_heads=2,
                  head_dim=64, d_ff=4864, vocab=32768, qkv_bias=True,
                  tied=True, rope_theta=1e6, eps=1e-5)
PAD = 256


class _Req:
    def __init__(self, prompt):
        self.prompt = np.asarray(prompt, np.int32)


class _Served:
    def __init__(self, prompt, tokens):
        self.req = _Req(prompt)
        self.sreq = type("S", (), {"generated": tokens})()


def greedy(w, prompt, n):
    """The reference's own greedy tokens (float32), one at a time."""
    seq = list(prompt)
    for _ in range(n):
        toks = np.zeros(PAD, np.int32)
        toks[:len(seq)] = seq
        h = dense.hidden(w, jnp.asarray(toks), DIMS)
        row = dense.logits(w, h[len(seq) - 1:len(seq)], DIMS)
        seq.append(int(np.argmax(np.asarray(row)[0])))
    return seq[len(prompt):]


def test_float8_control_fails_and_float32_passes():
    limits = json.load(open(CELL))["check"]
    w = jax.jit(dense.make_weights, static_argnums=0)(
        DIMS, reference.param_key(2 ** 40 + 3))
    rng = np.random.default_rng(1)
    sample = []
    for n in (150, 60):
        prompt = rng.integers(1, DIMS.vocab, n).tolist()
        sample.append(_Served(prompt, greedy(w, prompt, 24)))
    got = check.compare(dense, w, DIMS, sample, quant="fp8")
    assert got["compared_tokens"] == 48
    sound = check.judged(got, limits, control=False)
    ctl = check.judged(got, limits, control=True)
    for k in check.LIMIT_KEYS:
        assert sound[k]["value"] < 1e-3 < sound[k]["limit"]
    # the control fails a number, as the harness judges it with --control
    assert any(ctl[k]["value"] > ctl[k]["limit"] for k in check.LIMIT_KEYS)
