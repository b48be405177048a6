"""Model families as modules that a configuration names.

The dense family gives what the harness gave before it was a module: the
same weights, gaps and counts of work, pinned to values that the dense
reference and counts computed before they moved into
``chipbench/families/dense.py``. A second family lands as new files
alone, and is the one the harness judges by. A family that does not
exist, or a registry entry that differs from the configuration file,
ends the run before warm-up."""

import copy
import dataclasses
import hashlib
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

import _paths
from _added import rehearse_added_cell

import jax

from chipbench import families, reference, run
from chipbench.families import dense
from repro.configs import get_config

SEED = 2 ** 35 + 11

# sha256 over every leaf (path, dtype, shape, bytes) of the seed's weights
WEIGHTS = {
    "internlm2-1.8b":
        "20a0fbe2acab27e89739e97b54560ab9b596ca954019b45dc22ed195b47a4ff1",
    "qwen2-0.5b":
        "51d91af81c441afe2d20320a36bfaf8a51060375c8dbf9578bc6325ee0d851d7",
}

# gaps of a fixed prompt and served list, with the float8 control; the
# CPU backend may sum a matmul in another order on another number of
# cores, so they are compared to within a few float32 roundings
GAPS = {
    "internlm2-1.8b": {
        "gap_sd": [1.9091863632202148, 3.5922296047210693, 4.024460315704346,
                   1.542848825454712, 2.577418327331543, 3.1635005474090576,
                   3.195240020751953, 2.919719934463501, 1.8682934045791626,
                   2.8719472885131836, 0.9657129049301147,
                   1.3322105407714844],
        "control_gap_sd": [0.0] * 9 + [0.028823625296354294, 0.0, 0.0]},
    "qwen2-0.5b": {
        "gap_sd": [4.75887393951416, 5.565752983093262, 3.0794951915740967,
                   3.2199628353118896, 5.158577919006348, 1.4724122285842896,
                   5.3006591796875, 3.560972213745117, 7.6475372314453125,
                   2.687591075897217, 4.498779773712158, 4.105319976806641],
        "control_gap_sd": [0.0] * 12},
}


def reduced_dims(arch):
    return dense.Dims.from_program(get_config(arch).reduced())


def weights(arch):
    dm = reduced_dims(arch)
    return dm, jax.jit(dense.make_weights, static_argnums=0)(
        dm, reference.param_key(SEED))


@pytest.mark.parametrize("arch", sorted(WEIGHTS))
def test_dense_weights_are_the_parents(arch):
    _, w = weights(arch)
    h = hashlib.sha256()
    for path, x in jax.tree_util.tree_leaves_with_path(w):
        a = np.asarray(x)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    assert h.hexdigest() == WEIGHTS[arch]


@pytest.mark.parametrize("arch", sorted(GAPS))
def test_dense_gaps_are_the_parents(arch):
    dm, w = weights(arch)
    prompt = [(7 * i + 3) % dm.vocab for i in range(1, 40)]
    served = [(11 * i + 5) % dm.vocab for i in range(12)]
    got = dense.gaps(w, dm, prompt, served, quant="fp8")
    for k, want in GAPS[arch].items():
        np.testing.assert_allclose(got[k], np.asarray(want, np.float32),
                                   rtol=1e-6, atol=1e-6)


HAND = dense.Dims(n_layers=1, d_model=8, n_heads=2, n_kv_heads=1, head_dim=4,
                  d_ff=8, vocab=16)
INTERNLM2 = dense.Dims(n_layers=24, d_model=2048, n_heads=16, n_kv_heads=8,
                       head_dim=128, d_ff=8192, vocab=92544)
# a chat-sized step: 18 decode rows at contexts 200-1900, and one 512-token
# chunk of a prompt at positions 1024-1535 (18 + 1 rows through the head)
CTXS = list(range(200, 2000, 100))
CHUNK = [(1024, 512)]


@pytest.mark.parametrize("call,want", [
    (lambda: dense.decode_attn(HAND, [6, 8]), (448.0, 288.0)),
    (lambda: dense.prefill_attn(HAND, [(5, 3)]), (672.0, 224.0)),
    (lambda: dense.step_work(HAND, [6, 8], [(5, 3)], 3), (5728.0, 1616.0)),
    (lambda: dense.step_work(INTERNLM2, [100], [], 1),
     (3418619904.0, 3409084416.0)),
    (lambda: dense.decode_attn(INTERNLM2, CTXS),
     (3715891200.0, 1861484544.0)),
    (lambda: dense.prefill_attn(INTERNLM2, CHUNK),
     (128899350528.0, 251658240.0)),
    (lambda: dense.step_work(INTERNLM2, CTXS, CHUNK, 19),
     (1740363792384.0, 5564203008.0)),
    (lambda: dense.kv_bytes_per_token(INTERNLM2), 98304),
], ids=["decode-hand", "prefill-hand", "step-hand", "step-internlm2",
        "decode-chat", "prefill-chat", "step-chat", "kv-internlm2"])
def test_dense_counts_are_the_parents(call, want):
    assert call() == want


# -- refusals -------------------------------------------------------------------
def _refused(monkeypatch, capsys, change):
    cell = run.load_cell("internlm2-chat")
    config = copy.deepcopy(cell["config"])
    change(config)
    monkeypatch.setattr(run, "load_cell", lambda name: dict(cell,
                                                           config=config))
    rc = run.main(["--workload", "internlm2-chat", "--seed", "5",
                   "--seconds", "1", "--trace", "0"], rehearse=True)
    out, err = capsys.readouterr()
    assert rc != 0
    assert '"correct"' not in out
    assert "warm-up" not in err
    return err


def test_unknown_family_ends_the_run(monkeypatch, capsys):
    err = _refused(monkeypatch, capsys,
                   lambda c: c.update(family="no_such_family"))
    assert "no family module 'no_such_family'" in err


def test_registry_mismatch_ends_the_run(monkeypatch, capsys):
    err = _refused(monkeypatch, capsys, lambda c: c.update(rope_theta=1e4))
    assert "at ['rope_theta']" in err


def test_every_family_module_supplies_the_interface():
    for name in sorted(os.listdir(families.HERE)):
        if name.endswith(".py") and name != "__init__.py":
            fam = families.load({"family": name[:-3]})
            for attr in ("Dims", "check_program", "make_weights", "gaps",
                         "step_work", "decode_attn", "prefill_attn",
                         "kv_bytes_per_token"):
                assert hasattr(fam, attr), (name, attr)
    assert families.load({}) is dense


# -- a second family, as new files alone ------------------------------------------
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# h2o-danube3's registry entry (windowed attention, window 4096; 8 in the
# rehearsal's reduced entry), with internlm2's engine geometry
DANUBE = {
    "registry_key": "h2o-danube-3-4b", "family": "windowed",
    "source": "arXiv:2401.16818", "num_hidden_layers": 24,
    "hidden_size": 3840, "intermediate_size": 10240,
    "num_attention_heads": 32, "num_key_value_heads": 8, "head_dim": 120,
    "vocab_size": 32000, "rms_norm_eps": 1e-05, "rope_theta": 10000.0,
    "sliding_window": 4096, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16"}
DANUBE_ENTRY = {"name": "h2o-danube3-4b", "source": "arXiv:2401.16818",
                "file": "chipbench/configs/h2o-danube3-4b.json",
                "reduced": [], "why": "sliding-window attention"}
DANUBE_CELL = {"name": "danube3-chat", "config": "h2o-danube3-4b",
               "traffic": "chat-azure", "chips": 1,
               "why": "windowed attention under the chat mix"}


def _windowed(monkeypatch):
    spec = importlib.util.spec_from_file_location(
        "windowed_family", os.path.join(DATA, "windowed.py"))
    mod = importlib.util.module_from_spec(spec)
    monkeypatch.setitem(sys.modules, spec.name, mod)
    spec.loader.exec_module(mod)
    return mod


def test_windowed_family_asks_only_which_keys_a_query_sees(monkeypatch):
    win = _windowed(monkeypatch)
    wide = win.Dims(**dataclasses.asdict(HAND), window=64)
    narrow = dataclasses.replace(wide, window=4)
    # a window wider than every context counts as dense does
    for fn, args in (("decode_attn", ([6, 8],)),
                     ("prefill_attn", ([(5, 3)],)),
                     ("step_work", ([6, 8], [(5, 3)], 3))):
        assert getattr(win, fn)(wide, *args) == getattr(dense, fn)(HAND,
                                                                   *args)
    # contexts 6 and 8 see 4 keys each: 8 keys x 32 operations, 8 K/V
    # rows of 16 bytes and two q/o rows of 32 bytes
    assert win.decode_attn(narrow, [6, 8]) == (256.0, 192.0)
    # the forward: as dense's where the window holds the whole prompt,
    # not where it is shorter
    dm = reduced_dims("internlm2-1.8b")
    w = jax.jit(dense.make_weights, static_argnums=0)(
        dm, reference.param_key(SEED))
    toks = jax.numpy.asarray([(7 * i + 3) % dm.vocab for i in range(24)])
    want = np.asarray(dense.hidden(w, toks, dm))
    wide = win.Dims(**dataclasses.asdict(dm), window=24)
    np.testing.assert_array_equal(np.asarray(dense.hidden(w, toks, wide)),
                                  want)
    short = np.asarray(dense.hidden(w, toks, dataclasses.replace(wide,
                                                                 window=8)))
    np.testing.assert_array_equal(short[:8], want[:8])
    assert np.abs(short[8:] - want[8:]).max() > 1e-2


def _tree(root):
    out = {}
    for d, dirs, files in os.walk(root):
        dirs[:] = [x for x in dirs if x not in ("tests", "__pycache__")]
        for f in files:
            p = os.path.join(d, f)
            out[os.path.relpath(p, root)] = open(p, "rb").read()
    return out


@pytest.mark.parametrize("family", ["windowed", "dense"])
def test_second_family_lands_as_new_files(tmp_path, family):
    base = json.load(open(os.path.join(
        _paths.ROOT, "chipbench", "configs", "internlm2-1.8b.json")))
    config = dict(DANUBE, family=family, engine=base["engine"],
                  rehearsal=base["rehearsal"])
    new = {"chipbench/families/windowed.py":
           open(os.path.join(DATA, "windowed.py")).read(),
           DANUBE_ENTRY["file"]: json.dumps(config)}
    cell = json.load(open(os.path.join(_paths.ROOT, "chipbench", "cells",
                                       "internlm2-chat.json")))
    res, err = rehearse_added_cell(
        tmp_path, DANUBE_CELL, cell, seed=2 ** 36 + 9, config=DANUBE_ENTRY,
        files=new, args=("--seconds", "3", "--trace", "1"))
    # the harness judged by the module the configuration named: windowed
    # attention passes its own reference and fails the dense one
    assert res["correct"] is (family == "windowed"), err[-2000:]
    assert res["checks"]["compared_tokens"]["value"] > 10
    # three new files, and every other file as the tree has it
    written = set(new) | {"chipbench/cells/danube3-chat.json"}
    copied = _tree(tmp_path)
    tree = {os.path.join("chipbench", k): v for k, v in
            _tree(os.path.join(_paths.ROOT, "chipbench")).items()}
    assert set(copied) - set(tree) == written | {"BENCHMARK.json"}
    for k, v in tree.items():
        assert copied[k] == v, k
    spec = json.load(open(os.path.join(_paths.ROOT, "BENCHMARK.json")))
    got = json.loads(copied["BENCHMARK.json"])
    spec["configs"].append(DANUBE_ENTRY)
    spec["workloads"].append(DANUBE_CELL)
    assert got == spec           # entries added, none changed
