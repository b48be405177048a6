"""One cell end to end at the reduced configuration on the CPU (a
rehearsal path the chip path never takes), the engine counters the
harness reads, and the runs that must fail: no TPU, and a checkout that
holds only the benchmark. (The four-chip reclaim cell's rehearsals are
``test_chipbench_reclaim.py``.)"""

import json
import os
import shutil
import subprocess
import sys

import _paths  # noqa: F401
from _added import (OFFLINE, OFFLINE_CELL, OFFLINE_DECODE, last_json,
                    rehearse_added_cell)

import jax
import numpy as np

from chipbench import reference, run, serve


def test_chat_cell_rehearsal_is_correct_and_names_the_cpu(capsys):
    rc = run.main(["--workload", "internlm2-chat", "--seed", str(2 ** 34 + 5),
                   "--seconds", "2", "--trace", "1"], rehearse=True)
    res = last_json(capsys.readouterr().out)
    assert rc == 0
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["count"] == len(jax.devices())
    assert res["checks"]["window_compiles"]["value"] == 0
    assert list(res)[-1] == "checks"
    # a CPU run carries no device-trace metric
    spec = json.load(open(os.path.join(_paths.ROOT, "BENCHMARK.json")))
    device_metrics = {m["name"] for m in spec["per_layer"]
                      if m["source"] == "device_trace"}
    assert not device_metrics & set(res["metrics"])
    for name in ("mfu_pct.chat", "prefill_mfu_pct.chat",
                 "queue_wait_p90_ms.chat"):
        assert name in res["metrics"], name
    assert 0 < res["metrics"]["prefill_mfu_pct.chat"]["value"] < 100


def test_without_tpu_fails_and_prints_no_result(capsys):
    rc = run.main(["--workload", "internlm2-chat", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert '"correct"' not in capsys.readouterr().out


def test_benchmark_alone_fails(tmp_path):
    shutil.copy(os.path.join(_paths.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(_paths.ROOT, "chipbench"),
                    tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "internlm2-chat", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_offline_cell_rehearsal_is_correct(tmp_path):
    res, err = rehearse_added_cell(tmp_path, OFFLINE, OFFLINE_CELL,
                                   traffic=OFFLINE_DECODE)
    assert res["correct"] is True, err[-2000:]
    assert res["checks"]["window_compiles"]["value"] == 0
    assert res["attempted"] == 4 * 4 and res["failed"] == 0   # 4 per slot
    # the backlog is due as the pre-roll starts: the window opens full
    steps = int(err.split("steps in window ")[1].split(";")[0])
    assert steps > 0
    assert "mfu_pct.chat" not in res["metrics"]


def test_counters_sum_every_engine_counter_as_before():
    """``counters`` sums every integer field of ``EngineStats``; on the
    chat cell's rehearsal server after its warm-up, the four it summed by
    name before read what they read then (pinned from that code)."""
    from repro.configs import get_config
    from repro.models import build_model
    config = run.load_json(os.path.join(_paths.ROOT, "chipbench", "configs",
                                        "internlm2-1.8b.json"))
    cell = run.load_json(os.path.join(_paths.ROOT, "chipbench", "cells",
                                      "internlm2-chat.json"))
    cfg = get_config(config["registry_key"]).reduced()
    geo = serve.geometry(config, True)
    seed = 2 ** 34 + 5
    params = build_model(cfg).init(reference.param_key(seed))
    srv = serve.build_server(cfg, geo, [params], jax.devices()[:1], cell)
    rng = np.random.default_rng(seed ^ 0x3C3C)
    assert serve.warm_up(
        srv, serve.warmup_requests(geo, cfg.vocab, False, rng)) == 9
    got = run.counters([p.engine.stats for p in srv.pipelines])
    assert {k: got[k] for k in ("tokens_out", "prefills", "decode_steps",
                                "prefix_shared_tokens")} == {
        "tokens_out": 26.0, "prefills": 13.0, "decode_steps": 5.0,
        "prefix_shared_tokens": 0.0}
    assert got["kv_imports"] == 0.0 and got["prefill_chunks"] > 0
    assert set(got) == {f for f in vars(srv.pipelines[0].engine.stats)}
