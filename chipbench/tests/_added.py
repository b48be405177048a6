"""Cells that land as data alone, rehearsed in a copy of the benchmark
with one more ``BENCHMARK.json`` entry and its files: the backlog cell
of the paper's offline setting and the four-replica cell with a reclaim
(``PERF.md``, Open questions)."""

import json
import os
import shutil
import subprocess
import sys

import _paths

# a backlog of four requests per engine slot, all due as the pre-roll
# starts (ShuntServe paper section 7, offline setting)
OFFLINE_DECODE = {
    "generator": "mix",
    "arrivals": {"kind": "backlog", "per_slot": 4},
    "input": {"mean": 256, "sigma": 0.9, "min": 32, "max": 1024},
    "output": {"mean": 640, "sigma": 0.9, "min": 128, "max": 2048}}
CHECK = {"sample": 6, "gap_limit_sd": 0.45, "mean_gap_limit_sd": 0.03}
OFFLINE_CELL = {"rate_rps": 0.0, "preroll_s": 10.0, "trace_s": 8.0,
                "replicas": 1, "check": CHECK}
OFFLINE = {"name": "internlm2-offline-decode", "config": "internlm2-1.8b",
           "traffic": "offline-decode", "chips": 1,
           "why": "a backlog at 64 rows: decode-bound throughput"}
# four replicas behind the router, chip1 reclaimed a third of the way
# into the window, its live KV migrated through the store (ShuntServe
# paper section 5); the rate is the rehearsal's, which keeps every
# reduced engine busy
RECLAIM = {"name": "internlm2-reclaim-x4", "config": "internlm2-1.8b",
           "traffic": "chat-azure", "chips": 4,
           "why": "four replicas, chip1 reclaimed, KV migrated"}
RECLAIM_CELL = {"rate_rps": 16.0, "preroll_s": 20.0, "drain_max_s": 120.0,
                "trace_s": 8.0, "replicas": 4, "store": True,
                "kv_migration": True, "reclaim_at_frac": 0.333,
                "reclaim_replica": 1, "check": CHECK}
RECLAIM_LAYER = "Migration (serving/server.py GlobalServer)"
RECLAIM_METRICS = [
    {"name": "migration_gap_p90_ms.reclaim", "unit": "ms", "better": "lower",
     "source": "host_clock", "layer": RECLAIM_LAYER, "moves": "ttft_p90_ms",
     "workloads": [RECLAIM["name"]]},
    {"name": "kv_attach_pct.reclaim", "unit": "%", "better": "higher",
     "source": "program_counter", "layer": RECLAIM_LAYER,
     "moves": "ttft_p90_ms", "workloads": [RECLAIM["name"]]}]


def last_json(out: str):
    return json.loads(out.strip().splitlines()[-1])


def rehearse_added_cell(tmp_path, workload, cell, devices=1, traffic=None,
                        seed=2 ** 33 + 7, args=("--seconds", "3",
                                                "--trace", "1"),
                        config=None, files=None, per_layer=(),
                        end_to_end=()):
    """One run of ``workload`` (its entry, cell file and, where new, its
    traffic file added to a copy of the benchmark; where new, its
    configuration's entry ``config``; its ``per_layer`` metric entries;
    the names of the ``end_to_end`` metrics it reports beside
    ``setup_s``; and ``files``, text by path under the copy) in a fresh
    process on ``devices`` virtual CPU devices; returns the result line
    and stderr."""
    shutil.copytree(os.path.join(_paths.ROOT, "chipbench"),
                    tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.load(open(os.path.join(_paths.ROOT, "BENCHMARK.json")))
    spec["workloads"].append(workload)
    if config is not None:
        spec["configs"].append(config)
    spec["per_layer"] += list(per_layer)
    for m in spec["end_to_end"]:
        if m["name"] in end_to_end:
            m["workloads"].append(workload["name"])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    for path, text in (files or {}).items():
        (tmp_path / path).write_text(text)
    (tmp_path / "chipbench" / "cells" / (workload["name"] + ".json")
     ).write_text(json.dumps(cell))
    if traffic is not None:
        (tmp_path / "chipbench" / "traffic" / (workload["traffic"] + ".json")
         ).write_text(json.dumps(traffic))
    code = ("import sys; sys.path[:0] = [sys.argv[1], sys.argv[2]]\n"
            "from chipbench import run\n"
            "sys.exit(run.main(sys.argv[3:], rehearse=True))")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    p = subprocess.run([sys.executable, "-c", code, str(tmp_path),
                        os.path.join(_paths.ROOT, "src"), "--workload",
                        workload["name"], "--seed", str(seed), *args],
                       env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    return last_json(p.stdout), p.stderr
