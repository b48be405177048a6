"""Dry-run machinery on a small host-device mesh (subprocess, so the 8-device
XLA flag never pollutes this test process's single-device view)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    from repro.configs import get_config
    from repro.configs.shapes import ShapeSpec
    from repro.launch.steps import build_step, lower_step
    from repro.launch import hlo_utils
    from repro.launch.hlo_costs import normalize_cost_analysis
    from repro.launch.mesh import make_mesh

    out = {}
    cfg = get_config("internlm2-1.8b").reduced()
    mesh = make_mesh((2, 4), ("data", "model"))
    for shape in [ShapeSpec("t", 64, 8, "train_step"),
                  ShapeSpec("p", 64, 4, "prefill_step"),
                  ShapeSpec("d", 64, 8, "serve_step")]:
        built = build_step(cfg, shape, mesh, attn_chunk=32)
        comp = lower_step(built, mesh).compile()
        ca = normalize_cost_analysis(comp.cost_analysis())
        cb = hlo_utils.collective_bytes(comp.as_text(), built.trip_hints)
        out[shape.step] = {"flops": ca.get("flops", -1.0),
                           "coll": cb["total"]}
    # multi-pod mesh: DP serve + PP serve
    mesh3 = make_mesh((2, 2, 2), ("pod", "data", "model"))
    for pp in (False, True):
        built = build_step(cfg, ShapeSpec("d", 64, 8, "serve_step"), mesh3,
                           serve_pp=pp)
        comp = lower_step(built, mesh3).compile()
        cb = hlo_utils.collective_bytes(comp.as_text(), built.trip_hints)
        key = "serve_pp" if pp else "serve_dp_multipod"
        out[key] = {"coll": cb["total"],
                    "split": built.meta.get("pp_split")}
    # hybrid family lowers too (zamba2 reduced)
    zcfg = get_config("zamba2-2.7b").reduced()
    built = build_step(zcfg, ShapeSpec("d", 64, 8, "serve_step"), mesh)
    lower_step(built, mesh).compile()
    out["hybrid_serve_ok"] = True
    print("JSON::" + json.dumps(out))
""")


@pytest.mark.slow
def test_dryrun_small_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert res.returncode == 0, res.stderr[-4000:]
    line = [l for l in res.stdout.splitlines() if l.startswith("JSON::")][0]
    out = json.loads(line[len("JSON::"):])
    assert out["train_step"]["flops"] > 0
    assert out["train_step"]["coll"] > 0          # FSDP/TP collectives exist
    assert out["serve_step"]["coll"] > 0
    assert out["serve_pp"]["split"] is not None
    assert sum(out["serve_pp"]["split"]) == 4     # reduced config layers
    assert out["hybrid_serve_ok"]
