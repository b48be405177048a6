"""The chip smoke script, rehearsed in-process on the CPU backend: the
reduced config with the kernels in interpret mode, all phases, and the
contract's last line reporting the device honestly; and where the
persistent compile cache goes."""

import importlib.util
import json
import os
import pathlib
import sys

import jax

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _load():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod       # dataclasses resolve their module
    spec.loader.exec_module(mod)
    return mod


def test_rehearsal_passes_and_reports_cpu(capsys):
    rc = _load().main(["--rehearse"])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    assert "served 8/8 requests" in "\n".join(out)
    last = json.loads(out[-1])
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}


def test_without_tpu_fails_and_prints_no_result(capsys):
    rc = _load().main([])
    out = capsys.readouterr().out
    assert rc != 0
    assert '"ok"' not in out


def test_compile_cache_follows_env_else_a_fixed_checkout_path(monkeypatch,
                                                              tmp_path):
    from repro.launch import compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # nothing set
    assert compile_cache.CACHE_DIR == (
        pathlib.Path(ROOT).resolve() / ".jax_cache")
