"""One run of one benchmark cell on the chip:

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, its traffic mix and its per-layer metrics are
found by name from ``BENCHMARK.json``: ``chipbench/cells/<cell>.json``
(rate, pre-roll, trace and check settings, replicas),
``chipbench/configs/<config>.json``, the model family that configuration
names (``chipbench/families/<family>.py``), ``chipbench/traffic/<mix>.json``
and ``chipbench/layer_metrics/<metric>.py``.

Set-up (``setup_s``, process start to window open): params made on the
chip from the seed, one ``GlobalServer`` pipeline per chip, a warm-up
that reaches every program shape the cell's traffic uses (in a cell with
a reclaim, also the export and attach of every block count the reclaim
can move), and the pre-roll, in which the traffic's arrivals before the
window fill the server to its steady state. Then the window:
``--seconds`` of the open loop on the host clock, with nothing compiled
inside it; a cell's ``reclaim_at_frac`` reclaims its ``reclaim_replica``
that far into it. After it, chat cells keep stepping until every request
that arrived in the window has its first token, and every request the
reclaim moved a token since. Then ``memory_peak_bytes`` is read, the
program's state freed, and the served tokens of a sample of requests
compared with the plain float32 reference of the configuration's model
family (``chipbench/check.py``, ``chipbench/families/``).

With ``--trace 0`` the result carries the cell's end-to-end metrics;
with ``--trace 1`` a few seconds in the middle of the window are traced
and the result carries the per-layer metrics. The last line of standard
output is one JSON object; a run that finds no TPU, or fewer chips than
the cell asks for, exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# run as a script, this directory heads sys.path; its module names (trace)
# must not shadow the standard library's
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]


class RunError(RuntimeError):
    """The run cannot produce a result."""


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> Dict:
    """The cell's entry of BENCHMARK.json with its data files."""
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise RunError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    cfg_entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    layer = [m for m in spec["per_layer"]
             if "workloads" not in m or name in m["workloads"]]
    return {"workload": w,
            "cell": load_json(os.path.join(HERE, "cells", name + ".json")),
            "config": load_json(os.path.join(ROOT, cfg_entry["file"])),
            "traffic": load_json(os.path.join(HERE, "traffic",
                                              w["traffic"] + ".json")),
            "end_to_end": e2e, "per_layer": layer}


class CompileCounter:
    """Counts backend compiles and their seconds (persistent-cache hits
    are not compiles and are counted apart)."""

    def __init__(self, monitoring):
        self.monitoring = monitoring
        self.n = 0
        self.secs = 0.0
        self.cache_hits = 0
        self.names: List[str] = []
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs
            self.names.append(str(kw.get("fun_name", "?")))

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def close(self) -> None:
        self.monitoring.unregister_event_duration_listener(self._on_duration)
        self.monitoring.unregister_event_listener(self._on_event)


def enable_cache(jax) -> str:
    """The program's persistent compile cache (``$JAX_COMPILATION_CACHE_DIR``
    or a fixed directory inside the checkout), holding every program."""
    from repro.launch.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def counters(stats: List) -> Dict[str, float]:
    """Every integer field of ``EngineStats``, summed over ``stats``."""
    out: Dict[str, float] = {}
    for st in stats:
        for f in dataclasses.fields(st):
            v = getattr(st, f.name)
            if isinstance(v, int) and not isinstance(v, bool):
                out[f.name] = out.get(f.name, 0.0) + v
    return out


def main(argv: Optional[List[str]] = None, rehearse: bool = False,
         fault=None) -> int:
    """``rehearse`` (tests only): the reduced config on whatever backend
    JAX finds, without the compile cache. ``fault(srv)`` breaks the served
    path before warm-up (tests; ``--fault`` names one of
    ``chipbench/faults.py`` on the chip)."""
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from chipbench import faults
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # tuning and calibration only; the benchmark's runs never pass these
    ap.add_argument("--rate", type=float, default=None,
                    help="override the cell's arrival rate (rate sweep)")
    ap.add_argument("--control", choices=("fp8",), default=None,
                    help="judge the lower-precision control in the "
                    "program's place (it must come out not correct)")
    ap.add_argument("--fault", choices=sorted(faults.FAULTS), default=None,
                    help="plant a fault beneath the served path (it must "
                    "come out not correct)")
    ap.add_argument("--dump-trace", default=None,
                    help="write the compact trace here (.json.gz)")
    args = ap.parse_args(argv)
    if args.fault is not None:
        fault = faults.FAULTS[args.fault]
    try:
        return run(args, rehearse, fault)
    except RunError as e:
        log(f"FAILED: {e}")
        return 1


def run(args, rehearse: bool, fault) -> int:
    from chipbench import families
    data = load_cell(args.workload)
    config = data["config"]
    try:
        family = families.load(config)
    except LookupError as e:
        raise RunError(str(e))
    try:
        import jax
        from repro.configs import get_config
        from repro.models import build_model
    except ImportError as e:
        raise RunError(f"cannot import the system under test: {e}")
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from chipbench import check, peaks as peaks_mod, reference, serve, stats
    from chipbench import trace as tr
    from chipbench.readings import Reading, reader
    from chipbench.traffic import generator

    w, cell = data["workload"], data["cell"]
    cfg = get_config(config["registry_key"])
    differ = family.check_program(cfg, config)
    if differ:
        raise RunError(f"the program's registry entry {cfg.name!r} differs "
                       f"from the configuration file at {differ}")
    chips = int(w["chips"])
    devices = jax.devices()
    dev0 = devices[0]
    if dev0.platform != "tpu" and not rehearse:
        raise RunError(f"no TPU: JAX found {dev0.platform}")
    if len(devices) < chips:
        raise RunError(f"cell needs {chips} chips, JAX found {len(devices)}")
    devices = devices[:chips]
    pk = None if rehearse else peaks_mod.peaks(dev0.device_kind)
    if not rehearse:
        log(f"compile cache: {enable_cache(jax)}")
    compiles = CompileCounter(jax.monitoring)

    if rehearse:
        cfg = cfg.reduced()
        dims = family.Dims.from_program(cfg)
        pk = {"flops_bf16": 197e12, "hbm_bytes_s": 819e9}
    else:
        dims = family.Dims.from_config(config)
    geo = serve.geometry(config, rehearse)
    replicas = int(cell.get("replicas", chips))

    # -- set-up ---------------------------------------------------------------
    model = build_model(cfg)
    key = reference.param_key(args.seed)
    params = []
    for d in devices[:replicas]:
        init = jax.jit(model.init, out_shardings=SingleDeviceSharding(d))
        params.append(init(key))
    jax.block_until_ready(params)
    srv = serve.build_server(cfg, geo, params, devices[:replicas], cell)
    del params
    if fault is not None:
        fault(srv)
    T = float(args.seconds)
    rate = args.rate if args.rate is not None else float(
        cell.get("rate_rps", 0.0))
    preroll = float(cell.get("preroll_s", 0.0))
    traffic = data["traffic"]
    gen = generator(traffic["generator"])
    if rehearse:            # the reduced model drains a backlog in a second
        preroll = min(preroll, 0.2)
        traffic = gen.scaled(traffic, geo["max_len"] / 4096)
    reqs = gen.generate(traffic, seed=args.seed, rate=rate,
                        t0=-preroll, t1=T,
                        slots=geo["max_batch"] * replicas, vocab=cfg.vocab)
    log(f"traffic: {len(reqs)} requests, {sum(len(r.prompt) for r in reqs)}"
        f" prompt tokens, {sum(r.max_new for r in reqs)} output tokens")
    sp = bool(data["traffic"].get("shared_prefix"))
    wrng = np.random.default_rng(args.seed ^ 0x3C3C)
    warm_steps = serve.warm_up(
        srv, serve.warmup_requests(geo, cfg.vocab, sp, wrng))
    compiles_setup = compiles.n
    log(f"warm-up: {warm_steps} steps, {compiles.n} compiles "
        f"({compiles.secs:.1f} s), {compiles.cache_hits} cache hits")
    reclaim = None
    if cell.get("reclaim_at_frac") is not None:
        reclaim = (float(cell["reclaim_at_frac"]) * T,
                   f"chip{int(cell.get('reclaim_replica', 1))}")
        nbs = serve.migration_block_counts(reqs, reclaim[0],
                                           geo["block_size"], geo["max_len"])
        n = compiles.n
        attaches = serve.warm_migration(srv, reclaim[1], nbs, cfg.vocab,
                                        wrng)
        compiles_setup = compiles.n
        log(f"migration warm-up: {len(nbs)} block counts "
            f"({nbs[0] if nbs else 0}-{nbs[-1] if nbs else 0}) exported on "
            f"{reclaim[1]}, {attaches} attaches, {compiles.n - n} compiles")
    t_open = time.perf_counter() + preroll
    drv = serve.Driver(srv, reqs, t_open, geo["prefill_chunk"],
                       trace_spans=bool(args.trace))
    drv.run_until(0.0, None)                     # pre-roll
    setup_s = time.perf_counter() - T_START
    queued_open = sum(len(p.queue) for p in srv.pipelines)
    log(f"setup_s {setup_s:.2f} (pre-roll {preroll:.0f} s); compiles so far "
        f"{compiles.n} ({compiles.secs:.1f} s), cache hits "
        f"{compiles.cache_hits}")
    c0, n_steps0 = counters(drv.engine_stats()), len(drv.steps)
    compiles_open = compiles.n

    # -- the window ------------------------------------------------------------
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
    trace_s = min(float(cell.get("trace_s", 5.0)), T)
    t_tr0 = T / 2 - trace_s / 2
    state = {"on": False, "done": False, "span": None}

    def trace_hook(d):
        now = d.now()
        if not state["on"] and not state["done"] and now >= t_tr0:
            jax.profiler.start_trace(tdir, profiler_options=tr.profile_options())
            state["span"] = jax.profiler.TraceAnnotation(tr.WINDOW_SPAN)
            state["span"].__enter__()
            state["on"], d.tracing = True, True
        elif state["on"] and now >= t_tr0 + trace_s:
            stop_trace(d)

    def stop_trace(d):
        state["span"].__exit__(None, None, None)
        jax.profiler.stop_trace()
        state["on"], state["done"], d.tracing = False, True, False

    drv.run_until(T, reclaim, trace_hook if args.trace else None)
    if state["on"]:
        stop_trace(drv)
    for at, inst in drv.reclaimed:
        log(f"reclaimed {inst} at {at:.2f} s of the window: "
            f"{len(drv.moved)} requests moved, "
            f"{sum(1 for t in drv.moved if t.tokens_at_reclaim)} of them "
            f"live with their KV")
    window_steps = drv.steps[n_steps0:]
    c1 = counters(drv.engine_stats())
    compiles_window = compiles.n - compiles_open
    chat = rate > 0
    unsent = sum(1 for t in drv.tracked[drv.next:] if t.req.arrival_s < T)
    log(f"window arrivals unsent at its close: {unsent}")
    if chat or drv.moved:
        drv.drain_first_tokens(T, float(cell.get("drain_max_s", 120.0)))
    stranded = len(drv.stranded())

    # -- result numbers ----------------------------------------------------------
    arrived = [t for t in drv.tracked if 0.0 <= t.req.arrival_s < T]
    attempted = len(arrived) if chat else len(drv.tracked)
    failed = (sum(1 for t in arrived if not t.stamps) if chat else
              sum(1 for t in drv.tracked
                  if t.sreq is not None and not t.stamps
                  and t.left_queue_s is not None))
    stamps = [t.stamps for t in drv.tracked]
    e2e = {"output_tok_s": stats.rate(stats.tokens_in(stamps, T), T),
           "setup_s": setup_s}
    if chat:
        tt = stats.ttfts([t.req.arrival_s for t in arrived],
                         [t.stamps[0] if t.stamps else None for t in arrived],
                         float("inf"))
        itl = stats.inter_token_gaps(stamps, T)
        e2e["ttft_p90_ms"] = 1000.0 * (stats.percentile(tt, 90) or 0.0)
        e2e["itl_p95_ms"] = 1000.0 * (stats.percentile(itl, 95) or 0.0)
        half = [[t.stamps[0] - t.req.arrival_s for t in arrived
                 if t.stamps and (t.req.arrival_s < T / 2) == first]
                for first in (True, False)]
        log(f"queue: {queued_open} at window open; ttft p50 first half "
            f"{1000 * (stats.percentile(half[0], 50) or 0):.0f} ms, second "
            f"half {1000 * (stats.percentile(half[1], 50) or 0):.0f} ms")
        log(f"requests in window {len(arrived)}, ttft samples {len(tt)}, "
            f"itl samples {len(itl)}; ttft p50 "
            f"{1000 * (stats.percentile(tt, 50) or 0):.1f} ms, ttft p90 "
            f"{e2e['ttft_p90_ms']:.1f} ms, itl p50 "
            f"{1000 * (stats.percentile(itl, 50) or 0):.1f} ms")
    late = drv.late
    log(f"generator lateness: p50 {1000 * (stats.percentile(late, 50) or 0):.1f}"
        f" ms, max {1000 * max(late, default=0):.1f} ms over {len(late)} "
        f"submits; queued at window end "
        f"{sum(len(p.queue) for p in srv.pipelines)}; steps in window "
        f"{len(window_steps)}; compiles: set-up {compiles_setup}, window "
        f"{compiles_window}")
    if compiles_window:
        log(f"compiled in the window: {compiles.names[compiles_open:]}")

    layer: Dict[str, float] = {}
    busy = window_ns = None
    breakdown = None
    if args.trace:
        tpath = tr.find_xplane(tdir)
        trace = tr.load(tpath)
        if args.dump_trace:
            tr.save(trace, args.dump_trace)
            shutil.copy(tpath, args.dump_trace + ".xplane.pb")
        shutil.rmtree(tdir, ignore_errors=True)
        spans = [(t, t + d) for n, t, d in trace["host"] if n == "cb.step"]
        win = tr.window(trace)
        traced_recs = [s for s in drv.steps if s.traced]
        pairs = [(s, sp_) for s, sp_ in zip(traced_recs, spans)
                 if win[0] <= sp_[0] and sp_[1] <= win[1]]
        reading = Reading(
            dims=dims, family=family, peaks=pk, max_batch=geo["max_batch"],
            window_s=T, steps=window_steps,
            tracked=drv.tracked,
            counters={k: c1[k] - c0[k] for k in c0}, trace=trace,
            traced=pairs)
        for m in data["per_layer"]:
            v = reader(m["name"])(reading)
            if v is not None:
                layer[m["name"]] = v
        busy = tr.busy_s(trace, win)
        window_ns = win[1] - win[0]
        breakdown = {"device_ops": tr.top_ops(trace, win),
                     "idle_gaps": tr.idle_gaps(trace, win)}
        log(f"trace: {len(pairs)} steps, busy {busy:.3f} s of "
            f"{window_ns / 1e9:.3f} s")
    for name, v in sorted(layer.items()):
        log(f"per-layer {name} = {v}")

    # -- memory, then free the program, then the reference ------------------------
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    peak = max(peaks)
    log(f"memory peak by chip: {peaks} (limit "
        f"{(dev0.memory_stats() or {}).get('bytes_limit')})")
    sample = check.pick_sample(drv.tracked, int(cell["check"]["sample"]),
                               geo["prefill_chunk"], args.seed)
    log("sample: served tokens " + ", ".join(
        f"{len(t.sreq.generated)}" + (f" (moved after {t.tokens_at_reclaim})"
                                      if t.tokens_at_reclaim else "")
        for t in sample))
    for t in sample:
        t.sreq = _Served(list(t.sreq.generated))
    for p in srv.pipelines:             # free the program's device state
        for x in jax.tree.leaves((p.engine.params, p.engine.cache)):
            x.delete()
    del drv, srv
    gc.collect()
    t_ref = time.perf_counter()
    with jax.default_device(devices[0]):
        weights = jax.jit(family.make_weights, static_argnums=0)(dims, key)
        got = check.compare(family, weights, dims, sample, args.control)
    del weights
    checks = check.judged(got, cell["check"], bool(args.control))
    checks["compared_tokens"] = {"value": got["compared_tokens"], "limit": 1}
    checks["window_compiles"] = {"value": compiles_window, "limit": 0}
    exact = ["window_compiles"]
    if reclaim is not None:
        checks["moved_without_token"] = {"value": stranded, "limit": 0}
        exact.append("moved_without_token")
    correct = (all(checks[k]["value"] <= checks[k]["limit"]
                   for k in [*check.LIMIT_KEYS, *exact])
               and got["compared_tokens"] > 0)
    log(f"reference: {len(sample)} requests, {got['compared_tokens']} "
        f"served tokens compared in {time.perf_counter() - t_ref:.1f} s; "
        f"reference argmax agrees on {got['reference_argmax_agree']}")
    log(f"gaps: widest {got['widest_gap_sd']!r} mean {got['mean_gap_sd']!r}")
    if args.control:
        log(f"control {args.control} (judged in the program's place): "
            f"widest {got['control_widest_gap_sd']!r} mean "
            f"{got['control_mean_gap_sd']!r}, argmax agrees on "
            f"{got['control_argmax_agree']}")

    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if args.trace:
        device["busy_s"] = busy
        device["window_s"] = window_ns / 1e9
    units = {m["name"]: m["unit"] for m in data["end_to_end"]
             + data["per_layer"]}
    values = layer if args.trace else {
        m["name"]: e2e[m["name"]] for m in data["end_to_end"]}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed,
              "metrics": {k: {"value": v, "unit": units[k]}
                          for k, v in values.items()},
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    compiles.close()
    for k in check.LIMIT_KEYS:
        log(f"{k} {checks[k]['value']!r} limit {checks[k]['limit']!r}")
    log(f"compared_tokens {got['compared_tokens']} limit >= 1")
    for k in exact:
        log(f"{k} {checks[k]['value']} limit {checks[k]['limit']}")
    print(json.dumps(result), flush=True)
    return 0


class _Served:
    """What the check keeps of a served request once the program is freed."""

    def __init__(self, generated):
        self.generated = generated


if __name__ == "__main__":
    sys.exit(main())
