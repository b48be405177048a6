"""Smoke run of the serving path on a TPU: ``python3 chip_smoke.py``.

One process drives the main path — ``GlobalServer`` -> ``Engine`` -> paged
KV pool -> Pallas attention kernels — at the full published width of
internlm2-1.8b (24 layers, d_model 2048, 16 heads over 8 KV heads,
head_dim 128, bf16), with random params made on the chip from ``--seed``.

Default (one chip):

1. kernels vs oracles: two prompts are prefilled in 512-token chunks and
   then decode 8 steps, once through the Pallas kernels and once through
   the jnp oracles, with the same params and the same fed tokens. Every
   logit row must agree within ``LOGIT_TOL`` (relative to the oracle's
   largest |logit|: bf16 carries 8 mantissa bits, and the two paths round
   the attention probabilities at different points across 24 layers).
2. serve: 8 requests (prompts of 64-1500 tokens, 32 new tokens each)
   through ``GlobalServer`` with chunked prefill (chunk 512). Every request
   must finish, and the engine's decode and chunk dispatches must compile
   to a Pallas kernel (``tpu_custom_call`` in the compiled HLO).

``--chips 4``: only the replica phase and what it is compared with. One
internlm2-1.8b pipeline per chip behind the router, each holding its
params and KV cache on its own chip; one pipeline is reclaimed mid-run
(``interrupt_instance``, KV migrated through the tensor store). Every
request must finish, with greedy outputs equal to those of the same
requests on the same chips without the reclaim.

``--rehearse``: the same phases on whatever backend JAX finds (the CPU in
tests), at the reduced config with the kernels in interpret mode, and
without the compile-cache directory. Without it the script fails on any
platform but ``tpu``.

Wall times printed here are smoke timings, not benchmark results. The
last line of standard output is one JSON object, ``{"ok": true,
"device": {"platform": ..., "kind": ..., "count": ...}}``; on a failure
the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ARCH = "internlm2-1.8b"
LOGIT_TOL = 5e-2
DECODE_STEPS = 8


class SmokeFailure(RuntimeError):
    """A phase of the smoke run did not meet its check."""


@dataclasses.dataclass(frozen=True)
class Plan:
    reduced: bool
    max_len: int
    chunk: int
    prompt_lens: tuple        # serve phase, one request each
    new_tokens: int
    check_lens: tuple         # kernel-vs-oracle phase, one row each
    interrupt_round: int      # replica phase


FULL = Plan(reduced=False, max_len=2048, chunk=512,
            prompt_lens=(64, 200, 384, 512, 700, 1024, 1300, 1500),
            new_tokens=32, check_lens=(1100, 700), interrupt_round=12)
REHEARSAL = Plan(reduced=True, max_len=256, chunk=64,
                 prompt_lens=(8, 20, 40, 64, 70, 100, 130, 150),
                 new_tokens=8, check_lens=(110, 70), interrupt_round=6)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileCounter:
    """Counts backend compiles and their seconds (persistent-cache hits
    are not compiles and are counted apart)."""

    def __init__(self, monitoring):
        self.monitoring = monitoring
        self.n = 0
        self.secs = 0.0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def close(self) -> None:
        self.monitoring.unregister_event_duration_listener(self._on_duration)
        self.monitoring.unregister_event_listener(self._on_event)

    def line(self) -> str:
        return (f"compiles={self.n} compile_s={self.secs:.1f} "
                f"persistent_cache_hits={self.cache_hits}")


def make_params(jax, model, seed: int, device):
    """Random params, made on ``device`` itself (no host copy)."""
    from jax.sharding import SingleDeviceSharding
    init = jax.jit(model.init, out_shardings=SingleDeviceSharding(device))
    params = init(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    return params


def tree_bytes(jax, tree) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(tree))


def requests(cfg, plan: Plan, seed: int, copies: int = 1):
    import numpy as np
    from repro.serving import ServeRequest
    rng = np.random.RandomState(seed)
    lens = list(plan.prompt_lens) * copies
    prompts = [rng.randint(0, cfg.vocab, size=n).tolist() for n in lens]
    return [ServeRequest(prompt=p, max_new_tokens=plan.new_tokens)
            for p in prompts]


# -- phase 1: kernels vs oracles --------------------------------------------
def check_kernels(jax, cfg, params, plan: Plan, seed: int, device) -> None:
    """Chunked prefill + DECODE_STEPS decode steps through the paged
    kernels and through the jnp oracles; every compared logit row must
    agree within LOGIT_TOL of the oracle's largest |logit|."""
    import numpy as np
    from repro.models import build_model
    block = 16
    rows = len(plan.check_lens)
    lens = np.asarray(plan.check_lens, np.int32)
    n_chunks = -(-int(lens.max()) // plan.chunk)
    mb = plan.max_len // block
    tbl = (1 + np.arange(rows * mb, dtype=np.int32)).reshape(rows, mb)
    rng = np.random.RandomState(seed + 1)
    prompt = rng.randint(0, cfg.vocab, size=(rows, n_chunks * plan.chunk)
                         ).astype(np.int32)
    put = lambda x: jax.device_put(x, device)     # noqa: E731

    def run(use_pallas: bool, fed: Optional[List[np.ndarray]]):
        model = build_model(cfg, use_pallas=use_pallas)
        with jax.default_device(device):
            cache = put(model.init_cache(rows, plan.max_len, vector_pos=True,
                                         kv_layout="paged",
                                         n_blocks=rows * mb + 1,
                                         block_size=block))
        chunk_fn = jax.jit(
            lambda p, c, t, base, last, rem, tb: model.prefill_chunk(
                p, c, t, base, last_pos=last, block_tbl=tb, lens=rem),
            donate_argnums=(1,))
        decode_fn = jax.jit(model.decode_step, donate_argnums=(1,))
        out, tokens, final = [], [], [None] * rows
        for i in range(n_chunks):
            base = i * plan.chunk
            rem = np.clip(lens - base, 0, plan.chunk).astype(np.int32)
            last = np.clip(lens - 1 - base, 0, plan.chunk - 1
                           ).astype(np.int32)
            logits, cache = chunk_fn(
                params, cache, put(prompt[:, base:base + plan.chunk]),
                put(np.int32(base)), put(last), put(rem), put(tbl))
            logits = np.asarray(logits, np.float32)
            # rows with no token in this chunk compute don't-care logits
            out.append(logits[rem > 0])
            for r in np.nonzero((rem > 0) & (lens - base <= plan.chunk))[0]:
                final[r] = logits[r]          # the row's last prompt token
        cache["pos"] = put(lens)
        cache["block_tbl"] = put(tbl)
        tok = np.argmax(np.stack(final)[:, :cfg.vocab], axis=-1
                        ).astype(np.int32)
        for step in range(DECODE_STEPS):
            feed = tok if fed is None else fed[step]
            tokens.append(feed)
            logits, cache = decode_fn(params, cache, put(feed[:, None]))
            row = np.asarray(logits, np.float32)[:, 0]
            out.append(row)
            tok = np.argmax(row[:, :cfg.vocab], axis=-1).astype(np.int32)
        del cache
        return out, tokens

    t0 = time.perf_counter()
    got, fed = run(True, None)
    ref, _ = run(False, fed)
    worst, agree, total = 0.0, 0, 0
    for g, r in zip(got, ref):
        err = float(np.max(np.abs(g - r)) / max(np.max(np.abs(r)), 1e-30))
        worst = max(worst, err)
        agree += int(np.sum(np.argmax(g[:, :cfg.vocab], -1)
                            == np.argmax(r[:, :cfg.vocab], -1)))
        total += g.shape[0]
    log(f"kernel vs oracle: prompts {list(plan.check_lens)} in "
        f"{plan.chunk}-token chunks + {DECODE_STEPS} decode steps; "
        f"max |dlogit| / max |logit| = {worst:.3e} (tol {LOGIT_TOL:.0e}); "
        f"argmax agree {agree}/{total}; smoke time "
        f"{time.perf_counter() - t0:.1f}s")
    if not np.isfinite(worst) or worst > LOGIT_TOL:
        raise SmokeFailure(f"kernel path logits off the oracle by {worst}")


# -- phase 2: serve on one chip ---------------------------------------------
def engine_kernels(jax, eng, plan: Plan) -> Dict[str, bool]:
    """Whether the engine's decode and chunk dispatches compile to a
    Pallas kernel, lowered with the shapes the serve phase used."""
    import numpy as np
    g = eng._group
    mb = eng.bm.table.shape[1]
    tok = np.zeros((eng.max_batch, 1), np.int32)
    live = np.ones((eng.max_batch,), bool)
    decode = eng._decode.lower(eng.params, eng.cache, eng._put(tok),
                               eng._put(live)).compile()
    z = np.zeros((g,), np.int32)
    chunk = eng._chunk_paged.lower(
        eng.params, eng.cache, eng._put(np.zeros((g, plan.chunk), np.int32)),
        eng._put(np.int32(0)), eng._put(z), eng._put(z),
        eng._put(np.zeros((g, mb), np.int32))).compile()
    return {name: "tpu_custom_call" in c.as_text()
            for name, c in (("decode", decode), ("chunk", chunk))}


def serve_one(jax, cfg, params, plan: Plan, seed: int, device,
              check_hlo: bool) -> None:
    from repro.serving import GlobalServer
    srv = GlobalServer(cfg, store=None, max_batch=8, max_len=plan.max_len,
                       use_pallas=True, prefill_chunk=plan.chunk)
    pipe = srv.add_pipeline(params, ["chip0"], device=device)
    reqs = requests(cfg, plan, seed)
    for r in reqs:
        srv.submit(r)
    t0 = time.perf_counter()
    srv.run_until_drained(max_rounds=10_000)
    wall = time.perf_counter() - t0
    st = pipe.engine.stats
    done = [r for r in reqs if r.done and len(r.generated) == plan.new_tokens]
    toks = sum(len(r.generated) for r in reqs)
    log(f"served {len(done)}/{len(reqs)} requests, {toks} tokens "
        f"(prompts {min(plan.prompt_lens)}-{max(plan.prompt_lens)} tokens, "
        f"{sum(plan.prompt_lens)} prompt tokens); prefill batches "
        f"{st.prefill_batches}, chunk dispatches {st.prefill_chunks}, "
        f"decode steps {st.decode_steps}; smoke time {wall:.1f}s "
        f"(compiles included)")
    if len(done) != len(reqs):
        raise SmokeFailure("not every request finished")
    if st.prefill_chunks == 0:
        raise SmokeFailure("no request took the chunked-prefill path")
    if check_hlo:
        has = engine_kernels(jax, pipe.engine, plan)
        log(f"Pallas kernel in compiled dispatch: {has}")
        if not all(has.values()):
            raise SmokeFailure(f"a dispatch lacks its kernel: {has}")
    else:
        log("Pallas kernel check skipped: kernels run in interpret mode")


# -- --chips 4: one replica per chip behind the router ----------------------
def serve_replicas(jax, cfg, plan: Plan, seed: int, devices) -> None:
    from repro.models import build_model
    from repro.serving import GlobalServer, TensorStore
    model = build_model(cfg)
    params = [make_params(jax, model, seed, d) for d in devices]
    log(f"params on {len(devices)} devices: "
        f"{[tree_bytes(jax, p) for p in params]} bytes")

    def run(interrupt: bool):
        srv = GlobalServer(cfg, store=TensorStore(), max_batch=8,
                           max_len=plan.max_len, use_pallas=True,
                           prefill_chunk=plan.chunk, use_kv_migration=True)
        for i, d in enumerate(devices):
            srv.add_pipeline(params[i], [f"chip{i}"], device=d)
        for p, d in zip(srv.pipelines, devices):
            held = {dev for part in (p.engine.params, p.engine.cache)
                    for x in jax.tree.leaves(part) for dev in x.devices()}
            if held != {d}:
                raise SmokeFailure(f"pipeline {p.pid} holds arrays on {held}, "
                                   f"expected only {d}")
        reqs = requests(cfg, plan, seed, copies=len(devices) // 2)
        for r in reqs:
            srv.submit(r)
        t0 = time.perf_counter()
        rounds = 0
        while srv.pending() and rounds < 10_000:
            if interrupt and rounds == plan.interrupt_round:
                srv.interrupt_instance("chip1")
            srv.step()
            srv.tick()
            rounds += 1
        wall = time.perf_counter() - t0
        done = sum(r.done and len(r.generated) == plan.new_tokens
                   for r in reqs)
        kinds = [e[1] for e in srv.events]
        log(f"{'reclaim' if interrupt else 'no reclaim'}: served {done}/"
            f"{len(reqs)} requests over {len(devices)} replicas in {rounds} "
            f"rounds; migrated {sum(r.migrations > 0 for r in reqs)}, "
            f"kv_publish {kinds.count('kv_publish')}, kv_attach "
            f"{kinds.count('kv_attach')}; smoke time {wall:.1f}s")
        if done != len(reqs):
            raise SmokeFailure("not every request finished")
        outs = [list(r.generated) for r in reqs]
        del srv
        gc.collect()
        return outs

    base = run(False)
    moved = run(True)
    same = sum(a == b for a, b in zip(base, moved))
    log(f"greedy outputs equal to the run without the reclaim: "
        f"{same}/{len(base)}")
    if same != len(base):
        raise SmokeFailure("outputs after the reclaim differ")


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the one-replica-per-chip phase")
    ap.add_argument("--rehearse", action="store_true",
                    help="reduced config on any backend (kernels in "
                         "interpret mode on the CPU)")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(HERE, "src"))
    try:
        import jax
        from repro.configs import get_config
        from repro.launch.compile_cache import enable_compile_cache
        from repro.models import build_model
    except ImportError as e:
        print(f"[chip_smoke] cannot import the serving stack: {e}",
              file=sys.stderr)
        return 2
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    log(f"device: {device}")
    if dev.platform != "tpu" and not args.rehearse:
        print("[chip_smoke] no TPU found (use --rehearse off the chip)",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"[chip_smoke] --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    if not args.rehearse:
        log(f"compile cache: {enable_compile_cache()}")
    plan = REHEARSAL if args.rehearse else FULL
    cfg = get_config(ARCH)
    cfg = cfg.reduced() if plan.reduced else cfg
    log(f"config: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} head_dim={cfg.hd} "
        f"dtype={cfg.dtype} vocab={cfg.vocab}")
    compiles = CompileCounter(jax.monitoring)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            serve_replicas(jax, cfg, plan, args.seed, devices[:4])
        else:
            params = make_params(jax, build_model(cfg), args.seed, dev)
            log(f"param bytes: {tree_bytes(jax, params)}")
            check_kernels(jax, cfg, params, plan, args.seed, dev)
            gc.collect()
            serve_one(jax, cfg, params, plan, args.seed, dev,
                      check_hlo=dev.platform == "tpu")
    except SmokeFailure as e:
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        compiles.close()
    log(compiles.line())
    for d in devices[:args.chips]:
        stats = d.memory_stats() or {}
        log(f"{d}: peak_bytes_in_use={stats.get('peak_bytes_in_use', 'not reported')} "
            f"bytes_limit={stats.get('bytes_limit', 'not reported')}")
    log(f"total smoke time {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
