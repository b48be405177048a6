"""Generator ``mix``: reads a mix's parameters from
``chipbench/traffic/<mix>.json`` and returns the requests of one run.

Every ``--seed`` offers the same work: the request sizes, the arrival
times, which system prompt a request opens with and the sizes of those
prompts are drawn from ``POOL_SEED``, so a cell's schedule is fixed. The
run's seed draws every token id (the benchmark draws the weights from it
too), which changes what is computed but not how much. (A seed that
permuted the sizes over the arrivals changed how much work fell inside
the window: output tokens/s spread 5-6% across seeds against 0.1-0.7%
between two runs of one seed, internlm2-chat on one TPU v5e.)

Parameters (all but ``input``/``output`` optional):

- ``arrivals.kind``: ``open`` (the arrival rate comes from the cell) or
  ``backlog`` (``per_slot`` requests per engine slot, all due as the
  pre-roll starts, so the window opens on a full server);
- ``arrivals.segment_s``, ``ar``, ``burstiness``: the rate is modulated by
  a lognormal AR(1) process on segments of ``segment_s`` seconds, Poisson
  within a segment, normalised to mean 1 over the measured window (the
  process of ``cluster/workload.azure_conversation_like``, with the
  segment length as a parameter);
- ``input`` / ``output``: lognormal lengths with the given ``mean`` and
  ``sigma``, clipped to ``[min, max]``;
- ``shared_prefix``: a ``share`` of prompts opens with one of
  ``n_prefixes`` system prompts of ``prefix_min``-``prefix_max`` tokens,
  picked Zipf(``zipf_a``), followed by a ``suffix`` of lognormal length.
"""

from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

from chipbench.traffic import Req

POOL_SEED = 1_000_003


def lognormal(rng, spec: Dict, n: int) -> np.ndarray:
    sigma = float(spec["sigma"])
    mu = math.log(float(spec["mean"])) - sigma ** 2 / 2.0
    x = rng.lognormal(mu, sigma, n)
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def open_arrivals(rng, rate: float, t0: float, t1: float,
                  spec: Dict) -> np.ndarray:
    """Arrival times in [t0, t1) (t0 <= 0 < t1: the window is [0, t1))."""
    seg = float(spec.get("segment_s", 2.0))
    ar = float(spec.get("ar", 0.8))
    burst = float(spec.get("burstiness", 0.6))
    edges = np.arange(math.floor(t0 / seg) * seg, t1 + seg, seg)
    n_seg = len(edges) - 1
    log_mod = np.zeros(n_seg)
    for i in range(1, n_seg):
        log_mod[i] = ar * log_mod[i - 1] + rng.normal(0.0, burst * 0.5)
    mod = np.exp(log_mod)
    in_window = (edges[:-1] >= 0.0) & (edges[:-1] < t1)
    mod = mod / np.mean(mod[in_window])
    times = []
    for i in range(n_seg):
        lo, hi = max(edges[i], t0), min(edges[i + 1], t1)
        if hi <= lo:
            continue
        n = rng.poisson(rate * mod[i] * (hi - lo))
        times.append(np.sort(rng.uniform(lo, hi, n)))
    return np.concatenate(times) if times else np.zeros(0)


def scaled(mix: Dict, factor: float) -> Dict:
    """The mix with every length scaled by ``factor`` (CPU rehearsals at
    a reduced geometry only). Prompts keep the mix's own shortest length,
    so the rehearsal, like the chip, sends none shorter than a KV block
    (a shorter one can open like an indexed prompt by chance)."""
    def lengths(spec: Dict, floor: int = 2) -> Dict:
        out = dict(spec)
        for k in ("mean", "min", "max"):
            out[k] = max(floor, int(spec[k] * factor))
        return out
    out = dict(mix, input=lengths(mix["input"], mix["input"]["min"]),
               output=lengths(mix["output"]))
    if mix.get("shared_prefix"):
        sp = dict(mix["shared_prefix"], suffix=lengths(
            mix["shared_prefix"]["suffix"]))
        for k in ("prefix_min", "prefix_max"):
            sp[k] = max(2, int(sp[k] * factor))
        out["shared_prefix"] = sp
    return out


def generate(mix: Dict, *, seed: int, rate: float, t0: float, t1: float,
             slots: int, vocab: int) -> List[Req]:
    """The run's requests, sorted by arrival. ``t0 <= 0`` starts the
    pre-roll, ``t1`` closes the window; ``slots`` is the engine slots of
    every replica together (backlog sizing)."""
    pool = np.random.default_rng(POOL_SEED)
    arr = mix["arrivals"]
    if arr["kind"] == "open":
        times = open_arrivals(pool, rate, t0, t1, arr)
    elif arr["kind"] == "backlog":
        times = np.full(int(arr["per_slot"]) * slots, float(t0))
    else:
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    n = len(times)
    n_in = lognormal(pool, mix["input"], n)
    n_out = lognormal(pool, mix["output"], n)
    pid = np.full(n, -1)
    sp = mix.get("shared_prefix")
    plens = np.zeros(0, np.int64)
    if sp:
        plens = pool.integers(sp["prefix_min"], sp["prefix_max"] + 1,
                              int(sp["n_prefixes"]))
        ranks = np.arange(1, int(sp["n_prefixes"]) + 1, dtype=float)
        pz = ranks ** -float(sp["zipf_a"])
        use = pool.random(n) < float(sp["share"])
        picks = pool.choice(len(plens), size=n, p=pz / pz.sum())
        pid = np.where(use, picks, -1)
        n_in = np.where(use, plens[np.maximum(picks, 0)]
                        + lognormal(pool, sp["suffix"], n), n_in)
    run = np.random.default_rng(seed)
    prefixes = [run.integers(1, vocab, int(m)).astype(np.int32)
                for m in plens]
    out = []
    for i in range(n):
        p = int(pid[i])
        head = prefixes[p] if p >= 0 else np.zeros(0, np.int32)
        tail = run.integers(1, vocab, int(n_in[i]) - len(head)
                            ).astype(np.int32)
        out.append(Req(float(times[i]), np.concatenate([head, tail]),
                        int(n_out[i]), p, len(head)))
    return out
