"""The system under test, driven from the client's side: one
``GlobalServer`` with one pipeline per chip, warmed up on the cell's own
shapes, then fed by an open loop on the host clock.

Each loop iteration submits every request whose scheduled arrival has
passed, then calls ``GlobalServer.step`` once. Every token a step
returned is stamped on the host clock when the step returns (the engine
has already synced the sampled tokens to the host). Latencies run from
the scheduled arrival. Around each step the driver also records what the
step computed, for the per-layer readers: the context of every decode
row and the prompt pieces ``(first position, tokens)`` it prefilled.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from chipbench.traffic import Req


@dataclasses.dataclass(eq=False)
class Tracked:
    req: Req
    sreq: object = None                 # the ServeRequest once submitted
    submit_s: Optional[float] = None
    left_queue_step: Optional[int] = None
    left_queue_s: Optional[float] = None
    shared: int = 0                     # prompt tokens served from cache
    stamps: List[float] = dataclasses.field(default_factory=list)
    # tokens it had when its replica was reclaimed under it (0: not live
    # there, so no KV of its own to migrate)
    tokens_at_reclaim: int = 0


@dataclasses.dataclass
class StepRec:
    t0: float
    t1: float
    traced: bool
    decode_ctx: List[int] = dataclasses.field(default_factory=list)
    pieces: List[Tuple[int, int]] = dataclasses.field(default_factory=list)
    first_tokens: int = 0
    blocks_used: int = 0
    blocks_total: int = 0


def geometry(config: Dict, rehearse: bool) -> Dict:
    g = dict(config["engine"])
    if rehearse:
        g.update(config.get("rehearsal", {}))
    return g


def build_server(cfg, geo: Dict, params: List, devices, cell: Dict):
    from repro.serving import GlobalServer
    store = None
    if cell.get("store"):
        from repro.serving import TensorStore
        store = TensorStore()
    srv = GlobalServer(
        cfg, store=store, max_batch=geo["max_batch"], max_len=geo["max_len"],
        use_pallas=geo["use_pallas"], prefill_chunk=geo["prefill_chunk"],
        engine_kw={"n_blocks": geo["n_blocks"],
                   "block_size": geo["block_size"],
                   "prefill_group": geo["prefill_group"]},
        use_prefix_share=geo["prefix_share"],
        use_kv_migration=bool(cell.get("kv_migration")),
        dispatch=geo.get("dispatch", "uniform"))
    for i, d in enumerate(devices):
        srv.add_pipeline(params[i], [f"chip{i}"], device=d)
    return srv


# -- warm-up -------------------------------------------------------------------
def warmup_requests(geo: Dict, vocab: int, shared_prefix: bool,
                    rng: np.random.Generator) -> List[List]:
    """Batches of (prompt, max_new) that reach every program shape the
    cell's traffic can: each prefill bucket up to the chunk, chunk groups
    of 1..prefill_group members finishing together, and with shared
    prefixes each suffix bucket (copy-on-write included)."""
    chunk, group = geo["prefill_chunk"], geo["prefill_group"]
    draw = lambda n: rng.integers(1, vocab, n).tolist()     # noqa: E731
    buckets, b = [], 16
    while b < chunk:
        buckets.append(b)
        b *= 2
    buckets.append(chunk)
    batches = [[(draw(b), 2) for b in buckets]]
    for k in range(1, group + 1):
        n = chunk + 1 + k                 # two chunks, all finish together
        batches.append([(draw(n), 2) for _ in range(k)])
    if shared_prefix:
        base = draw(2 * chunk + 5)        # block-unaligned: COW on a hit
        batches.append([(base, 2)])
        batches.append([(base + draw(max(1, 3 * b // 4)), 2)
                        for b in buckets])
    return batches


def migration_block_counts(reqs: List[Req], before_s: float, block: int,
                           max_len: int) -> List[int]:
    """Every block count a reclaim at ``before_s`` can export: a request
    that arrived before it and is live, fully prefilled, after its k-th
    token (k from 1 to max_new - 1) holds prompt + k - 1 tokens."""
    out = set()
    for r in reqs:
        if r.arrival_s >= before_s:
            continue
        lo, hi = len(r.prompt), min(len(r.prompt) + r.max_new - 2,
                                    max_len - 1)
        if hi >= lo:
            out.update(range(-(-lo // block), -(-hi // block) + 1))
    return sorted(out)


def warm_migration(srv, reclaimed: str, block_counts: List[int],
                   vocab: int, rng: np.random.Generator) -> int:
    """For each block count, a reclaim's steps through the server's own
    path: a request served on the reclaimed replica until it is live with
    that many blocks, its KV exported there as ``interrupt_instance``
    exports it (``Engine.export_live_kv``) and published once for every
    other replica (``GlobalServer._publish_kv``); each of those queues a
    copy of the request, attaches its KV when it steps
    (``GlobalServer._admit_kv_attached``) and decodes its last token.
    Nothing is interrupted: the replicas and their instance ids stay as
    built, and the pools and the store end empty of these requests.
    Returns the attaches made."""
    from repro.serving import ServeRequest
    donor = next(p for p in srv.pipelines if reclaimed in p.instance_ids)
    others = [p for p in srv.pipelines if p is not donor]
    block = donor.engine.bm.block_size
    n = 0
    for nb in block_counts:
        # the fewest prompt tokens that fill nb blocks: the step that
        # prefills its last chunk may decode once more, which stays in them
        prompt = rng.integers(1, vocab, (nb - 1) * block + 1).tolist()
        req = ServeRequest(prompt=prompt, max_new_tokens=3)
        donor.queue.append(req)
        while not req.generated:
            srv.step()
        payload = donor.engine.export_live_kv()[req.rid]
        donor.engine.evict_all()
        if payload["k"].shape[1] != nb:
            raise RuntimeError(f"the warm-up exported "
                               f"{payload['k'].shape[1]} blocks for {nb}")
        before = sum(p.engine.stats.kv_imports for p in others)
        for p in others:
            moved = ServeRequest(prompt=prompt,
                                 max_new_tokens=len(req.generated) + 1,
                                 generated=list(req.generated))
            srv._publish_kv(srv._kv_key(moved), payload)
            p.queue.append(moved)
        while srv.pending():
            srv.step()
        got = sum(p.engine.stats.kv_imports for p in others) - before
        if got != len(others):
            raise RuntimeError(f"{got} of {len(others)} replicas attached "
                               f"{nb} blocks in the warm-up")
        n += got
    return n


def warm_up(srv, batches: List[List]) -> int:
    """Serve each batch to completion on every pipeline (queued on each
    directly, past the router); returns the steps it took."""
    from repro.serving import ServeRequest
    steps = 0
    for batch in batches:
        for p in srv.pipelines:
            p.queue.extend(ServeRequest(prompt=list(prompt), max_new_tokens=n)
                           for prompt, n in batch)
        while srv.pending():
            srv.step()
            steps += 1
    return steps


# -- the open loop -------------------------------------------------------------
class Driver:
    """Open-loop client of one ``GlobalServer``; times are seconds on the
    host clock from window open (``t_open``)."""

    def __init__(self, srv, reqs: List[Req], t_open: float, chunk: int,
                 trace_spans: bool):
        self.srv = srv
        self.chunk = chunk
        self.tracked = [Tracked(r) for r in reqs]
        self.t_open = t_open
        self.next = 0                     # next request to submit
        self.inflight: List[Tracked] = []
        self.steps: List[StepRec] = []
        self.late: List[float] = []       # how late the generator ran
        self.spans = trace_spans
        self.tracing = False
        self.reclaimed: List[Tuple[float, str]] = []
        self.retired: List = []           # EngineStats of replaced engines
        self.moved: List[Tracked] = []    # requests a reclaim moved
        if trace_spans:
            self._wrap_engines()

    def now(self) -> float:
        return time.perf_counter() - self.t_open

    def _span(self, name: str):
        if not self.spans:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def _wrap_engines(self) -> None:
        """Host spans around ``Engine.admit_many`` and ``Engine.step``,
        wrapped on the instance (a rebuilt engine is wrapped again)."""
        for p in self.srv.pipelines:
            eng = p.engine
            if getattr(eng, "_cb_wrapped", False):
                continue
            for attr, span in (("admit_many", "cb.admit"),
                               ("step", "cb.engine_step")):
                fn = getattr(eng, attr)

                def wrapped(*a, _fn=fn, _span=span, **kw):
                    with self._span(_span):
                        return _fn(*a, **kw)
                setattr(eng, attr, wrapped)
            eng._cb_wrapped = True

    def submit_due(self, before: float = float("inf")) -> None:
        """Submit every request due by now that arrives before
        ``before``."""
        from repro.serving import ServeRequest
        now = self.now()
        with self._span("cb.submit"):
            while (self.next < len(self.tracked)
                   and self.tracked[self.next].req.arrival_s <= now
                   and self.tracked[self.next].req.arrival_s < before):
                tr = self.tracked[self.next]
                tr.sreq = ServeRequest(prompt=tr.req.prompt.tolist(),
                                       max_new_tokens=tr.req.max_new)
                self.srv.submit(tr.sreq)
                tr.submit_s = self.now()
                self.late.append(tr.submit_s - tr.req.arrival_s)
                self.inflight.append(tr)
                self.next += 1

    def idle_until_due(self, limit: float) -> None:
        """Nothing to serve: sleep until the next arrival (or ``limit``)."""
        due = (self.tracked[self.next].req.arrival_s
               if self.next < len(self.tracked) else limit)
        wait = min(due, limit) - self.now()
        if wait > 0:
            with self._span("cb.idle"):
                time.sleep(wait)

    def _queued(self) -> set:
        return {id(r) for p in self.srv.pipelines for r in p.queue}

    def _shared_tokens(self) -> int:
        return sum(p.engine.stats.prefix_shared_tokens
                   for p in self.srv.pipelines)

    def step(self) -> StepRec:
        if self.spans:
            self._wrap_engines()
        queued = self._queued()
        before = [len(t.sreq.generated) for t in self.inflight]
        shared0 = self._shared_tokens()
        t0 = self.now()
        with self._span("cb.step"):
            self.srv.step()
        t1 = self.now()
        with self._span("cb.record"):
            return self._record(t0, t1, queued, before, shared0)

    def _record(self, t0, t1, queued, before, shared0) -> StepRec:
        rec = StepRec(t0, t1, self.tracing)
        idx = len(self.steps)
        now_queued = self._queued()
        admitted = [t for t in self.inflight
                    if id(t.sreq) in queued and id(t.sreq) not in now_queued]
        for t in admitted:
            t.left_queue_step, t.left_queue_s = idx, t0
        self._split_shared(admitted, self._shared_tokens() - shared0)
        still = []
        for t, n0 in zip(self.inflight, before):
            n1 = len(t.sreq.generated)
            for k in range(n0, n1):
                t.stamps.append(t1)
                if k == 0:
                    rec.first_tokens += 1
                    self._attribute_prefill(t, idx, rec)
                else:
                    rec.decode_ctx.append(len(t.req.prompt) + k)
            if not t.sreq.done:
                still.append(t)
        self.inflight = still
        used = total = 0
        for p in self.srv.pipelines:
            bm = p.engine.bm
            if bm is not None:
                used += bm.blocks_in_use()
                total += bm.n_blocks - 1      # block 0 is the trash block
        rec.blocks_used, rec.blocks_total = used, total
        self.steps.append(rec)
        return rec

    @staticmethod
    def _split_shared(admitted: List[Tracked], shared: int) -> None:
        """Spread the step's prefix-shared tokens over the requests it
        admitted that open with a system prompt (exact for one)."""
        cand = [t for t in admitted if t.req.prefix_len > 0]
        if shared <= 0 or not cand:
            return
        tot = sum(t.req.prefix_len for t in cand)
        left = shared
        for t in cand[:-1]:
            t.shared = min(len(t.req.prompt) - 1,
                           shared * t.req.prefix_len // tot)
            left -= t.shared
        cand[-1].shared = max(0, min(len(cand[-1].req.prompt) - 1, left))

    def _attribute_prefill(self, t: Tracked, idx: int, rec: StepRec) -> None:
        """The prompt work of a request whose first token came in step
        ``idx``: all in its admission step, or one chunk per step from
        admission on (chunked prefill)."""
        n = len(t.req.prompt)
        s = t.left_queue_step if t.left_queue_step is not None else idx
        if s == idx:
            rec.pieces.append((t.shared, n - t.shared))
            return
        span = idx - s + 1
        chunk = self.chunk if span == -(-n // self.chunk) else -(-n // span)
        for i in range(span):
            lo, hi = i * chunk, min(n, (i + 1) * chunk)
            target = rec if s + i == idx else self.steps[s + i]
            if hi > lo:
                target.pieces.append((lo, hi - lo))

    def reclaim(self, instance: str) -> None:
        """Reclaim the replica ``instance`` through the server's own path;
        the statistics of the engine it replaces are kept."""
        self.retired.extend(p.engine.stats for p in self.srv.pipelines
                            if p.alive and instance in p.instance_ids)
        live = {id(t.sreq): t for t in self.inflight}
        for r in self.srv.interrupt_instance(instance):
            t = live.get(id(r))
            if t is not None:
                t.tokens_at_reclaim = len(r.generated)
                self.moved.append(t)
        self.reclaimed.append((self.now(), instance))

    def stranded(self) -> List[Tracked]:
        """The requests a reclaim moved that have had no token since."""
        return [t for t in self.moved
                if len(t.stamps) <= t.tokens_at_reclaim]

    def engine_stats(self) -> List:
        """``EngineStats`` of every engine that served: the pipelines'
        engines now, and those a reclaim replaced."""
        return [p.engine.stats for p in self.srv.pipelines] + self.retired

    def run_until(self, t_end: float, reclaim: Optional[Tuple[float, str]],
                  trace_hook=None) -> None:
        """Submit and step until the host clock passes ``t_end``."""
        while self.now() < t_end:
            if trace_hook is not None:
                trace_hook(self)
            if reclaim is not None and not self.reclaimed \
                    and self.now() >= reclaim[0]:
                self.reclaim(reclaim[1])
            self.submit_due()
            if not self.srv.pending():
                self.idle_until_due(t_end)
                continue
            self.step()

    def drain_first_tokens(self, t1: float, limit_s: float) -> None:
        """No new arrivals: submit what arrived in the window ``[0, t1)``
        and is not yet submitted, then step until every request that
        arrived in it has its first token, and every request a reclaim
        moved a token since, or ``limit_s`` passes."""
        stop = self.now() + limit_s
        while self.now() < stop:
            self.submit_due(before=t1)
            waiting = [t for t in self.tracked[:self.next]
                       if 0.0 <= t.req.arrival_s < t1 and not t.stamps]
            if not (waiting or self.stranded()) or not self.srv.pending():
                return
            self.step()
