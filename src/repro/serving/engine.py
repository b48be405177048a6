"""Shape-stable batched continuous-batching engine (execution plane v2).

Slot-based continuous batching over a fixed KV/state cache, rebuilt for
admission throughput and trace stability:

* **Paged block-KV cache** (``kv_layout="paged"``, the default for
  attention families) — the KV lives in a pool of fixed-size token blocks
  shared by every slot, with a per-slot block table mapping virtual
  positions to pool blocks (``serving/kv_blocks.py``), so memory scales
  with *actual* context lengths instead of ``max_batch * max_len`` — the
  lever that lets mixed-length workloads run the large batches the
  roofline estimator assumes. When the pool can't cover a request the
  engine refuses admission (``EngineStats.alloc_failures`` — backpressure,
  not OOM), skipping ahead a bounded window so one oversized request
  can't starve fit-able smaller ones behind it. ``kv_layout="contig"``
  keeps the dense slot-row layout (required for SSM/MoE/enc-dec, and the
  A/B baseline for benchmarks/bench_kv_paging.py).
* **Demand-paged block allocation** (``kv_alloc="lazy"``, the default) —
  admission books a request's worst-case ``ceil(total_ctx / block_size)``
  blocks as a *reservation* in the block manager's ledger (admission
  control stays sound) but allocates only the blocks covering the prefill
  context; ``step()`` grows a slot by one block when decode crosses a
  block boundary (``EngineStats.block_grows``). With ``kv_overcommit > 1``
  the ledger books more reserved blocks than physically exist, betting
  that EOS-early requests free capacity before everyone reaches worst
  case; when a grow then finds the free list dry, the engine PREEMPTS a
  victim slot (fewest generated tokens): its live KV blocks are exported
  (position-exact, the §5.1 invariant), its blocks freed, and the request
  parked on ``take_preempted()`` for KV-attach re-admission — the global
  server publishes the payload to the shared tensor store and requeues;
  a standalone engine re-attaches it itself once capacity frees. Greedy
  outputs stay byte-identical across grow and preempt/re-admit paths.
  ``kv_alloc="upfront"`` keeps whole-request allocation at admission (a
  lazily-admitted pool can never preempt under ``kv_overcommit=1.0``
  either: reservations never exceed physical blocks, so every grow is
  covered).
* **Prefix-sharing KV cache** (``prefix_share=True``, paged layout) — a
  block-aligned prefix index (``serving/prefix_index.py``) is consulted at
  admission: a request extending a cached prefix maps the shared blocks
  into its slot table (refcounted, read-only), COPY-ON-WRITES the first
  partially-shared boundary block, and prefills ONLY the divergent suffix
  (``EngineStats.prefix_hits`` / ``prefix_shared_tokens`` /
  ``cow_copies``). Freed blocks keep content until reallocated, so a hot
  prefix survives its requests; ``hot_prefixes``/``warm_prefix`` round
  shared-prefix payloads through the tensor store so re-placed pipelines
  warm up instead of recomputing (``prefix_warmups``). Greedy outputs stay
  byte-identical to the no-sharing engine (prefix activations are causally
  independent of the suffix).
* **Block-granular KV migration** — ``export_kv``/``import_kv`` round-trip
  a live request's blocks through the shared tensor store, so a migrated
  request re-attaches its KV instead of recomputing it (§5.1 upgraded via
  §5.2's store; see serving/server.py).
* **Batched, bucketed prefill** — waiting requests are admitted in groups
  of ``prefill_group``, right-padded to a power-of-2 length bucket, so the
  jit'd prefill traces O(log max_len) shapes instead of one per prompt
  length (``EngineStats.prefill_retraces`` proves the bound). Causal
  masking makes right-padding exact for dense-attention families;
  SSM/hybrid trunks carry recurrent state through pad tokens and MoE
  expert capacity is shared across the flattened token stream, so those
  admit at exact length (and MoE at batch 1) to stay output-exact.
* **Batched chunked prefill** — contexts longer than ``prefill_chunk``
  (the migration-recompute case) prefill chunk-by-chunk between decode
  steps, bounding head-of-line blocking for live slots during interruption
  storms. Pendings admitted together advance as ONE dispatch per scheduling
  step (a ``_PendingGroup``), not a batch-1 loop per request. Under the
  paged layout each chunk's K/V is written STRAIGHT into the owning slots'
  pool blocks through a snapshot of their block tables — no transient
  group cache, no terminal scatter dispatch (``EngineStats.chunk_direct``
  vs ``chunk_scatters``); contig keeps the transient path as the A/B
  baseline. Enc-dec requests chunk too: the cross-attention cache is
  warmed by one encoder pass when the group cache is created.
* **Fused jit'd slot scatter** — one jit'd gather/scatter installs a whole
  prefill group into its slots (through the block tables under the paged
  layout), replacing the per-cache-key Python ``at[].set`` loop.
* **Masked, donated decode** — dead slots are masked (their cache position
  is frozen) instead of decoding token 0 forever; the cache buffer is
  donated across steps.

Migration semantics: re-admission prefills ``prompt + generated[:-1]`` and
lets the first decode step feed ``generated[-1]``, reproducing the
uninterrupted run's cache layout byte-for-byte. With greedy sampling an
interrupted run emits identical tokens to an uninterrupted one whether it
recomputes or KV-attaches (paper §5.1, tested end-to-end in
tests/test_engine_v2.py and tests/test_kv_paging.py).

``admission="legacy"`` keeps the seed's per-request batch-1 eager path
(contiguous layout only) as the baseline for
benchmarks/bench_engine_throughput.py.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import build_model
from repro.serving import obs
from repro.serving.kv_blocks import KV_POISON, BlockManager
from repro.serving.request import ServeRequest

_donation_filter_installed = False


def _silence_cpu_donation_warnings() -> None:
    """CPU has no buffer donation EVER, so the per-compile warning carries
    no signal there — silence it once so driver/example logs stay readable.
    On TPU/GPU the warning stays live: a missed donation is a real
    regression on accelerators."""
    global _donation_filter_installed
    if _donation_filter_installed or jax.default_backend() != "cpu":
        return
    _donation_filter_installed = True
    warnings.filterwarnings(
        "ignore", message="Some donated buffers were not")


@dataclasses.dataclass
class EngineStats:
    prefills: int = 0           # requests prefilled (admissions)
    prefill_batches: int = 0    # batched prefill dispatches
    prefill_chunks: int = 0     # chunked-prefill chunk dispatches
    chunk_direct: int = 0       # paged chunks written in-place (no scatter)
    chunk_scatters: int = 0     # contig finisher scatters (transient path)
    decode_steps: int = 0
    tokens_out: int = 0
    retraces: int = 0           # total jit traces (prefill+decode+scatter)
    prefill_retraces: int = 0   # prefill traces — bounded by bucket count
    alloc_failures: int = 0     # paged admissions refused (backpressure)
    block_grows: int = 0        # blocks allocated on demand mid-decode
    preemptions: int = 0        # slots evicted when a grow found a dry pool
    kv_imports: int = 0         # re-admissions that attached KV (no prefill)
    prefix_hits: int = 0        # admissions that mapped shared-prefix blocks
    prefix_shared_tokens: int = 0   # prefill tokens NOT recomputed
    cow_copies: int = 0         # boundary blocks copied before first write
    prefix_warmups: int = 0     # published prefixes attached from the store
    grow_ahead_skips: int = 0   # boundary crossings served by look-ahead
    admit_deferred: int = 0     # admissions deferred for free-block headroom


@dataclasses.dataclass
class _PendingMember:
    req: ServeRequest
    slot: int
    tokens: np.ndarray
    done: bool = False


@dataclasses.dataclass
class _PendingGroup:
    """Long-context admissions prefilled chunk-by-chunk as ONE batched
    dispatch per scheduling step (members share the chunk boundary)."""
    members: List[_PendingMember]
    base: int = 0
    cache: Any = None


class Engine:
    def __init__(self, cfg: ArchConfig, params: Any, max_batch: int = 8,
                 max_len: int = 256, model_kw: Optional[Dict] = None,
                 np_rng: Optional[np.random.RandomState] = None,
                 use_pallas: bool = False, prefill_group: int = 4,
                 prefill_bucket: int = 16, prefill_chunk: int = 0,
                 admission: str = "bucketed", kv_layout: str = "auto",
                 block_size: int = 16, n_blocks: int = 0,
                 kv_alloc: str = "lazy", kv_overcommit: float = 1.0,
                 admit_window: int = 4, prefix_share: bool = False,
                 grow_ahead: int = 1, admit_headroom: bool = True,
                 kv_sanitize: Optional[bool] = None,
                 victim_policy: str = "cost", placement: Any = None,
                 device: Optional[jax.Device] = None):
        assert admission in ("bucketed", "legacy"), admission
        assert kv_layout in ("auto", "paged", "contig"), kv_layout
        assert kv_alloc in ("lazy", "upfront"), kv_alloc
        assert victim_policy in ("cost", "fewest"), victim_policy
        _silence_cpu_donation_warnings()
        self.cfg = cfg
        # params, KV cache and every host-built dispatch input live on
        # ``device`` (None: the default device), so the jitted dispatches
        # run there — one engine per chip behind the global server
        self.device = device
        self.params = (params if device is None
                       else jax.device_put(params, device))
        self.max_batch = max_batch
        self.max_len = max_len
        self.admission = admission
        self.prefill_chunk = int(prefill_chunk)
        # MoE expert capacity is computed over the flattened (batch, seq)
        # token stream, so pad tokens/rows would compete with real tokens
        # for expert slots and change which tokens get dropped — batched or
        # padded prefill is not output-exact for MoE. Admit batch-1 at
        # exact length until the router masks pads (ROADMAP follow-up).
        self._moe = cfg.n_experts > 0
        self._group = 1 if self._moe else max(1, min(prefill_group,
                                                     max_batch))
        self._min_bucket = max(1, min(prefill_bucket, max_len))
        # paged layout: dense-attention families only (SSM/hybrid carry
        # recurrent state, not KV rows; enc-dec has a second cache; MoE
        # rides the contig path with its batch-1 admission). The legacy
        # baseline predates the block table and stays contiguous.
        paged_ok = not (cfg.is_encdec or cfg.family in ("ssm", "hybrid")
                        or self._moe or admission == "legacy")
        if kv_layout == "auto":
            kv_layout = "paged" if paged_ok else "contig"
        elif kv_layout == "paged" and not paged_ok:
            raise ValueError(
                f"kv_layout='paged' unsupported for {cfg.name} "
                f"(family={cfg.family}, admission={admission})")
        self.kv_layout = kv_layout
        self.kv_alloc = kv_alloc
        self._lazy = kv_alloc == "lazy" and kv_layout == "paged"
        self._admit_window = max(0, int(admit_window))
        self._grow_ahead = max(1, int(grow_ahead))
        self._admit_headroom = bool(admit_headroom)
        # preemption-victim choice: "cost" picks the slot with the lowest
        # estimated re-admission cost (restore vs recompute, priced by
        # cluster/recovery); "fewest" is the legacy fewest-generated rule,
        # which remains the tie-break within a cost bucket. ``placement``
        # (core.estimator.Placement) prices the recompute branch; without
        # it only the restore (store round-trip) branch is priced.
        self._victim_policy = victim_policy
        self._placement = placement
        self._victim_costs: Dict[int, float] = {}
        self._victim_spec = None
        self.bm: Optional[BlockManager] = None
        self._prefix = None
        self._tbl_dirty = False
        self.enc_frames = 8           # stubbed frontend frame count
        if kv_layout == "paged":
            mb = -(-max_len // block_size)
            if n_blocks <= 0:
                n_blocks = max_batch * mb + 1     # capacity-parity + trash
            self.bm = BlockManager(n_blocks, block_size, max_batch, mb,
                                   overcommit=kv_overcommit,
                                   sanitize=kv_sanitize)
        elif prefix_share:
            raise ValueError("prefix_share requires kv_layout='paged'")
        # model AFTER the block manager: sanitize mode arms the device-side
        # poison probe — paged gathers emit a max readable |K|/|V| that is
        # checkify'd against KV_POISON, so a stale block-table read fires
        # at the offending dispatch instead of only via output divergence
        model_kw = dict(model_kw or {})
        model_kw.setdefault("use_pallas", use_pallas)
        self.use_pallas = model_kw["use_pallas"]
        if self.bm is not None:
            model_kw.setdefault("kv_probe", self.bm.sanitize)
        self._kv_probe = bool(model_kw.get("kv_probe", False))
        self.model = build_model(cfg, **model_kw)
        with jax.default_device(device):
            self.cache = self._put(self._init_cache(n_blocks, block_size))
        if kv_layout == "paged":
            if prefix_share:
                if admission == "legacy":
                    raise ValueError(
                        "prefix_share requires the bucketed paged engine")
                from repro.serving.prefix_index import PrefixIndex
                self._prefix = PrefixIndex(block_size, self.bm)
                self.bm.on_reuse = self._prefix.invalidate_block
        self.slots: List[Optional[ServeRequest]] = [None] * max_batch
        self.stats = EngineStats()
        self._pending: List[_PendingGroup] = []
        self._admit_finished: List[ServeRequest] = []
        # requests evicted by a dry-pool grow, with their exported KV
        # payloads; drained by the global server (publish + requeue) or
        # re-attached internally once capacity frees (standalone use)
        self._preempted: List[Tuple[ServeRequest, Dict]] = []
        self._legacy_shapes: set = set()

        def prefill_fn(params, tokens, last_pos):
            self.stats.retraces += 1
            self.stats.prefill_retraces += 1
            if cfg.is_encdec:
                frames = jnp.zeros(
                    (tokens.shape[0], self.enc_frames, cfg.d_model),
                    jnp.float32)
                return self.model.prefill(
                    params, {"embeds": frames, "tokens": tokens},
                    max_len=self.max_len, last_pos=last_pos)
            return self.model.prefill(params, {"tokens": tokens},
                                      max_len=self.max_len, ring=False,
                                      last_pos=last_pos)

        def chunk_fn(params, cache, tokens, base, last_pos):
            self.stats.retraces += 1
            self.stats.prefill_retraces += 1
            return self.model.prefill_chunk(params, cache, tokens, base,
                                            last_pos=last_pos)

        def chunk_paged_fn(params, cache, tokens, base, last_idx, rem,
                           tbls):
            # direct paged chunking: the chunk's K/V land in the owning
            # slots' pool blocks as they are computed, each row routed
            # through a snapshot of its slot's block table — no transient
            # group cache, no terminal scatter. ``rem`` masks columns past
            # a row's remaining tokens (and whole finished rows, rem=0)
            # into the trash block.
            self.stats.retraces += 1
            self.stats.prefill_retraces += 1
            return self.model.prefill_chunk(params, cache, tokens, base,
                                            last_pos=last_idx,
                                            block_tbl=tbls, lens=rem)

        def enc_warm_fn(params, frames):
            # chunked enc-dec prefill: the transient group cache needs the
            # cross-attention K/V resident before the first decoder chunk
            self.stats.retraces += 1
            cache = self.model.init_cache(frames.shape[0], self.max_len,
                                          s_enc=self.enc_frames)
            enc_out = self.model.encode(params, frames)
            cache["ck"], cache["cv"] = self.model.cross_kv(params, enc_out)
            return cache

        def scatter_contig_fn(cache, group, slots, rows, lens):
            # Install ``group`` (batch G, possibly with pad rows remapped to
            # row 0 / slot[0] so duplicate writes agree) into slot rows.
            self.stats.retraces += 1
            out = dict(cache)
            for key, small in group.items():
                if key == "pos":
                    out["pos"] = cache["pos"].at[slots].set(lens)
                elif key == "slot_pos":
                    continue              # engine caches are linear
                else:
                    sel = jnp.take(small, rows, axis=1)
                    out[key] = cache[key].at[:, slots].set(
                        sel.astype(cache[key].dtype))
            return out

        def scatter_paged_fn(cache, group, slots, rows, lens, tbls):
            # Same contract, but K/V route through the destination slots'
            # block tables (``tbls``: (G, max_blocks)). Positions past a
            # row's real length land in the reserved trash block 0.
            self.stats.retraces += 1
            bs = cache["k"].shape[2]
            out = dict(cache)
            for key, small in group.items():
                if key == "pos":
                    out["pos"] = cache["pos"].at[slots].set(lens)
                elif key in ("slot_pos", "block_tbl"):
                    continue
                else:
                    sel = jnp.take(small, rows, axis=1)   # (L,G,S,nkv,d)
                    t = jnp.arange(sel.shape[2])
                    dest = jnp.take(tbls, t // bs, axis=1)       # (G, S)
                    dest = jnp.where(t[None, :] < lens[:, None], dest, 0)
                    out[key] = cache[key].at[:, dest, t % bs].set(
                        sel.astype(cache[key].dtype))
            out["block_tbl"] = cache["block_tbl"].at[slots].set(tbls)
            return out

        def decode_fn(params, cache, tokens, live):
            self.stats.retraces += 1
            pos0 = cache["pos"]
            if "block_tbl" in cache:
                # dead/pending rows must not write their (masked, garbage)
                # token through their tables: mid-chunk pending slots hold
                # LIVE in-place chunk KV now, so route those writes to the
                # trash block instead
                tbl = cache["block_tbl"]
                cache = dict(cache,
                             block_tbl=jnp.where(live[:, None], tbl, 0))
                logits, new_cache = self.model.decode_step(params, cache,
                                                           tokens)
                new_cache["block_tbl"] = tbl
            else:
                logits, new_cache = self.model.decode_step(params, cache,
                                                           tokens)
            # dead slots: freeze the cache position instead of advancing on
            # a dummy token (their rows are fully overwritten on reuse)
            new_cache["pos"] = jnp.where(live, new_cache["pos"], pos0)
            return logits, new_cache

        def suffix_fn(params, cache, tokens, bases, lens, slots, tbls):
            # prefix-sharing admission: prefill only the divergent suffix;
            # the shared prefix is read through the (updated) block tables
            self.stats.retraces += 1
            self.stats.prefill_retraces += 1
            logits, out = self.model.prefill_suffix(params, cache, tokens,
                                                    bases, tbls, lens)
            out["pos"] = out["pos"].at[slots].set(bases + lens)
            out["block_tbl"] = out["block_tbl"].at[slots].set(tbls)
            return logits, out

        def cow_fn(cache, src, dst):
            # copy-on-write a partially-shared boundary block BEFORE any
            # divergent suffix write lands in it
            self.stats.retraces += 1
            out = dict(cache)
            out["k"] = cache["k"].at[:, dst].set(cache["k"][:, src])
            out["v"] = cache["v"].at[:, dst].set(cache["v"][:, src])
            return out

        def warm_fn(cache, k, v, ids):
            # install a published shared-prefix payload into free blocks
            self.stats.retraces += 1
            out = dict(cache)
            out["k"] = cache["k"].at[:, ids].set(k.astype(cache["k"].dtype))
            out["v"] = cache["v"].at[:, ids].set(v.astype(cache["v"].dtype))
            return out

        self._prefill_b = jax.jit(prefill_fn)
        self._chunk = jax.jit(chunk_fn, donate_argnums=(1,))
        self._enc_warm = jax.jit(enc_warm_fn)
        # the group cache is NOT donated: a pending group's cache outlives
        # the scatter of its early finishers
        scatter = (scatter_paged_fn if kv_layout == "paged"
                   else scatter_contig_fn)
        self._scatter = jax.jit(scatter, donate_argnums=(0,))
        # with the poison probe armed these dispatches read through block
        # tables and carry checkify.checks; _run discharges the error
        if self._kv_probe:
            from jax.experimental import checkify

            def probed(f):
                return checkify.checkify(f, errors=checkify.user_checks)
        else:
            def probed(f):
                return f
        self._decode = jax.jit(probed(decode_fn), donate_argnums=(1,))
        self._suffix = jax.jit(probed(suffix_fn), donate_argnums=(1,))
        self._chunk_paged = jax.jit(probed(chunk_paged_fn),
                                    donate_argnums=(1,))
        self._cow = jax.jit(cow_fn, donate_argnums=(0,))
        self._warm = jax.jit(warm_fn, donate_argnums=(0,))

    def _init_cache(self, n_blocks: int, block_size: int) -> Dict:
        if self.kv_layout == "paged":
            return self.model.init_cache(
                self.max_batch, self.max_len, vector_pos=True,
                kv_layout="paged", n_blocks=n_blocks, block_size=block_size)
        if self.cfg.is_encdec:
            return self.model.init_cache(self.max_batch, self.max_len,
                                         s_enc=self.enc_frames,
                                         vector_pos=True)
        return self.model.init_cache(self.max_batch, self.max_len,
                                     ring=False, vector_pos=True)

    def _put(self, x):
        """Host value (or another device's array) -> array on this
        engine's device."""
        return jax.device_put(x, self.device)

    def _run(self, fn, *args):
        """Dispatch a (possibly checkify'd) jit: with the poison probe
        armed the device-side checks are discharged here — sanitize/debug
        mode only, the probe-off hot path pays no extra sync."""
        if not self._kv_probe:
            return fn(*args)
        err, out = fn(*args)
        err.throw()
        return out

    # -- buckets ----------------------------------------------------------------
    def bucket_lens(self) -> List[int]:
        """Prefill length buckets: powers of two up to max_len."""
        out, b = [], self._min_bucket
        while b < self.max_len:
            out.append(b)
            b *= 2
        out.append(self.max_len)
        return out

    def _bucket(self, n: int) -> int:
        if self.cfg.family in ("ssm", "hybrid") or self._moe:
            return n      # recurrent state / expert capacity: no padding
        b = self._min_bucket
        while b < n:
            b *= 2
        return min(b, self.max_len)

    def _use_chunked(self, n: int) -> bool:
        # MoE excluded: per-chunk expert capacity differs from full-prefill
        # capacity, changing token drops (same exactness issue as padding).
        # Enc-dec chunks fine: the cross-attention cache is warmed once at
        # group creation and the decoder chunks like any attention family.
        if (self.prefill_chunk <= 0
                or self.cfg.family in ("ssm", "hybrid") or self._moe):
            return False
        n_chunks = -(-n // self.prefill_chunk)
        return n > self.prefill_chunk and \
            n_chunks * self.prefill_chunk <= self.max_len

    @staticmethod
    def _prefill_tokens(req: ServeRequest) -> List[int]:
        """Context to prefill: the full context *minus* the last generated
        token, which the first decode step feeds — so a recomputed cache is
        laid out identically to an uninterrupted run's."""
        ctx = req.full_context()
        return ctx[:-1] if req.generated else ctx

    @staticmethod
    def _total_tokens(req: ServeRequest) -> int:
        """Token capacity a request needs for its whole lifetime: current
        context plus every token it may still generate."""
        return req.ctx_len + req.max_new_tokens - len(req.generated)

    # -- slot management --------------------------------------------------------
    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    def active(self) -> List[ServeRequest]:
        return [s for s in self.slots if s is not None]

    def _pending_slots(self) -> set:
        return {m.slot for g in self._pending for m in g.members
                if not m.done}

    def _free_blocks(self, slot: int) -> None:
        if self.bm is not None and self.bm.slot_blocks(slot):
            self.bm.free(slot)
            self._poison_released()
            self._tbl_dirty = True

    def _poison_released(self) -> None:
        """Sanitize mode: overwrite the device content of blocks whose
        last mapping just died with the KV_POISON sentinel — a stale
        gather through a dangling table entry then produces unmissable
        garbage instead of silently-plausible old KV. Blocks a prefix
        index still references are exempt (their content is the warm
        prefix feature, kept valid until reallocation)."""
        if self.bm is None or not self.bm.sanitize \
                or not self.bm.last_released:
            return
        ids = self._put(np.asarray(self.bm.last_released, np.int32))
        self.cache["k"] = self.cache["k"].at[:, ids].set(KV_POISON)
        self.cache["v"] = self.cache["v"].at[:, ids].set(KV_POISON)
        self.bm.last_released = []

    def _sync_block_tbl(self) -> None:
        """Push the host-side block table to the device cache when
        allocations changed since the last dispatch."""
        if self.bm is not None and self._tbl_dirty:
            self.cache["block_tbl"] = self._put(self.bm.table)
            self._tbl_dirty = False

    def block_stats(self) -> Dict[str, int]:
        """Paged-pool occupancy/fragmentation counters (empty for contig)."""
        if self.bm is None:
            return {}
        return {"blocks_in_use": self.bm.blocks_in_use(),
                "blocks_free": self.bm.blocks_free(),
                "reserved_blocks": self.bm.reserved_blocks(),
                "outstanding_blocks": self.bm.outstanding_blocks(),
                "frag_tokens": self.bm.frag_tokens(),
                "peak_blocks": self.bm.peak_blocks,
                "block_size": self.bm.block_size,
                "n_blocks": self.bm.n_blocks,
                "block_grows": self.stats.block_grows,
                "preemptions": self.stats.preemptions,
                "alloc_failures": self.stats.alloc_failures,
                "prefix_hits": self.stats.prefix_hits,
                "cow_copies": self.stats.cow_copies}

    # -- admission --------------------------------------------------------------
    def admit(self, req: ServeRequest) -> bool:
        return bool(self.admit_many([req]))

    def admit_many(self, reqs: Sequence[ServeRequest]
                   ) -> List[ServeRequest]:
        """Admit from ``reqs`` in order, bounded by free slots and (paged)
        the block manager's reservation ledger.

        Lazy mode books each request's worst-case blocks in the ledger but
        allocates only the prefill-context blocks (``step()`` grows on
        demand). A request the pool can't cover is SKIPPED rather than
        blocking the whole queue — admission keeps scanning up to
        ``admit_window`` failures so fit-able smaller requests behind an
        oversized one still drain (approximate FIFO). The returned list is
        therefore NOT necessarily a prefix of ``reqs``; callers must
        remove admitted requests from their queues by identity.

        Requests are grouped by length bucket and prefilled in batches of
        ``prefill_group``; long contexts go to the chunked path (grouped
        into one dispatch per step). Finished ones surface via ``step()``."""
        with obs.span("serve.admit") as sp:
            with obs.span("serve.admit.plan"):
                admitted, groups, sgroups, chunked = self._plan_admission(
                    reqs)
            for blen, items in sorted(groups.items()):
                for i in range(0, len(items), self._group):
                    self._admit_group(items[i:i + self._group], blen)
            for blen, items in sorted(sgroups.items()):
                for i in range(0, len(items), self._group):
                    self._admit_group_suffix(items[i:i + self._group], blen)
            # pendings admitted together share a group: one chunk dispatch
            # per step for the whole group instead of a batch-1 loop
            for i in range(0, len(chunked), self._group):
                members = [_PendingMember(r, slot, np.asarray(toks, np.int32))
                           for r, toks, slot in chunked[i:i + self._group]]
                self._pending.append(_PendingGroup(members))
            sp.set_metadata(n=len(admitted))
        return admitted

    def _plan_admission(self, reqs: Sequence[ServeRequest]):
        """Host half of ``admit_many``: prefix match, block reservation and
        grouping. Returns (admitted, bucketed groups, suffix groups,
        chunked); only a boundary copy-on-write (or a legacy batch-1
        prefill) dispatches here."""
        free = self.free_slots()
        admitted: List[ServeRequest] = []
        skipped = 0
        # free blocks live slots will claim at their NEXT boundary crossing;
        # admissions that would eat into it are deferred, so a fresh
        # admission can't guarantee an immediate preemption storm
        imminent = self._imminent_blocks() if (
            self._admit_headroom and self._lazy) else 0
        groups: Dict[int, List[Tuple[ServeRequest, List[int], int]]] = {}
        sgroups: Dict[int, List] = {}
        chunked: List[Tuple[ServeRequest, List[int], int]] = []
        # blocks pre-indexed THIS call whose content only materializes when
        # the full-prefill groups dispatch (before any suffix dispatch)
        fresh_this_call: set = set()
        for r in reqs:               # done reqs need no slot: pass through
            if r.done:
                self._admit_finished.append(r)
                admitted.append(r)
                continue
            if not free:
                break                # no slot for anyone: skipping can't help
            assert self._total_tokens(r) <= self.max_len, \
                "context exceeds engine max_len"
            slot = free[0]
            toks: Optional[List[int]] = None
            match = None
            if self.bm is not None:
                # prefill length without materializing the token list (it
                # is only built once the reservation succeeds) — unless the
                # prefix index needs it for matching
                ctx = r.ctx_len - (1 if r.generated else 0)
                live = ctx if self._lazy else None
                if self._prefix is not None:
                    toks = self._prefill_tokens(r)
                    match = self._prefix.match(toks)
                shared = match.full if match is not None else None
                n_sh = len(shared) if shared else 0
                if imminent > 0:
                    fresh = max(0, self.bm.blocks_for(ctx) - n_sh)
                    if self.bm.blocks_free() - fresh < imminent:
                        self.stats.admit_deferred += 1
                        skipped += 1
                        if skipped >= self._admit_window:
                            break
                        continue
                boundary = match.boundary if match is not None else None
                if not self.bm.reserve(slot, self._total_tokens(r), live,
                                       shared=shared, boundary=boundary):
                    self.stats.alloc_failures += 1
                    skipped += 1
                    if skipped >= self._admit_window:
                        break        # backpressure: leave the rest queued
                    continue         # skip ahead: smaller reqs may still fit
                self.bm.note_live(slot, ctx)         # true-frag accounting
                self._tbl_dirty = True
            free.pop(0)
            if not r.admit_ns:
                r.admit_ns = obs.now_ns()
            if toks is None:
                toks = self._prefill_tokens(r)
            if self.admission == "legacy":
                self._admit_one_legacy(r, toks, slot)
            elif match is not None and match.n_tokens > 0:
                cow = None
                if match.boundary is not None:
                    # COW the partially-shared boundary block before any
                    # suffix write lands in it. A donor admitted THIS call
                    # hasn't prefilled yet — its copy is deferred to the
                    # suffix dispatch (full-prefill groups run first, and
                    # the donor's mapping keeps the source block pinned).
                    dst = int(self.bm.table[slot, len(match.full)])
                    if match.boundary in fresh_this_call:
                        cow = (match.boundary, dst)
                    else:
                        self._copy_block(match.boundary, dst)
                self.stats.prefix_hits += 1
                self.stats.prefix_shared_tokens += match.n_tokens
                sgroups.setdefault(
                    self._bucket(len(toks) - match.n_tokens), []).append(
                    (r, toks, slot, match.n_tokens, cow))
            elif self._use_chunked(len(toks)):
                self.slots[slot] = r
                chunked.append((r, toks, slot))
            else:
                groups.setdefault(self._bucket(len(toks)), []).append(
                    (r, toks, slot))
                if self._prefix is not None:
                    # pre-index so later requests in this SAME call share;
                    # safe because every full-prefill group dispatches
                    # before the first suffix dispatch
                    self._index_insert(toks, slot)
                    fresh_this_call.update(self.bm.slot_blocks(slot))
            admitted.append(r)
        return admitted, groups, sgroups, chunked

    def _admit_group(self, items, blen: int) -> None:
        """One batched prefill + fused scatter for <= prefill_group
        requests sharing a length bucket."""
        g, n = self._group, len(items)
        tokens = np.zeros((g, blen), np.int32)
        lens = np.zeros((g,), np.int32)
        slots = np.zeros((g,), np.int32)
        rows = np.zeros((g,), np.int32)
        for j, (r, toks, slot) in enumerate(items):
            tokens[j, :len(toks)] = toks
            lens[j] = len(toks)
            slots[j] = slot
            rows[j] = j
        # pad rows replicate row 0: duplicate slot writes carry identical
        # data, keeping the scatter deterministic
        lens[n:] = lens[0]
        slots[n:] = slots[0]
        with obs.span("serve.prefill", rows=n,
                      tokens=sum(len(toks) for _, toks, _ in items)):
            logits, group_cache = self._prefill_b(
                self.params, self._put(tokens), self._put(lens - 1))
            self._scatter_group(group_cache, slots, rows, lens)
        first, t_ns = self._sample(logits, "first")
        self.stats.prefill_batches += 1
        for j, (r, toks, slot) in enumerate(items):
            self._install(r, slot, first[j], t_ns)

    def _admit_group_suffix(self, items, blen: int) -> None:
        """Prefix-sharing admission: one batched SUFFIX prefill for <=
        prefill_group requests sharing a suffix-length bucket. Each row's
        shared prefix is already resident (mapped via its block table); the
        dispatch computes/writes only the divergent suffix and samples the
        first token from each row's last real suffix position."""
        g, n = self._group, len(items)
        tokens = np.zeros((g, blen), np.int32)
        bases = np.zeros((g,), np.int32)
        lens = np.zeros((g,), np.int32)
        slots = np.zeros((g,), np.int32)
        for j, (r, toks, slot, n_sh, cow) in enumerate(items):
            if cow is not None:       # deferred COW: donor prefilled by now
                self._copy_block(*cow)
            suf = toks[n_sh:]
            tokens[j, :len(suf)] = suf
            bases[j] = n_sh
            lens[j] = len(suf)
            slots[j] = slot
        # pad rows replicate row 0: duplicate slot writes carry identical
        # data, keeping the scatter deterministic
        tokens[n:] = tokens[0]
        bases[n:] = bases[0]
        lens[n:] = lens[0]
        slots[n:] = slots[0]
        tbls = self.bm.table[slots]
        with obs.span("serve.suffix", rows=n,
                      tokens=sum(len(t) - k for _, t, _, k, _ in items)):
            logits, self.cache = self._run(
                self._suffix, self.params, self.cache, self._put(tokens),
                self._put(bases), self._put(lens), self._put(slots),
                self._put(tbls))
        first, t_ns = self._sample(logits, "first")
        self.stats.prefill_batches += 1
        for j, (r, toks, slot, n_sh, cow) in enumerate(items):
            self._index_insert(toks, slot)
            self._install(r, slot, first[j], t_ns)

    def _copy_block(self, src: int, dst: int) -> None:
        """Copy-on-write of a partially shared boundary block, before any
        suffix write lands in it."""
        with obs.span("serve.cow", rows=1, tokens=self.bm.block_size):
            self.bm.note_cow(src, dst)
            self.cache = self._cow(self.cache, self._put(np.int32(src)),
                                   self._put(np.int32(dst)))
        self.stats.cow_copies += 1

    def _sample(self, logits, what: str) -> Tuple[np.ndarray, int]:
        """Wait for the greedy tokens of ``logits`` on the host; returns
        them with the host time they landed. ``what`` is "first" (an
        admission's first tokens) or "decode"."""
        with obs.span("serve.sync", what=what):
            # jaxlint: disable=host-sync -- intended: the engine's waits for
            # sampled tokens, which host-side scheduling needs next
            toks = np.asarray(self.model.sample_greedy(logits))
            return toks, obs.now_ns()

    def _index_insert(self, toks, slot: int) -> None:
        """Register a freshly-prefilled context's blocks with the prefix
        index (BEFORE ``_install`` may free an immediately-done slot — a
        freed block's content stays valid, which is exactly how a hot
        prefix survives its first request's completion)."""
        if self._prefix is not None:
            self._prefix.insert(toks, self.bm.slot_blocks(slot))

    def _scatter_group(self, group_cache, slots, rows, lens) -> None:
        """Fused install of a (remapped) group cache into slot rows, routed
        through the block tables under the paged layout."""
        args = [self._put(slots), self._put(rows), self._put(lens)]
        if self.bm is not None:
            args.append(self._put(self.bm.table[slots]))
        self.cache = self._scatter(self.cache, group_cache, *args)

    def _install(self, req: ServeRequest, slot: int, first_tok,
                 t_ns: int) -> None:
        """Post-prefill bookkeeping shared by all admission paths;
        ``t_ns`` is when the sampled token reached the host."""
        self.slots[slot] = req
        self.stats.prefills += 1
        if not req.generated:        # fresh request: prefill emits 1st token
            req.generated.append(int(first_tok))
            req.first_token_ns = t_ns
            self.stats.tokens_out += 1
        if req.done:
            req.finish_ns = t_ns
            self.slots[slot] = None
            self._free_blocks(slot)
            self._admit_finished.append(req)

    def _admit_one_legacy(self, req: ServeRequest, toks: List[int],
                          slot: int) -> None:
        """Seed admission path: eager batch-1 exact-length prefill plus a
        per-key Python scatter loop (one trace per distinct length)."""
        if len(toks) not in self._legacy_shapes:
            self._legacy_shapes.add(len(toks))
            self.stats.retraces += 1
            self.stats.prefill_retraces += 1
        tokens = jnp.asarray([toks], jnp.int32)
        if self.cfg.is_encdec:
            frames = jnp.zeros((1, self.enc_frames, self.cfg.d_model),
                               jnp.float32)
            logits, one = self.model.prefill(
                self.params, {"embeds": frames, "tokens": tokens},
                max_len=self.max_len)
        else:
            logits, one = self.model.prefill(self.params,
                                             {"tokens": tokens},
                                             max_len=self.max_len,
                                             ring=False)
        self._scatter_cache_legacy(slot, one, len(toks))
        self.stats.prefill_batches += 1
        first = int(self.model.sample_greedy(logits)[0])
        self._install(req, slot, first, obs.now_ns())

    def _scatter_cache_legacy(self, slot: int, one: Dict,
                              ctx_len: int) -> None:
        """Write a single-request cache (batch dim 1) into ``slot``."""
        def scatter(big, small, batch_axis):
            idx = [slice(None)] * big.ndim
            idx[batch_axis] = slice(slot, slot + 1)
            pad = [(0, b - s) for b, s in
                   zip(big[tuple(idx)].shape, small.shape)]
            if any(p != (0, 0) for p in pad):
                small = jnp.pad(small, pad)
            return big.at[tuple(idx)].set(small.astype(big.dtype))

        for key, small in one.items():
            if key == "pos":
                self.cache["pos"] = self.cache["pos"].at[slot].set(ctx_len)
            elif key == "slot_pos":
                continue                      # engine caches are linear
            else:
                self.cache[key] = scatter(self.cache[key], small, 1)

    # -- chunked prefill --------------------------------------------------------
    def _chunk_init(self, g: int):
        """Transient group cache for the contig chunked path (enc-dec
        groups additionally warm the cross-attention cache with one
        encoder pass over the stubbed frames)."""
        if self.cfg.is_encdec:
            frames = jnp.zeros((g, self.enc_frames, self.cfg.d_model),
                               jnp.float32)
            return self._enc_warm(self.params, frames)
        with jax.default_device(self.device):
            return self._put(self.model.init_cache(g, self.max_len,
                                                   ring=False))

    def _advance_pending(self) -> None:
        """One chunk of prefill work per pending GROUP, interleaved between
        decode steps (bounds head-of-line blocking; one dispatch covers
        every member at the shared chunk boundary).

        Paged engines write each chunk's K/V STRAIGHT into the owning
        slots' pool blocks, routed through a snapshot of their block
        tables — no transient group cache is ever allocated and finishing
        needs no scatter (``stats.chunk_direct``). Contig engines keep the
        transient-cache + terminal-scatter path (the A/B baseline, and the
        only option without block routing)."""
        c = self.prefill_chunk
        still: List[_PendingGroup] = []
        for grp in self._pending:
            g = len(grp.members)
            chunk = np.zeros((g, c), np.int32)
            last_idx = np.zeros((g,), np.int32)
            rem = np.zeros((g,), np.int32)
            rows = tokens = 0
            for j, m in enumerate(grp.members):
                if m.done:
                    continue        # finished early: row computes pad zeros
                end = min(grp.base + c, len(m.tokens))
                chunk[j, :end - grp.base] = m.tokens[grp.base:end]
                last_idx[j] = min(c - 1, len(m.tokens) - 1 - grp.base)
                rem[j] = end - grp.base
                rows, tokens = rows + 1, tokens + end - grp.base
            if self.bm is not None:
                # snapshot the members' table rows; finished members (whose
                # slots now decode, or may even have been reused) are routed
                # wholesale to the trash block — their rows compute don't-care
                tbls = self.bm.table[
                    [m.slot for m in grp.members]].copy()
                tbls[rem == 0] = 0
                if self.bm.sanitize:
                    for j, m in enumerate(grp.members):
                        if rem[j]:
                            # jaxlint: disable=host-sync -- host numpy rem
                            # (sanitizer-armed debug path only)
                            hi = grp.base + int(rem[j])
                            self.bm.check_write(m.slot, grp.base, hi)
            with obs.span("serve.chunk", rows=rows, tokens=tokens,
                          base=grp.base):
                if self.bm is not None:
                    logits, self.cache = self._run(
                        self._chunk_paged, self.params, self.cache,
                        self._put(chunk), self._put(np.int32(grp.base)),
                        self._put(last_idx), self._put(rem), self._put(tbls))
                    self.stats.chunk_direct += 1
                else:
                    if grp.cache is None:
                        grp.cache = self._chunk_init(g)
                    logits, grp.cache = self._chunk(
                        self.params, grp.cache, self._put(chunk),
                        self._put(np.int32(grp.base)), self._put(last_idx))
            self.stats.prefill_chunks += 1
            grp.base += c
            finishers = [(j, m) for j, m in enumerate(grp.members)
                         if not m.done and grp.base >= len(m.tokens)]
            if finishers:
                first, t_ns = self._sample(logits, "first")
                self._finish_pending(grp, finishers, first, t_ns)
            if not all(m.done for m in grp.members):
                still.append(grp)
        self._pending = still

    def _finish_pending(self, grp: _PendingGroup, finishers, first,
                        t_ns: int) -> None:
        """Finish fully-prefilled members. Paged groups already wrote every
        chunk in place through the block tables — only the per-slot cache
        positions need setting; contig groups scatter out of the transient
        group cache (one fused dispatch for this step's finishers)."""
        slots = np.array([m.slot for _, m in finishers], np.int32)
        lens = np.array([len(m.tokens) for _, m in finishers], np.int32)
        if self.bm is not None:
            self.cache["pos"] = self.cache["pos"].at[
                self._put(slots)].set(self._put(lens))
        else:
            rows = np.array([j for j, _ in finishers], np.int32)
            self._scatter_group(grp.cache, slots, rows, lens)
            self.stats.chunk_scatters += 1
        for j, m in finishers:
            m.done = True
            self.slots[m.slot] = None     # _install re-marks the slot
            self._index_insert(list(m.tokens), m.slot)
            self._install(m.req, m.slot, first[j], t_ns)

    # -- decode-time grow / preemption ------------------------------------------
    def _victim_cost(self, slot: int) -> float:
        """Estimated re-admission cost of preempting this slot: the
        cheaper of the store restore round trip
        (``recovery.preemption_seconds``) and a context recompute
        (``recovery.recompute_seconds``, when a placement prices it) —
        the same estimates the cluster simulator charges. Context is
        bucketed to the block grid before pricing: two slots whose KV
        occupies the same number of blocks cost the same to re-admit, so
        the fewest-generated rule stays the live tie-break instead of
        being drowned by sub-block context noise."""
        r = self.slots[slot]
        bs = self.bm.block_size if self.bm is not None else 16
        ctx_b = max(bs, -(-r.ctx_len // bs) * bs)
        c = self._victim_costs.get(ctx_b)
        if c is None:
            from repro.cluster.recovery import (preemption_seconds,
                                                recompute_seconds)
            if self._victim_spec is None:
                self._victim_spec = self.cfg.to_modelspec()
            c = preemption_seconds(self._victim_spec, ctx_b)
            if self._placement is not None:
                c = min(c, recompute_seconds(
                    self._victim_spec, self._placement, ctx_b,
                    chunk=self.prefill_chunk, max_len=self.max_len))
            self._victim_costs[ctx_b] = c
        return c

    def _pick_victim(self, candidates: List[int]) -> Optional[int]:
        """Preemption victim. Policy "cost": the slot whose re-admission
        is estimated cheapest (``_victim_cost``); fewest generated tokens
        breaks cost ties (least progress to park), slot index breaks the
        rest. Policy "fewest": the legacy fewest-generated-only rule."""
        owned = [i for i in candidates if self.slots[i] is not None]
        if not owned:
            return None
        if self._victim_policy == "fewest":
            return min(owned, key=lambda i: (len(self.slots[i].generated),
                                             i))
        return min(owned, key=lambda i: (self._victim_cost(i),
                                         len(self.slots[i].generated), i))

    def _preempt(self, slot: int) -> None:
        """Evict a live slot to make room: export its KV (position-exact,
        so re-admission can attach byte-identically), free its blocks, and
        park (request, payload) for the server to publish + requeue."""
        req = self.slots[slot]
        payload = self.export_kv(slot)
        self.slots[slot] = None
        self.bm.free(slot)
        self._poison_released()
        self._tbl_dirty = True
        self.stats.preemptions += 1
        self._preempted.append((req, payload))

    def _ensure_grow(self, live: List[int]) -> List[int]:
        """Demand paging's decode-side half: every slot decoding this step
        writes token ``pos``, so its block table must cover ``pos + 1``
        tokens — which is the request's ``ctx_len`` (§5.1 invariant:
        everything but the last generated token is in the cache), so no
        device sync is needed. Grow crossing slots by a block; when the
        free list is dry, preempt victims until the grow fits (preempting
        the grower itself ends its grow — it re-attaches later). Returns
        the slots that still decode this step."""
        grows0 = self.bm.grows
        alive = list(live)
        k = self._grow_ahead
        for slot in list(live):
            if self.slots[slot] is None:        # preempted by an earlier grow
                continue
            need = self.slots[slot].ctx_len
            if k > 1:
                crossing = (self.bm.blocks_for(need)
                            > self.bm.blocks_for(need - 1))
                if crossing and (self.bm.covered_blocks(slot)
                                 >= self.bm.blocks_for(need)):
                    # hysteresis win: an earlier look-ahead grow already
                    # covers this boundary crossing — no dispatch, no
                    # preempt/re-admit thrash near pool-full
                    self.stats.grow_ahead_skips += 1
                    continue
            # look ahead only with free-list headroom; exactly one block
            # when the pool is tight (look-ahead must never force preempts)
            ahead = (k - 1 if k > 1
                     and self.bm.blocks_free() >= len(alive) + k else 0)
            while not self.bm.grow(slot, need, ahead=ahead):
                ahead = 0
                victim = self._pick_victim(alive)
                assert victim is not None, "grow failed with no live victim"
                self._preempt(victim)
                alive.remove(victim)
                if victim == slot:
                    break
        if self.bm.grows > grows0:
            self.stats.block_grows += self.bm.grows - grows0
            self._tbl_dirty = True
        return [i for i in alive if self.slots[i] is not None]

    def _imminent_blocks(self) -> int:
        """Free blocks live slots will need at their NEXT decode step's
        boundary crossing — the headroom admission must not consume."""
        if self.bm is None:
            return 0
        pend = self._pending_slots()
        n = 0
        for i, r in enumerate(self.slots):
            if r is None or r.done or i in pend:
                continue
            n += max(0, self.bm.blocks_for(r.ctx_len + 1)
                     - self.bm.covered_blocks(i))
        return n

    # -- decode -----------------------------------------------------------------
    def step(self) -> List[ServeRequest]:
        """One scheduling iteration: re-attach preempted requests capacity
        now allows, advance chunked prefills, grow block tables crossing a
        block boundary (preempting victims when the pool is dry), then
        decode one token for every live slot; returns finished requests."""
        if self._preempted:
            self._readmit_preempted()
        if self._pending:
            self._advance_pending()
        finished = list(self._admit_finished)
        self._admit_finished.clear()
        pending = self._pending_slots()
        live = [i for i, s in enumerate(self.slots)
                if s is not None and i not in pending]
        if not live:
            return finished
        if self._lazy:           # upfront allocations can never need a grow
            with obs.span("serve.grow"):
                live = self._ensure_grow(live)
            if not live:
                return finished
        with obs.span("serve.decode", rows=len(live)) as sp:
            tokens = np.zeros((self.max_batch, 1), np.int32)
            mask = np.zeros((self.max_batch,), bool)
            for i in live:
                tokens[i, 0] = self.slots[i].generated[-1]
                mask[i] = True
            if self.bm is not None and obs.recording():
                # the live rows' pool blocks (what the decode kernel's page
                # loop reads per layer when no window is set)
                sp.set_metadata(pages=sum(
                    self.bm.blocks_for(self.slots[i].ctx_len) for i in live))
            if self.bm is not None and self.bm.sanitize:
                for i in live:
                    # this dispatch reads each live slot's KV history and
                    # writes the incoming token at position ctx_len - 1
                    self.bm.check_read(i, self.slots[i].ctx_len - 1)
                    self.bm.check_write(i, self.slots[i].ctx_len - 1,
                                        self.slots[i].ctx_len)
            self._sync_block_tbl()
            logits, self.cache = self._run(self._decode, self.params,
                                           self.cache, self._put(tokens),
                                           self._put(mask))
        # THE per-step sync point: sampled tokens feed the next step's
        # host-side scheduling; every other sync in step() has been
        # eliminated, so the pipeline stalls exactly once per decode step
        nxt, t_ns = self._sample(logits, "decode")
        nxt = nxt[:, 0]
        for i in live:
            req = self.slots[i]
            req.generated.append(int(nxt[i]))
            self.stats.tokens_out += 1
            if self.bm is not None:
                # tokens in the cache == ctx_len - 1 (§5.1 invariant)
                self.bm.note_live(i, req.ctx_len - 1)
            if req.done:
                req.finish_ns = t_ns
                finished.append(req)
                self.slots[i] = None
                self._free_blocks(i)
        self.stats.decode_steps += 1
        return finished

    def _readmit_preempted(self) -> None:
        """Re-attach parked preempted requests whose blocks now fit
        (standalone operation; the global server normally drains
        ``take_preempted`` every round before this can fire)."""
        still: List[Tuple[ServeRequest, Dict]] = []
        with obs.span("serve.readmit"):
            for req, payload in self._preempted:
                if not self.import_kv(req, payload):
                    still.append((req, payload))
        self._preempted = still

    def take_preempted(self) -> List[Tuple[ServeRequest, Dict]]:
        """Drain (request, KV payload) pairs evicted by dry-pool grows —
        the global server publishes the payloads to the tensor store and
        requeues the requests for KV-attach re-admission."""
        out, self._preempted = self._preempted, []
        return out

    def drain(self) -> List[ServeRequest]:
        """Run until every admitted request finishes."""
        out = []
        while (self.active() or self._pending or self._admit_finished
               or self._preempted):
            out.extend(self.step())
        return out

    def evict_all(self) -> List[ServeRequest]:
        """Simulated engine death: return in-flight requests (their
        ``generated`` lists are the preserved output — paper §5.1),
        including preempted ones still parked for re-admission."""
        reqs = [s for s in self.slots if s is not None]
        reqs += [r for r, _ in self._preempted]
        reqs += [r for r in self._admit_finished if r not in reqs]
        self.slots = [None] * self.max_batch
        self._pending = []
        self._admit_finished = []
        self._preempted = []
        if self.bm is not None:
            self.bm.free_all()
            self._tbl_dirty = True
        return reqs

    # -- block-granular KV migration (paper §5.1 x §5.2) ------------------------
    def export_kv(self, slot: int, pos: Optional[int] = None) -> Dict:
        """Snapshot a live slot's KV blocks for publication to the tensor
        store. The payload is position-exact: importing it reproduces the
        donor engine's cache state for that request byte-for-byte."""
        assert self.bm is not None, "KV export requires the paged layout"
        if pos is None:
            # §5.1 invariant: a live, fully-prefilled slot's cache holds
            # everything but the last generated token, so its position is
            # ctx_len - 1. Reading it from the request avoids syncing the
            # device pos array on the dry-pool preemption hot path (the
            # same identity note_live/import_kv already rely on).
            pos = self.slots[slot].ctx_len - 1
        self.bm.check_read(slot, pos)      # no-op unless sanitize mode
        nb = -(-pos // self.bm.block_size) if pos > 0 else 0
        ids = self._put(self.bm.table[slot, :nb].copy())
        return {"k": self.cache["k"][:, ids], "v": self.cache["v"][:, ids],
                "pos": int(pos), "block_size": self.bm.block_size,
                "arch": self.cfg.name}

    def export_live_kv(self) -> Dict[int, Dict]:
        """Payloads for every live, fully-prefilled slot, keyed by request
        id (mid-chunked-prefill slots have incomplete KV and are skipped —
        those requests fall back to recompute)."""
        if self.bm is None:
            return {}
        pend = self._pending_slots()
        # §5.1 invariant (see export_kv): pos == ctx_len - 1 for every
        # live, fully-prefilled slot — no device sync needed here either
        return {r.rid: self.export_kv(slot, r.ctx_len - 1)
                for slot, r in enumerate(self.slots)
                if r is not None and slot not in pend}

    def import_kv(self, req: ServeRequest, payload: Dict) -> bool:
        """Admit ``req`` by attaching a published KV payload instead of
        recomputing its context. Returns False (caller falls back to the
        recompute path) on any incompatibility: contig layout, different
        arch or block size, no slot, no blocks, or a payload whose position
        doesn't match the request's migration state."""
        if self.bm is None or payload.get("arch") != self.cfg.name \
                or payload.get("block_size") != self.bm.block_size:
            return False
        if req.done or not req.generated:
            return False
        # invariant of the §5.1 layout: everything but the last generated
        # token is in the cache; the first decode step feeds that token
        if payload["pos"] != req.ctx_len - 1:
            return False
        free = self.free_slots()
        if not free or self._total_tokens(req) > self.max_len:
            return False
        slot = free[0]
        # lazy: allocate only the blocks the payload fills (the ledger
        # books the worst case); the rest arrive via decode-time grow
        live = payload["pos"] if self._lazy else None
        if not self.bm.reserve(slot, self._total_tokens(req), live):
            return False             # no capacity yet: caller retries later
        self.bm.note_live(slot, payload["pos"])
        self._tbl_dirty = True
        nb = payload["k"].shape[1]
        ids = self._put(self.bm.table[slot, :nb].copy())
        # the payload may come from another pipeline's device
        self.cache["k"] = self.cache["k"].at[:, ids].set(
            self._put(payload["k"]).astype(self.cache["k"].dtype))
        self.cache["v"] = self.cache["v"].at[:, ids].set(
            self._put(payload["v"]).astype(self.cache["v"].dtype))
        self.cache["pos"] = self.cache["pos"].at[slot].set(payload["pos"])
        self.slots[slot] = req
        if not req.admit_ns:
            req.admit_ns = obs.now_ns()
        self.stats.kv_imports += 1
        return True

    # -- shared-prefix publication / warm-up (tentpole, cluster half) -----------
    def export_prefix(self, tokens) -> Optional[Dict]:
        """Snapshot the KV blocks of a fully-indexed token run for
        publication to the tensor store (content-addressed by the run
        itself). Full blocks only: partial boundary blocks keep mutating
        under decode and are never published."""
        if self._prefix is None:
            return None
        ids = self._prefix.full_run(tokens)
        if not ids:
            return None
        idsj = self._put(np.asarray(ids, np.int32))
        toks = [int(t) for t in tokens[:len(ids) * self.bm.block_size]]
        return {"k": self.cache["k"][:, idsj], "v": self.cache["v"][:, idsj],
                "tokens": toks, "block_size": self.bm.block_size,
                "arch": self.cfg.name}

    def hot_runs(self, min_hits: int = 2) -> List[Tuple[int, ...]]:
        """The hottest fully-indexed token runs (matched at least
        ``min_hits`` times). Cheap — no KV gather — so the server can
        content-address them against the store BEFORE exporting."""
        return [] if self._prefix is None else self._prefix.hot(min_hits)

    def hot_prefixes(self, min_hits: int = 2) -> List[Dict]:
        """Payloads for the hottest shared-prefix runs — the server
        publishes them to the store."""
        out = []
        for run in self.hot_runs(min_hits):
            p = self.export_prefix(run)
            if p is not None:
                out.append(p)
        return out

    def warm_prefix(self, payload: Dict) -> bool:
        """Attach a published shared-prefix payload: write its KV into
        free blocks, index them, and hand the blocks straight back to the
        free list (refcount 0) — warm, fully reclaimable, and mapped
        read-only by the next admission matching the prefix. Returns False
        (recompute fallback) on any incompatibility or when the prefix is
        already resident."""
        if self._prefix is None or self.bm is None:
            return False
        if payload.get("arch") != self.cfg.name \
                or payload.get("block_size") != self.bm.block_size:
            return False
        toks = [int(t) for t in payload["tokens"]]
        nb = len(toks) // self.bm.block_size
        if nb <= 0 or payload["k"].shape[1] < nb:
            return False
        if len(self._prefix.full_run(toks)) >= nb:
            return False             # already warm (or computed locally)
        ids = self.bm.warm_blocks(nb)
        if ids is None:
            return False             # pool too tight right now
        idsj = self._put(np.asarray(ids, np.int32))
        self.cache = self._warm(self.cache, self._put(payload["k"][:, :nb]),
                                self._put(payload["v"][:, :nb]), idsj)
        self._prefix.insert(toks[:nb * self.bm.block_size], ids)
        self.bm.warm_release(ids)
        self.stats.prefix_warmups += 1
        return True
