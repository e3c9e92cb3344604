"""Continuous-batching engines over the paged decoder: slot scheduling,
horizon-fused decode, ragged chunked-prefill admission, prefix-cache
admission, speculative decoding."""
import collections
import time
import weakref

import jax.numpy as jnp
import numpy as np

from ..framework.core import Tensor
from ..profiler import _compiles_since, build_span, span
from .decoder import (PagedGPTDecoder, _spec_accept, packed_window,
                      pow2_at_least)
from .stats import _ENGINES, ServeStats

__all__ = ["ContinuousBatchingEngine", "SpeculativeEngine"]

# bounded schedule-event window: the SERVE-PREFILL-STALL audit reads
# the most recent scheduling decisions, not the process lifetime
_SCHED_WINDOW = 4096


class _Phase:
    """One phase of a scheduling round: a `profiler.span` around it (in
    the trace whenever a profiler session runs, half a microsecond
    otherwise) and its seconds on the host's clock added to the
    horizon's record under `key` (`rec` None: the span alone, as for a
    `step()` called outside a run loop)."""
    __slots__ = ("_span", "_rec", "_key", "_t0")

    def __init__(self, name, rec, key, **ids):
        self._span = span(name, **ids)
        self._rec, self._key = rec, key

    def __enter__(self):
        self._span.__enter__()
        self._t0 = time.perf_counter()

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._span.__exit__(*exc)
        if self._rec is not None:
            self._rec[self._key] += dt
        return False


class ContinuousBatchingEngine:
    """Slot-based continuous batching: requests are admitted into free
    slots as soon as capacity allows (iteration-level scheduling), decode
    runs one compiled step for ALL active slots, finished sequences free
    their pages.

    By default `run()` schedules RAGGED horizons (Ragged Paged
    Attention, arxiv 2604.15464): blocks of k device-resident ticks
    (`PagedGPTDecoder.ragged_multi`) in which decode rows emit a token
    per tick while newly admitted prompts stream their uncached
    suffixes in as token-budgeted CHUNKS — admission mounts
    prefix-cache pages and allocates the table row host-side, then
    hands the suffix to the device carry; there is NO host-blocking
    prefill dispatch on the decode critical path, so one long prompt
    costs running slots at most a few slightly-longer ticks instead of
    a monolithic prefill stall (`serving.RaggedScheduler` owns the
    chunk/horizon policy; the SERVE-PREFILL-STALL rule audits the
    scheduling trace). The host syncs only at block boundaries for
    admission/retirement/output append, and each block's fetch is
    overlapped against the NEXT block's dispatch (one-horizon-delayed
    retirement: a slot finishing inside block N stays frozen on device
    through block N+1 — its writes route to the scratch page — and its
    pages are freed exactly once, when block N is processed).
    `ragged=False` keeps the dispatch-separate loop (`_run_multi`:
    blocking chunked prefill at admission + decode-only
    `decode_multi` horizons — byte-identical streams; on the chip 2-3%
    more tokens/s, tails 1.4-1.8 x as long: PERF.md, PR 33). `k_max`
    defaults to `cost_model.decode_horizon`'s priced answer; `k_max=1`
    selects the legacy per-tick loop (`step()` is the per-tick API
    either way).

    With `prefix_cache` (a `PrefixCache`) admission becomes
    content-addressed: each prompt's full token blocks are hashed
    against the cache, fully-cached prefix spans are MOUNTED into the
    request's page-table row host-side (zero device work — the pages
    already hold exactly the KV bytes this prompt's prefill would
    write), and only the uncached suffix runs through the chunked
    prefill (`PagedGPTDecoder.prefill_suffix_batch`). Mounted pages are
    refcounted and immutable: a request about to write into a shared
    page (the first divergent token — only possible when the WHOLE
    prompt was cached and its last position must be re-consumed for
    logits) gets a copy-on-write private copy first. Retirement decrefs
    shared pages instead of freeing them; refcount-0 pages park in the
    cache's LRU and are evicted back to the free list only under pool
    pressure — every page freed exactly once, auditable via
    `page_ledger()`/`audit_pages()` (MEM-PAGE-REFCOUNT)."""

    @build_span("engine.build")
    def __init__(self, decoder: PagedGPTDecoder, eos_token_id=None,
                 max_new_tokens=64, k_max=None, host_sync_s=None,
                 prefix_cache=None, ragged=None, chunk_tokens=None,
                 scheduler=None, trace=None,
                 host_tier=None, tier_policy="auto"):
        if max_new_tokens < 1:
            raise ValueError(
                "max_new_tokens must be >= 1 (the prefill forward always "
                f"produces one token), got {max_new_tokens}")
        self.d = decoder
        self.eos = eos_token_id
        self.max_new = max_new_tokens
        # page 0..num_pages-2 allocatable; last page reserved as scratch
        self._free = list(range(decoder.num_pages - 2, -1, -1))
        S = decoder.max_batch
        self._slot_req = [None] * S          # request id per slot
        self._slot_pages = [[] for _ in range(S)]
        # pages a slot holds as SHARED (cache-refcounted, never written)
        self._slot_shared = [set() for _ in range(S)]
        # int32 end to end: decode() feeds these to the kernel as int32,
        # so int64 here would insert a convert_element_type every tick
        self._lens = np.zeros(S, np.int32)
        self._tokens = np.zeros(S, np.int32)
        self._kids = np.zeros(S, np.int32)   # request id per slot: the
        # sampling key id, so a request's draws are independent of
        # which slot/batch/schedule served it
        self._aids = np.zeros(S, np.int32)   # LoRA adapter id per slot
        # (multi-LoRA: only consulted when the decoder carries a bank)
        self._rid_adapter = {}               # rid -> adapter id (!= 0)
        # per-slot admission generation: a block dispatched for an
        # earlier occupancy of the slot must never book-keep against a
        # later one — the rid check alone can't tell them apart once
        # preemption (serving.tenancy) lets the SAME rid re-occupy a
        # slot whose stale block is still in flight
        self._slot_gen = [0] * S
        # rid -> output length at (re-)admission: the "first token of
        # this admission" mark. 0 for fresh requests (so the base
        # engine's behavior is unchanged); a preempted request resumes
        # with its generated prefix already in _outputs, and its first
        # post-resume token must NOT restamp TTFT or republish from
        # scratch
        self._emit_base = {}
        self._table_cache = None             # rebuilt on admit/retire only
        self._queue = []                     # (req_id, ids)
        self._outputs = {}                   # req_id -> [generated ids]
        self._next_id = 0
        self.steps = 0
        self.k_max = max(1, int(k_max)) if k_max is not None else None
        if prefix_cache is True:
            from .prefix_cache import PrefixCache
            prefix_cache = PrefixCache(decoder.page_size,
                                       salt=decoder.cache_fingerprint())
        if prefix_cache is not None and \
                prefix_cache.page_size != decoder.page_size:
            raise ValueError(
                f"prefix cache page_size {prefix_cache.page_size} != "
                f"decoder page_size {decoder.page_size}")
        self.cache = prefix_cache
        # TIERED KV (serving.kv_tier): a host-RAM spill tier behind the
        # prefix cache — refcount-0 pages evicted under pool pressure
        # demote their bytes to a capacity-bounded host LRU instead of
        # vanishing, and admissions whose chain continues onto host
        # entries restore them via H2D when the wire beats the prefill
        # recompute (tier_policy: "auto" = cost_model-priced per
        # admission; "restore"/"recompute" pin the decision — the CPU
        # bench pins "restore" since tiny-model recompute always wins
        # the pricing there). host_tier=True builds a default
        # HostKVTier; a PrefixCache constructed with tier= works too.
        # identity checks, not truthiness: an EMPTY HostKVTier is falsy
        # (__len__ == 0) but very much "tier on"
        if host_tier is not None and host_tier is not False:
            if self.cache is None:
                raise ValueError(
                    "host_tier needs a prefix_cache: the tier is keyed "
                    "by the cache's chain keys (pass prefix_cache=True "
                    "for a default cache)")
            if self.cache.tier is None:
                from .kv_tier import HostKVTier
                self.cache.tier = HostKVTier() if host_tier is True \
                    else host_tier
            elif host_tier is not True and \
                    host_tier is not self.cache.tier:
                # a loaded cache may arrive with a WARM tier — silently
                # replacing it would drop the persisted host entries
                raise ValueError(
                    "prefix_cache already carries a host tier — pass "
                    "host_tier=True to keep it, or attach your tier "
                    "to the cache (PrefixCache.load(tier=...)) "
                    "instead")
        self.tier = self.cache.tier if self.cache is not None else None
        if tier_policy not in ("auto", "restore", "recompute"):
            raise ValueError(f"tier_policy must be auto/restore/"
                             f"recompute, got {tier_policy!r}")
        self.tier_policy = tier_policy
        self._restore_s_pending = 0.0    # priced H2D awaiting a horizon
        if self.cache is not None:
            # a PRELOADED cache (PrefixCache.load) already owns pages:
            # they are parked cache property, not free pool — and bind
            # the decoder so cache.save() can read the pool later
            if self.cache.n_pages:
                # a populated cache's pages live in the pool of the
                # decoder it is bound to (PrefixCache.load and every
                # engine bind one) — with any OTHER decoder (even
                # same-weights: its pool does not hold these pages)
                # the chain keys would still hit and mount garbage KV
                # with no error anywhere
                bound = self.cache._decoder and self.cache._decoder()
                if bound is not decoder:
                    raise ValueError(
                        "prefix_cache holds pages for a different "
                        "decoder — pass the decoder the cache was "
                        "loaded onto, or PrefixCache.load the save "
                        "dir onto THIS decoder")
                owned = set(self.cache.pages())
                self._free = [p for p in self._free if p not in owned]
            self.cache._decoder = weakref.ref(decoder)
        decoder._engines.add(self)
        self._cache_meta = {}                # rid -> (start, keys, n_hit)
        # RAGGED scheduling (default on the multi-step path): prompt
        # suffixes stream into the SAME K-tick horizon as running
        # decode slots, w tokens per tick, with NO host-blocking
        # prefill dispatch on the decode critical path. ragged=False
        # keeps the dispatch-separate baseline (_run_multi: blocking
        # chunked prefill at admission + decode-only horizons).
        if scheduler is None and ragged is not False and \
                (self.k_max is None or self.k_max > 1 or ragged):
            from .scheduler import RaggedScheduler
            # k_max=None lets the SCHEDULER price K with the
            # chunk-aware mixed-tick roofline (decode_horizon's
            # chunk_tokens extension) — a compute-heavy chunk budget
            # correctly prices a smaller K than pure decode would
            scheduler = RaggedScheduler(decoder,
                                        chunk_tokens=chunk_tokens,
                                        k_max=self.k_max,
                                        host_sync_s=host_sync_s)
        self.scheduler = scheduler
        if self.k_max is None:
            if scheduler is not None:
                self.k_max = scheduler.k_max
            else:
                # explicitly non-ragged baseline: price K on the PURE
                # decode tick (no chunk compute leg, no scheduler)
                from ..cost_model import decode_horizon
                self.k_max = decode_horizon(decoder.step_hbm_bytes(),
                                            host_sync_s=host_sync_s)
        # a PRICED horizon of one tick (big model: the tick dwarfs the
        # sync) is still a ragged horizon — only an explicit k_max=1
        # asks for the legacy per-tick loop, whose blocking prefill
        # packs a whole admission wave into one dispatch
        self.ragged = bool((k_max is None or self.k_max > 1)
                           if ragged is None else ragged)
        _refuse_unserved(decoder, {
            "prefix_cache": self.cache is not None,
            "host_tier": self.tier is not None,
            "ragged=False": not self.ragged})
        self._prompt_len = [0] * S           # admitted prompt length/slot
        # THE record of every dispatched horizon (one "horizon" dict
        # each, always on: `_begin_round`), beside the "prefill_sync"
        # marks of the blocking path: what `serve_schedule()` returns,
        # the SERVE-PREFILL-STALL audit and a benchmark's readers
        # read, and — the same dicts — a flight recorder's ticks
        self._sched_events = collections.deque(maxlen=_SCHED_WINDOW)
        self._seq = 0                        # scheduling rounds begun
        self._open = None                    # the open round's record
        self.stats = ServeStats(
            engine=type(self).__name__, k_max=self.k_max,
            # num_pages - 1: the reserved scratch page never holds a
            # sequence's KV — capacity counts allocatable pages only
            kv_pool_bytes=(decoder.num_pages - 1) * decoder.kv_page_bytes,
            kv_bytes_per_token=decoder.kv_page_bytes // decoder.page_size)
        if self.tier is not None:
            # a warm-started tier (PrefixCache.load) already holds
            # resident bytes — the gauge must not read 0 until the
            # first spill/restore happens to refresh it
            self.stats.host_tier_bytes = self.tier.bytes_used
        self._submit_t = {}                  # rid -> submit wall time
        # FLIGHT RECORDER (serving.trace.FlightRecorder): off by
        # default; every hook below is a dead `if self.trace is not
        # None` branch, so the untraced engine does zero trace work
        # per tick (test-pinned). trace=True builds a default recorder.
        if trace is True:
            from .trace import FlightRecorder
            trace = FlightRecorder()
        self.trace = trace or None
        self._trace_price = None         # (hbm, flops/token, sync_s)
        self._trace_pool_mark = (0, 0)   # (cow, evictions) marks
        self._restore_warm = False       # a restore tick was recorded
        if self.trace is not None:
            self.trace.meta.update(
                engine=type(self).__name__, k_max=self.k_max,
                ragged=self.ragged,
                page_size=decoder.page_size,
                kv_quant=decoder.kv_quant or "none")
        _ENGINES.add(self)

    # ------------------------------------------------- flight recorder

    def _price_horizon(self, k, w, prefill_rows, decode_rows=0,
                       serial=False):
        """Roofline-PREDICTED wall cost of one dispatched horizon: k
        mixed ticks (`cost_model.ragged_tick_legs` priced on the
        tick's TOTAL new-token count — the decode HBM leg plus the
        compute leg of every new token, chunk rows at w each plus one
        per decode row; the packed layout's dispatch unit) plus ONE
        host sync. The tick records pair this with the measured wall
        time; the drift accounting (`FlightRecorder.drift_report` /
        ROOFLINE-DRIFT) is the predicted-vs-measured ledger.
        `serial=True` prices the SERIAL sum of the legs instead of
        their overlapped max — the ticks stamp both, so the ledger's
        verdict can tell a mispriced leg (measured outside even the
        sum) from a serialized schedule (measured at the sum).
        Called only with tracing on."""
        from ..cost_model import measured_host_sync_s, ragged_tick_legs
        if self._trace_price is None:
            sched = self.scheduler
            fpt = (sched.flops_per_token if sched is not None
                   else 2.0 * self.d.cfg.num_params())
            self._trace_price = (self.d.step_hbm_bytes(), fpt,
                                 measured_host_sync_s())
        hbm, fpt, sync = self._trace_price
        hbm_s, compute_s = ragged_tick_legs(
            hbm, w * prefill_rows + decode_rows, fpt)
        tick = (hbm_s + compute_s) if serial else max(hbm_s, compute_s)
        return k * tick + sync

    def _trace_pool_delta(self):
        """Pool events since the previous tick record (CoW copies,
        evictions), folded into each tick so the trace shows WHICH
        horizon paid for cache churn. Called only with tracing on."""
        cow, ev = self.stats.prefix_cow, self.stats.prefix_evictions
        d = {"cow": cow - self._trace_pool_mark[0],
             "evictions": ev - self._trace_pool_mark[1]}
        self._trace_pool_mark = (cow, ev)
        return d

    def _trace_event(self, kind, **fields):
        """One lifecycle event into the recorder, with the `seq` of the
        scheduling round it happened in (a submit between rounds
        carries the last round begun): a request's events join to the
        horizons' records and to the profiler's `engine.*` spans by
        id, with no conversion of clocks. Called only with tracing on."""
        return self.trace.record(kind, seq=self._seq, **fields)

    def _trace_admits(self, admitted, now):
        """Admit events with the prefix-cache mount detail (cached
        span, hit blocks) — the span segment between a request's
        submit and first_token marks. Called only with tracing on."""
        for slot, rid, ids, _pages in admitted:
            meta = self._cache_meta.get(rid)
            self._trace_event(
                "admit", ts=now, rid=rid, slot=slot,
                prompt_tokens=len(ids),
                cached_tokens=int(meta[0]) if meta else 0,
                hit_blocks=int(meta[2]) if meta else 0)

    def _trace_progress(self, rid):
        """Per-N-token progress mark (N = recorder.progress_every).
        Called only with tracing on, from the token-processing loops."""
        n = len(self._outputs[rid])
        if n % self.trace.progress_every == 0:
            self._trace_event("progress", rid=rid, tokens=n)

    # ------------------------------------------- the horizon's record

    def _begin_round(self):
        """Open the record of one scheduling round: THE dict that
        describes the horizon this round dispatches (a round that
        dispatches none drops it). Always on — some ten clock reads
        and twenty entries a horizon, microseconds against a shortest
        horizon of milliseconds. Stamped with `time.perf_counter()`
        where the work happens; every field is about THIS horizon, so
        on the pipelined loops `fetch_wait_s`, `book_s`, `on_sync_s`,
        `t_fetched` and the work fields are filled one round later,
        when its block lands. Fields (docs/observability.md):

        identity  kind, seq, k, w, t_tokens, decode_rows, prefill_rows,
                  slots, program (`PagedGPTDecoder.program_name`: the
                  name the jitted program and the `engine.dispatch`
                  span carry), compiles (the lowerings, compiles and
                  cache loads of the compile log that ended inside the
                  round, `profiler.compile_log`: any program's, this
                  one's in another signature included)
        queue     queue_depth after admission; admit_waits_s, the
                  submit-to-admit wait of each request admitted
        times     t_round, t_fetched; admit_s, plan_s, dispatch_s,
                  fetch_wait_s, book_s, on_sync_s
        work      tokens emitted, tokens_dispatched, tokens_padded
        pages     pages_gathered, the page copies ONE tick of the
                  program makes for K in one layer: slots x the
                  columns its attention walks, a row's pages once
                  whatever its tokens. A decoder whose walk ends at
                  the deepest row (`walk_block_pages`: whole blocks of
                  that many columns) counts the blocks that hold the
                  host's bound on the deepest position, at most the
                  table's width — an upper bound on what the device
                  walks, trailing it as pages_live does; a decoder
                  that copies the whole table (None) counts the width
                  it was handed. pages_live, the pages the rows' contexts
                  fill, sum of ceil(_lens / page_size) over rows
                  holding a request — the host's view at dispatch, not
                  fetched: it trails the device by the horizon in
                  flight, and a row still in prefill counts only its
                  cached prefix

        Every field has a reader, named in PERF.md section 3: a field
        nothing reads is not stamped."""
        self._seq += 1
        rec = self._open = {
            "kind": "horizon", "seq": self._seq, "admit_waits_s": [],
            "admit_s": 0.0, "plan_s": 0.0, "dispatch_s": 0.0,
            "fetch_wait_s": 0.0, "book_s": 0.0, "on_sync_s": 0.0,
            "t_round": time.perf_counter()}
        return rec

    def _note_admitted(self, admitted, now):
        """Queue-wait stamps (submit -> admit) of one admission pass:
        into the stats and into the open round's record."""
        rec = self._open
        for _, rid, _, _ in admitted:
            t0 = self._submit_t.get(rid)
            if t0 is not None:
                self._note_queue_wait(rid, now - t0)
                if rec is not None:
                    rec["admit_waits_s"].append(now - t0)
        if self.trace is not None:
            self._trace_admits(admitted, now)

    def _horizon_dispatched(self, rec, shape, program, k, w, t_tokens,
                            decode_rows, prefill_rows, disp_toks,
                            width, need, priced=True):
        """The open round has dispatched its horizon: stamp what it is
        and append the record to the schedule. `shape` is the dispatch
        shape the drift ledger keys on, `program` the name of the
        compiled program it ran (the shape with the table's width:
        `PagedGPTDecoder.program_name`), `width` the columns of the
        page table handed to it, `need` the columns that hold every
        position the horizon may reach (the host's bound, before
        `_table_width` rounds it up). With a recorder attached the
        SAME dict becomes its tick, the price added (`priced=False`:
        a window polluted by a blocking prefill is recorded unpriced
        and stays out of the ledger). The pending tiered-KV restore
        price is drained here even when untraced, so it cannot
        accumulate: the H2D of a restore dispatched at this round's
        admission lands inside THIS horizon's window."""
        restore_s = self._take_restore_s()
        ps = self.d.page_size
        block = self.d.walk_block_pages
        walked = width if block is None else \
            min(width, block * -(-need // block))
        rec.update(
            pages_gathered=self.d.max_batch * walked,
            # a free slot's length is 0 (`_release_slot`)
            pages_live=int(((self._lens + ps - 1) // ps).sum()),
            k=k, w=w, t_tokens=t_tokens, decode_rows=decode_rows,
            prefill_rows=prefill_rows, slots=self.d.max_batch,
            program=program, compiles=_compiles_since(rec["t_round"]),
            queue_depth=len(self._queue), tokens_dispatched=disp_toks)
        self._sched_events.append(rec)
        if self.trace is not None:
            pred = serial = None
            if priced:
                pred = restore_s + self._price_horizon(
                    k, w, prefill_rows, decode_rows=decode_rows)
                serial = restore_s + self._price_horizon(
                    k, w, prefill_rows, decode_rows=decode_rows,
                    serial=True)
            self.trace.tick_dispatch(
                "serve", shape, ts=rec["t_round"], ev=rec,
                predicted_s=pred, predicted_serial_s=serial)

    def _horizon_booked(self, rec, step_times, drift=True, **work):
        """The horizon's block is fetched and book-kept: stamp its
        `work` (tokens, tokens_padded; `step()` has stamped
        its own) and close the measured window (round start to here:
        it spans the dispatching round and, on the pipelined loops, the
        next round up to this call). Returns the window's seconds."""
        dt = time.perf_counter() - rec["t_round"]
        rec.update(work)
        if step_times is not None:
            step_times.append(dt)
        if self.trace is not None:
            # a round that compiled stays out of the drift ledger: one
            # compile sample would inflate the rolling mean for hundreds
            # of steady ticks
            self.trace.tick_complete(
                rec, dt, drift=drift and not rec["compiles"],
                pool=self._trace_pool_delta())
        return dt

    # ------------------------------------------------------- tiered KV

    def _flops_per_token(self):
        """Matmul FLOPs one prompt token costs (the 2x-params GPT rule;
        the scheduler already holds it on ragged engines)."""
        if self.scheduler is not None and \
                hasattr(self.scheduler, "flops_per_token"):
            return self.scheduler.flops_per_token
        return 2.0 * self.d.cfg.num_params()

    def _spill_wave(self, need, exclude=()):
        """Reclaim at least `need` parked pages, demoting the wave to
        the host tier with ONE stacked D2H (`PrefixCache.evict` walks
        the victims while their bytes are still mapped; the transfer
        itself is deferred until the walk ends — the freed pages are
        not handed out, let alone written, before this method returns,
        so the batched read still sees the exact write-time bytes).
        A page whose key already has a host twin (it was itself
        restored, or a recompute refreshed the entry) needs NO D2H —
        the host payload is still valid, only the device-twin backref
        clears. Returns the freed page ids."""
        tier = self.tier
        pending = []                     # (key, page): victims to D2H

        def note(key, page):
            if tier is None:
                return
            if key in tier:
                tier.note_unmounted(key)
                self.stats.host_tier_bytes = tier.bytes_used
                return
            if self.d.kv_page_bytes > tier.capacity_bytes:
                # put() would refuse a payload this size anyway — skip
                # the D2H entirely (the capacity-0 tier-off twin must
                # not pay a device sync on every pool-pressure
                # eviction for nothing)
                return
            pending.append((key, page))

        freed = self.cache.evict(need, exclude=exclude, spill=note)
        if pending:
            payloads = self.d.fetch_page_payloads(
                [p for _, p in pending])
            for (key, page), payload in zip(pending, payloads):
                if tier.put(key, payload):
                    self.stats.tier_spills += 1
                    if self.trace is not None:
                        self._trace_event("spill", page=int(page),
                                          bytes=tier.entry_bytes(key))
            self.stats.host_tier_bytes = tier.bytes_used
        return freed

    def _tier_plan(self, keys, n_dev):
        """How far the chain continues onto the HOST tier past the
        device-resident run, and whether to restore it: (n_tier,
        restore, hold). Policy "auto" prices `cost_model.kv_restore_s`
        (PCIe leg) against the span's prefill recompute
        (`kv_tier.restore_beats_recompute` — one formula for engine
        and tests); a losing wire RECOMPUTES and merely refreshes the
        host entries' recency (their bytes stay valid — write-time
        determinism), keeping the hot set warm for a bigger model or
        a longer span. On a restore decision, `hold` pins the span's
        (key, payload, bytes) triples HERE: this same admission's
        evictions may spill NEW entries into the tier and LRU-evict
        the very entries the plan selected — holding the payload
        objects makes the restore immune to that churn (and touches
        their recency, which the about-to-be-hot entries deserve
        anyway)."""
        tier = self.tier
        if tier is None:
            return 0, False, None
        n_tier = 0
        while n_dev + n_tier < len(keys) and \
                keys[n_dev + n_tier] in tier:
            n_tier += 1
        if not n_tier:
            return 0, False, None
        restore = self.tier_policy == "restore"
        if self.tier_policy == "auto":
            from .kv_tier import restore_beats_recompute
            span = keys[n_dev:n_dev + n_tier]
            restore = restore_beats_recompute(
                sum(tier.entry_bytes(k) for k in span),
                n_tier * self.d.page_size, self._flops_per_token(),
                # the cross-process tier (fleet.SharedHostKVTier) pays
                # a host-RAM read leg before the wire — price it
                shared=getattr(tier, "shared", False))
        hold = None
        if restore:
            try:
                hold = [(k, tier.get(k), tier.entry_bytes(k))
                        for k in keys[n_dev:n_dev + n_tier]]
            except KeyError:
                # shared-tier churn: a sibling replica evicted part of
                # the span between the membership walk and the hold —
                # fall back to recompute (bytes stay correct either way)
                return n_tier, False, None
        return n_tier, restore, hold

    def _tier_recompute(self, keys, lo, n):
        """Host blocks keys[lo:lo+n] will be RE-PREFILLED (the wire
        lost the pricing, or a restore span degraded under pool
        pressure): refresh the entries' recency — the recomputed bytes
        equal the spilled ones by write-time determinism, so the
        payload stays valid and the hot set must not age out — and
        count the decision. Called only once the admission COMMITS
        (counting at plan time would inflate tier_recomputes on every
        head-of-line retry)."""
        for i in range(n):
            self.tier.touch(keys[lo + i])
        self.stats.tier_recomputes += n

    def _tier_restore(self, keys, n_dev, pages, hold, rid):
        """Re-mount `len(pages)` host-resident blocks (keys[n_dev:],
        payloads pinned in `hold` at plan time — tier churn between
        plan and restore cannot invalidate them) into freshly
        allocated device pages: ONE batched H2D scatter for the whole
        span (`mount_page_payloads`, dispatched async — jax's pool
        threading orders every later horizon after the writes; a
        per-page mount paid one dispatch per block), then cache insert
        under the held parent chain and the device-twin backref for
        the ledger audit. Returns [(page, inserted)] — a
        capacity-refused insert leaves that page (and the rest of the
        chain, publish-stop rule) private to the request: bytes still
        correct, just not shareable. The priced H2D is handed to the
        horizon pricing (`note_restore`) and, with tracing on,
        recorded as an ("h2d_restore",) tick whose
        predicted-vs-measured — now the price of the batched transfer
        — feeds the drift ledger."""
        tier = self.tier
        tot_bytes = sum(nbytes for _, _, nbytes in hold[:len(pages)])
        t0 = time.perf_counter()
        self.d.mount_page_payloads(
            list(pages), [hold[i][1] for i in range(len(pages))])
        out = []
        stop = False
        for i, pid in enumerate(pages):
            key = hold[i][0]
            ok = False
            if not stop:
                parent = keys[n_dev + i - 1] if (n_dev + i) else None
                ok = self.cache.insert(key, pid, parent=parent)
                if ok:
                    tier.note_mounted(key, pid)
                else:
                    stop = True
            out.append((pid, ok))
        dt = time.perf_counter() - t0
        from ..cost_model import kv_restore_s
        pred = kv_restore_s(tot_bytes,
                            shared=getattr(tier, "shared", False))
        self.stats.tier_restores += len(pages)
        self.stats.host_tier_bytes = tier.bytes_used
        self._note_restore(pred)
        if self.trace is not None:
            self.trace.tick(
                "serve", ("h2d_restore",), dt, predicted_s=pred,
                drift=self._restore_warm,
                seq=self._seq, rid=rid, blocks=len(pages),
                bytes=tot_bytes)
            # the first restore compiled its mount program inside dt
            self._restore_warm = True
        return out

    def _note_restore(self, seconds):
        if self.scheduler is not None and \
                hasattr(self.scheduler, "note_restore"):
            self.scheduler.note_restore(seconds)
        else:
            self._restore_s_pending += float(seconds)

    def _take_restore_s(self):
        """Pending restore H2D price, drained once per dispatched
        horizon (the mount lands inside exactly one measured window —
        the next dispatch's — so its price belongs to that window's
        prediction)."""
        if self.scheduler is not None and \
                hasattr(self.scheduler, "take_restore_s"):
            return self.scheduler.take_restore_s()
        s, self._restore_s_pending = self._restore_s_pending, 0.0
        return s

    def submit(self, prompt_ids, adapter=None):
        """Queue one prompt; returns its request id. `adapter` selects
        a LoRA variant by id (1..n over an attached bank,
        `PagedGPTDecoder.attach_adapters`; 0/None = base weights) —
        requests of DIFFERENT adapters batch into the same ragged
        horizons, resolved per token on device."""
        ids = [int(t) for t in np.asarray(
            prompt_ids._value if isinstance(prompt_ids, Tensor)
            else prompt_ids).reshape(-1)]
        if not ids:
            raise ValueError(
                "prompt must contain at least one token (prefill "
                "samples the first generated token after the prompt's "
                "last position — an empty prompt has none)")
        aid = self._check_adapter(adapter)
        total = len(ids) + self.max_new
        need = self._pages_for(total)
        if need > min(self.d.max_pages, self.d.num_pages - 1):
            raise ValueError(
                f"request needs {need} pages (prompt {len(ids)} + "
                f"max_new {self.max_new} tokens) but the pool allows "
                f"{min(self.d.max_pages, self.d.num_pages - 1)}")
        if total > self.d.cfg.max_seq_len:
            raise ValueError(
                f"prompt {len(ids)} + max_new {self.max_new} tokens "
                f"exceeds the model's max_seq_len "
                f"{self.d.cfg.max_seq_len} (positions past it have no "
                "embedding)")
        return self._register_request(ids, adapter=aid)

    def _check_adapter(self, adapter):
        aid = int(adapter or 0)
        if aid and self.d.lora is None:
            raise ValueError(
                f"adapter {aid} requested but the decoder carries no "
                "LoRA bank — attach one with "
                "PagedGPTDecoder.attach_adapters")
        if aid < 0 or aid > self.d.n_adapters:
            raise ValueError(
                f"adapter id {aid} out of range: the attached bank "
                f"serves ids 0 (base) .. {self.d.n_adapters}")
        return aid

    def _register_request(self, ids, adapter=0, trace_fields=None):
        """Queue a VALIDATED request: rid allocation, queue-wait stamp,
        stats — one implementation for both engines' submit()s, and
        called only after validation so a rejected submission can't
        skew stats.requests or leak a _submit_t entry. `trace_fields`
        ride into the trace's submit record (the tenancy engine stamps
        tenant/slo there — the chrome exporter groups spans by it)."""
        rid = self._next_id
        self._next_id += 1
        self._submit_t[rid] = time.perf_counter()
        self.stats.requests += 1
        if adapter:
            self._rid_adapter[rid] = adapter
        self._queue.append((rid, ids))
        if self.trace is not None:
            self._trace_event("submit", ts=self._submit_t[rid], rid=rid,
                              prompt_tokens=len(ids),
                              **(trace_fields or {}))
        return rid

    def _request_max_new(self, rid):
        """Tokens this request may still emit, for admission-time page
        budgeting. A FRESH request may emit max_new; a resumed
        (previously preempted) one already banked len(outputs) of
        them, so its resume prompt (original + generated prefix) plus
        the remainder needs exactly the original page total."""
        return self.max_new - len(self._outputs.get(rid, ()))

    def _pages_for(self, n_tokens):
        return (n_tokens + self.d.page_size - 1) // self.d.page_size

    def _note_queue_wait(self, rid, dt):
        """Queue-wait stamp hook (submit -> admit); the tenancy engine
        additionally banks it per tenant."""
        self.stats.queue_wait_s.append(dt)

    def _note_ttft(self, rid, dt):
        """TTFT stamp hook (submit -> first token); the tenancy engine
        additionally banks it per tenant."""
        self.stats.ttft_s.append(dt)

    def _note_resident(self):
        """Update stats.max_resident_slots from the ONE definition of
        resident — slots currently holding a request (`_slot_req`) —
        so the peak is comparable across the per-tick, fused and
        ragged loops (and any dispatch site added later)."""
        n = sum(r is not None for r in self._slot_req)
        self.stats.max_resident_slots = max(
            self.stats.max_resident_slots, n)

    def _admit(self):
        # gather every admittable request first: same-suffix-bucket
        # prompts then prefill as ONE batched forward (iteration-level
        # batching applies to prefill too, not just decode). Pages freed
        # by EOS-at-prefill become available from the NEXT step's pass.
        # Returns the slots that entered decode (the multi-step run loop
        # merges exactly those into its device carry).
        active0 = sum(r is not None for r in self._slot_req)
        admitted = self._gather_admissions()
        if not admitted:
            return []
        self._note_admitted(admitted, time.perf_counter())
        self._table_cache = None
        firsts = self._prefill_admitted(admitted)
        self.stats.prefill_syncs += 1
        # the stall the ragged path exists to kill: this prefill
        # dispatch BLOCKED the host while `active0` slots sat decoding
        # (SERVE-PREFILL-STALL audits the trace)
        self._sched_events.append(
            {"kind": "prefill_sync", "decode_active": int(active0),
             "rows": len(admitted)})
        if active0:
            self.stats.prefill_stall_syncs += 1
        self._extra_prefill(admitted)
        done_t = time.perf_counter()
        live = []
        for (slot, rid, ids, pages), first in zip(admitted, firsts):
            # TTFT = submit -> FIRST TOKEN (the token exists right
            # here, so the prefill-sync timestamp is exactly it; the
            # ragged path stamps the same milestone at block
            # processing, so chunked and legacy engines report
            # comparable numbers)
            t0 = self._submit_t.pop(rid, None)
            if t0 is not None:
                self._note_ttft(rid, done_t - t0)
            self._outputs[rid] = [first]
            if self.trace is not None:
                self._trace_event("first_token", ts=done_t, rid=rid)
            self.stats.tokens += 1
            if (self.eos is not None and first == self.eos) \
                    or self.max_new <= 1:
                # finished at prefill: never occupy a decode slot
                self._retire(slot)
                continue
            self._lens[slot] = len(ids)
            self._tokens[slot] = first
            self._kids[slot] = rid
            self._after_admit(slot, len(ids))
            live.append(slot)
        return live

    def _prefill_admitted(self, admitted):
        """Dispatch the admitted requests' prefills: the flash-attention
        full prefill without a prefix cache; the CHUNKED suffix path
        with one (the cached span is mounted host-side — zero device
        work — and only positions start..L-1 compute). Freshly computed
        full blocks are published to the cache afterwards."""
        if self.cache is None:
            return self.d.prefill_suffix_batch(
                [(ids, 0, pages) for _, _, ids, pages in admitted],
                kids=[rid for _, rid, _, _ in admitted],
                aids=[self._rid_adapter.get(rid, 0)
                      for _, rid, _, _ in admitted])
        reqs = []
        for _, rid, ids, pages in admitted:
            start = self._cache_meta[rid][0]
            reqs.append((ids[start:], start, pages))
        firsts = self.d.prefill_suffix_batch(
            reqs, kids=[rid for _, rid, _, _ in admitted],
            aids=[self._rid_adapter.get(rid, 0)
                  for _, rid, _, _ in admitted])
        for slot, rid, ids, pages in admitted:
            self._publish_blocks(rid, slot)
        return firsts

    def _publish_blocks(self, rid, slot):
        """Publish a request's freshly computed full blocks to the
        prefix cache: content-addressable from now on (the cache takes
        one reference-managed view; the slot keeps holding the page
        until retirement decrefs it). Called once the blocks' bytes
        are KNOWN-ordered before any future reader — at prefill-sync
        time on the blocking path, at first-token block processing on
        the ragged path (every later mount dispatches after the
        horizon that wrote the pages). A same-batch duplicate whose
        insert is refused keeps its copy private — two requests never
        alias a page they both wrote — and publishing STOPS at the
        first refusal: a deeper block would chain under a parent this
        request neither mounted nor inserted, breaking the
        every-ancestor-referenced invariant the eviction cascade
        relies on (a parked parent could then cascade into a
        still-referenced child)."""
        if self.cache is None:
            return
        meta = self._cache_meta.pop(rid, None)
        if meta is None:
            return
        _start, keys, n_hit = meta
        pages = self._slot_pages[slot]
        for b in range(n_hit, len(keys)):
            parent = keys[b - 1] if b else None
            if not self.cache.insert(keys[b], pages[b], parent=parent):
                break
            self._slot_shared[slot].add(pages[b])

    def _gather_admissions(self):
        if self.cache is not None:
            return self._gather_admissions_cached()
        admitted = []
        blocked = False
        for slot in range(self.d.max_batch):
            if blocked:
                break
            if self._slot_req[slot] is not None or not self._queue:
                continue
            while True:
                rid, ids = self._queue[0]
                need = self._pages_for(len(ids) +
                                       self._request_max_new(rid))
                if need > self.d.max_pages:
                    blocked = True           # permanently oversized head
                    break
                if need > len(self._free):
                    if self._admission_blocked(rid, need):
                        blocked = True       # head-of-line: wait
                        break
                    # tenancy made room (a victim's pages freed):
                    # replan THIS slot — advancing would strand the
                    # latency head un-admitted for a whole horizon
                    # after its victim was already interrupted
                    continue
                self._queue.pop(0)
                pages = [self._free.pop() for _ in range(need)]
                self._occupy(slot, rid)
                self._slot_pages[slot] = pages
                admitted.append((slot, rid, ids, pages))
                break
        return admitted

    def _occupy(self, slot, rid):
        """Bind `rid` to `slot` (both admission paths): the request id,
        its adapter id for the dispatch-side aids row, and the slot
        generation stamp the stale-block check compares."""
        self._slot_req[slot] = rid
        self._aids[slot] = self._rid_adapter.get(rid, 0)
        self._slot_gen[slot] += 1

    def _admission_blocked(self, rid, need):
        """The queue head can't get its pages: True = wait (the base
        head-of-line discipline). The tenancy engine overrides this
        with preemption by page-spill — parking a throughput victim's
        KV in the prefix cache frees/parks enough pages that the
        admission can replan (return False)."""
        return True

    def _gather_admissions_cached(self):
        """Prefix-cache admission: hash the prompt's full blocks, mount
        the longest cached run into the page-table row (incref), evict
        parked refcount-0 pages if the free list can't cover the
        uncached remainder, and record (start, keys, n_hit) for the
        chunked prefill. A FULL-prompt hit still needs the last
        position's logits: its one re-consumed token would write into
        the final mounted page, so that page is copy-on-write'd to a
        private copy first (the recomputed KV bytes are identical — the
        chunked prefill is deterministic and position-local — so the
        copy diverges only once decode appends past the prompt).

        With a host tier, the chain may CONTINUE past the device run
        onto host-resident entries (`_tier_plan`): a priced winner
        RESTORES them into freshly allocated device pages (counted in
        need_new — a restored block costs a device page exactly like a
        computed one; the admission head-of-line check therefore
        accounts in-flight restores) and those blocks join the hit
        span; a priced loser recomputes them as ordinary misses. Pool
        eviction during either path spills through `_spill_wave` (one
        stacked D2H per wave), so pressure demotes instead of
        destroys."""
        admitted = []
        blocked = False
        ps = self.d.page_size
        tok_bytes = self.d.kv_page_bytes // ps
        for slot in range(self.d.max_batch):
            if blocked:
                break
            if self._slot_req[slot] is not None or not self._queue:
                continue
            while True:
                rid, ids = self._queue[0]
                L = len(ids)
                total = self._pages_for(L + self._request_max_new(rid))
                if total > self.d.max_pages:
                    blocked = True       # permanently oversized head
                    break
                keys = self.cache.block_keys(
                    ids, extra_salt=self.d.adapter_salt(
                        self._rid_adapter.get(rid, 0)))
                hits = self.cache.match(keys)
                n_dev = len(hits)
                n_tier, do_restore, hold = self._tier_plan(keys, n_dev)
                span = n_dev + (n_tier if do_restore else 0)
                # pick the largest mounted span the pool can cover: mounted
                # hit pages are excluded from eviction, so on a tight pool
                # a full-span mount can be self-blocking (the parked hit
                # pages ARE the reclaimable ones — e.g. a full-prompt hit
                # whose CoW page cannot be allocated). Degrading the span
                # turns the excess hits back into evictable parked pages,
                # so any request the cache-less engine could admit
                # eventually admits here too (n_hit=0 needs exactly the
                # cache-less page count). Restored blocks degrade FIRST
                # (deepest-span-off): they are the ones that COST free
                # pages.
                chosen = None
                for n_hit in range(span, -1, -1):
                    start = n_hit * ps
                    # full hit: re-consume the last token (n_hit > 0 guard:
                    # an EMPTY prompt trivially satisfies start >= L with
                    # nothing mounted — it prefills like any other miss)
                    cow = n_hit > 0 and start >= L
                    if cow:
                        start = L - 1
                    n_rest = max(0, n_hit - n_dev)
                    need_new = total - n_hit + (1 if cow else 0) + n_rest
                    if need_new <= len(self._free) + self.cache.evictable(
                            exclude=keys[:n_hit]):
                        chosen = (n_hit, start, cow, need_new, n_rest)
                        break
                if chosen is None:
                    if self._admission_blocked(rid, total):
                        blocked = True   # head-of-line: wait for pages
                        break
                    # tenancy made room (a victim's pages parked/
                    # freed): replan THIS slot — the cache contents
                    # changed, so keys re-match from scratch
                    continue
                n_hit, start, cow, need_new, n_rest = chosen
                hits = hits[:n_hit - n_rest]
                self._queue.pop(0)
                if n_tier:
                    # recompute-decided host blocks — plus any restore
                    # span DEGRADED away by the head-of-line loop — are
                    # re-prefilled: count + recency-refresh them (only now
                    # that the admission commits)
                    lo = max(n_hit, n_dev)
                    n_recomp = n_dev + n_tier - lo
                    if n_recomp:
                        self._tier_recompute(keys, lo, n_recomp)
                self.cache.mount(keys[:len(hits)])
                if len(self._free) < need_new:
                    freed = self._spill_wave(need_new - len(self._free))
                    self.stats.prefix_evictions += len(freed)
                    self._free.extend(freed)
                privates = [self._free.pop() for _ in range(need_new)]
                keys_meta = keys
                inserted = {}
                if n_rest:
                    rest_pages = [privates.pop() for _ in range(n_rest)]
                    inserted = dict(self._tier_restore(
                        keys, len(hits), rest_pages, hold, rid))
                    if not all(inserted.values()):
                        # a capacity-refused restore insert breaks the held
                        # chain: publishing deeper blocks would chain under
                        # an unheld parent (the eviction-cascade invariant)
                        # — stop publishing for this request entirely
                        keys_meta = keys[:len(hits)]
                    hits = hits + rest_pages
                shared = list(hits)
                shared_set = set(shared[:n_hit - n_rest]) | \
                    {p for p, ok in inserted.items() if ok}
                if cow:
                    last = shared[-1]
                    if last in shared_set:
                        dst = privates.pop()
                        self.d.copy_page(last, dst)
                        self.cache.release_page(last)
                        self.stats.prefix_cow += 1
                        shared_set.discard(last)
                        shared[-1] = dst
                    else:
                        # the final block is a restore whose cache insert
                        # was refused: the page is ALREADY private — no
                        # copy needed, return the spare CoW page
                        self._free.append(privates.pop())
                pages = shared + privates    # block order: prefix first
                self._occupy(slot, rid)
                self._slot_pages[slot] = pages
                self._slot_shared[slot] = shared_set
                self._cache_meta[rid] = (start, keys_meta, n_hit)
                self.stats.prefix_hits += n_hit
                self.stats.prefix_misses += len(keys) - n_hit
                self.stats.prefix_tokens_saved += start
                self.stats.prefix_bytes_saved += start * tok_bytes
                admitted.append((slot, rid, ids, pages))
                break
        return admitted

    def _extra_prefill(self, admitted):
        pass                                 # SpeculativeEngine: draft

    def _after_admit(self, slot, prompt_len):
        pass                                 # SpeculativeEngine: _dlens

    def _retire(self, slot):
        if self.trace is not None:
            rid = self._slot_req[slot]
            self._trace_event(
                "retire", rid=rid,
                tokens=len(self._outputs.get(rid, ())))
        shared = self._slot_shared[slot]
        for pid in self._slot_pages[slot]:
            if pid in shared:
                # drop this request's reference only: the cache still
                # owns the page (parked at refcount 0, reclaimed by
                # eviction alone) — so a shared page is freed exactly
                # once, by whoever finally unmaps it
                self.cache.release_page(pid)
            else:
                self._free.append(pid)
        rid = self._slot_req[slot]
        self._rid_adapter.pop(rid, None)
        self._emit_base.pop(rid, None)
        self._release_slot(slot)
        self.stats.completed += 1

    def _release_slot(self, slot):
        """Clear every per-slot field — retirement AND preemption
        (tenancy) share this one sequence, so a field added for one
        can never go stale under the other (the generation bump, the
        adapter id and the scheduler retire all ride here)."""
        self._slot_shared[slot] = set()
        self._slot_req[slot] = None
        self._slot_pages[slot] = []
        self._slot_gen[slot] += 1
        self._lens[slot] = 0
        self._tokens[slot] = 0
        self._aids[slot] = 0
        self._prompt_len[slot] = 0
        if self.scheduler is not None:
            self.scheduler.retire(slot)
        self._table_cache = None

    def page_ledger(self):
        """Auditable snapshot of page ownership: every allocatable page
        sits in exactly one of {free list, slot-held}, cache refcounts
        equal the number of slots mounting each shared page, and parked
        (refcount-0) cached pages are held by nobody. The
        MEM-PAGE-REFCOUNT lint (`analysis.memory.audit_page_ledger`)
        consumes this — double-frees, leaks and refcount drift all
        surface as findings."""
        return {
            "num_pages": self.d.num_pages,
            "scratch": self.d.num_pages - 1,
            "free": list(self._free),
            "slots": {s: list(p)
                      for s, p in enumerate(self._slot_pages) if p},
            "shared": {s: sorted(sh)
                       for s, sh in enumerate(self._slot_shared) if sh},
            "cache": self.cache.ledger() if self.cache else {},
            # multi-LoRA rows: each occupied slot's adapter id plus its
            # cache-key salt (hex) — the audit's cross-variant aliasing
            # check: a page shared by slots whose salts differ would
            # mean one variant reads another's KV bytes
            "slot_adapters": {
                s: {"adapter": int(self._aids[s]),
                    "salt": self.d.adapter_salt(
                        int(self._aids[s])).hex()}
                for s in range(self.d.max_batch)
                if self._slot_req[s] is not None
            } if self.d.lora is not None else {},
            # host-tier rows (tiered KV): spilled entries by chain key,
            # with the device-twin backref of restored entries — the
            # audit cross-checks a twin against the free list (a key
            # both host-resident-with-a-device-twin and device-free is
            # a dropped unmount)
            "host": self.tier.ledger() if self.tier is not None else {},
        }

    def audit_pages(self):
        """Run the MEM-PAGE-REFCOUNT audit over the live ledger; returns
        the findings (empty = every page owned exactly once). With an
        int8 KV pool the audit additionally cross-checks the scale
        planes: every held page position carrying quantized bytes must
        carry its write-time scale (a CoW/copy path that moved page
        bytes without the scales dequantizes the copy to garbage)."""
        from ..analysis.memory import (audit_kv_scale_planes,
                                       audit_page_ledger)
        findings = audit_page_ledger(self.page_ledger())
        if self.d.kv_quant:
            held = {p for pg in self._slot_pages for p in pg}
            if self.cache is not None:
                held |= set(self.cache.pages())
            findings += audit_kv_scale_planes(self.d, sorted(held))
        return findings

    def _table(self, pages_per_slot, decoder):
        """Page table with inactive/unused entries routed to the reserved
        scratch page (their masked, discarded KV writes must never land
        in allocatable pages)."""
        t = np.full((decoder.max_batch, decoder.max_pages),
                    decoder.num_pages - 1, np.int32)
        for s, pg in enumerate(pages_per_slot):
            if pg:
                t[s, :len(pg)] = pg
        return t

    def _step_admit(self, rec, seq):
        """The admission phase of `step()`: (active slots, whether a
        blocking prefill ran inside it — such a window is no decode
        tick, so its record goes unpriced and stays out of the drift
        ledger and the token percentiles)."""
        before_p = self.stats.prefill_syncs
        with _Phase("engine.admit", rec, "admit_s", seq=seq,
                    n=len(self._queue)):
            self._admit()
        active = [s for s in range(self.d.max_batch)
                  if self._slot_req[s] is not None]
        return active, self.stats.prefill_syncs != before_p

    def step(self):
        """Admit + one decode tick. Returns number of active slots.
        Inside `run()`'s per-tick loop the tick is that round's
        horizon and fills its record (`self._open`); called on its own
        it keeps no record and its spans carry the last round's `seq`."""
        rec, seq = self._open, self._seq
        active, prefilled = self._step_admit(rec, seq)
        if not active:
            return 0
        S = self.d.max_batch
        with _Phase("engine.plan", rec, "plan_s", seq=seq):
            if self._table_cache is None:    # slots changed since last tick
                self._table_cache = self._table(self._slot_pages, self.d)
        program = self.d.program_name("tick", 1, 1, self.d.max_pages)
        with _Phase("engine.dispatch", rec, "dispatch_s", seq=seq,
                    program=program):
            nxt = self.d.decode(self._tokens, self._lens,
                                self._table_cache, kids=self._kids,
                                aids=self._aids)
        if rec is not None:
            self._horizon_dispatched(
                rec, ("tick", 1, 1), program, k=1, w=1, t_tokens=None,
                decode_rows=len(active), prefill_rows=0, disp_toks=S,
                width=self.d.max_pages, need=self._need(1),
                priced=not prefilled)
        with _Phase("engine.fetch", rec, "fetch_wait_s", seq=seq,
                    horizon=seq):
            nxt = np.asarray(nxt)
        if rec is not None:
            rec["t_fetched"] = time.perf_counter()
        with _Phase("engine.bookkeep", rec, "book_s", seq=seq,
                    horizon=seq):
            self.steps += 1
            self.stats.ticks += 1
            self.stats.decode_syncs += 1
            # pad ledger: the tick computed every batch row (one
            # position each); only the active rows' positions were
            # real work
            self.stats.tokens_dispatched += S
            self.stats.tokens_padded += S - len(active)
            self.stats.occupancy.append(len(active) / S)
            self._note_resident()
            for s in active:
                rid = self._slot_req[s]
                tok = int(nxt[s])
                self._outputs[rid].append(tok)
                self.stats.tokens += 1
                if self.trace is not None:
                    self._trace_progress(rid)
                self._lens[s] += 1
                self._tokens[s] = tok
                done = (self.eos is not None and tok == self.eos) or \
                    len(self._outputs[rid]) >= self.max_new
                if done:
                    self._retire(s)
            if rec is not None:
                rec.update(tokens=len(active),
                           tokens_padded=S - len(active))
        return len(active)

    def run(self, step_times=None, on_sync=None):
        """Drain the queue; returns {request_id: generated token list}.
        `step_times`, if given, receives wall seconds per host sync —
        per decode tick on the per-tick path (k_max=1), per K-tick
        horizon on the multi-step paths (use `self.stats` for
        per-token percentiles either way). `on_sync(engine)`, if
        given, is called after every processed host sync — outputs are
        current at that point, and the callback may `submit()` new
        requests (the long-prompt-arrives-mid-stream bench drives
        arrival timing with it). The multi-step default is the RAGGED
        loop (prompt chunks ride the decode horizon, no host-blocking
        prefill); `ragged=False` keeps the dispatch-separate
        baseline."""
        try:
            if self.ragged:
                # an EXPLICIT ragged=True is honored even at k_max=1
                # (the horizons are just one tick long): the user asked
                # for no-stall admission, silently downgrading to the
                # blocking-prefill per-tick loop would betray that
                return self._run_ragged(step_times, on_sync)
            if self.k_max <= 1:
                return self._run_per_tick(step_times, on_sync)
            return self._run_multi(step_times, on_sync)
        finally:
            self._open = None    # no round is open outside a run loop

    def serve_schedule(self):
        """The recent scheduling-decision trace (bounded window): one
        event per host-blocking prefill dispatch ("prefill_sync", with
        the decode slots it stalled) and per dispatched horizon
        ("horizon": the horizon's one record, `_begin_round` — its k/w
        and row mix, its program, phase times and emitted work; the
        very dicts, so a horizon still in flight fills in later). The
        SERVE-PREFILL-STALL rule (`analysis.analyzers
        .PrefillStallAnalyzer`) audits this — a prefill_sync with
        decode_active > 0 is the stall the ragged path exists to
        kill."""
        return list(self._sched_events)

    def _run_per_tick(self, step_times=None, on_sync=None):
        """Legacy loop: one compiled tick, one host sync per token. A
        round is `step()`: it fills the round's record itself."""
        while self._queue or any(r is not None for r in self._slot_req):
            rec = self._begin_round()
            with span("engine.round", seq=rec["seq"]):
                before_p = self.stats.prefill_syncs
                self.step()
                if "k" in rec:                   # the round ran a tick
                    # a step that contained a blocking prefill is not
                    # a decode tick: its record is unpriced and the
                    # drift ledger stays a tick-roofline comparison
                    # (same exclusion as token_time_s below)
                    clean = self.stats.prefill_syncs == before_p
                    dt = self._horizon_booked(rec, step_times, drift=clean)
                    # token_time_s is the STEADY-STATE decode latency:
                    # a sync that contained a prefill is dominated by
                    # it (orders of magnitude more work than a tick)
                    # and would turn p99 into a prefill number — keep
                    # it out of the percentiles
                    n = rec["tokens"]
                    if n and clean:
                        self.stats.token_time_s.extend([dt / n] * n)
                else:
                    # tiered-KV: drain any restore price — on this
                    # blocking path a restore always rides a prefill-
                    # polluted window, which the ledger excludes anyway
                    self._take_restore_s()
                    if step_times is not None:
                        step_times.append(
                            time.perf_counter() - rec["t_round"])
                if on_sync is not None:
                    with _Phase("engine.on_sync", rec, "on_sync_s",
                                seq=rec["seq"], horizon=rec["seq"]):
                        on_sync(self)
        return dict(self._outputs)

    def _budget_left(self, slot):
        """Tokens this slot may still emit (host view, excludes ticks
        already dispatched but not yet processed)."""
        return self.max_new - len(self._outputs[self._slot_req[slot]])

    def _horizon(self, slots, inflight):
        """Largest power-of-two tick count ≤ k_max that fits every
        dispatchable slot's remaining budget (powers of two bound the
        decode_multi compile count, like the prefill buckets)."""
        rem = min(self._budget_left(s) - inflight[s] for s in slots)
        k = 1
        while k * 2 <= min(rem, self.k_max):
            k *= 2
        return k

    def _merge_carry(self, carry, admitted):
        """Device-resident decode state for the next horizon. The carry
        never round-trips through the host: newly admitted slots are
        scattered into the in-flight arrays with device ops."""
        S = self.d.max_batch
        if carry is None:
            done = np.array([r is None for r in self._slot_req])
            rem = np.array([self._budget_left(s) if self._slot_req[s]
                            is not None else 0 for s in range(S)],
                           np.int32)
            return (jnp.asarray(self._tokens), jnp.asarray(self._lens),
                    jnp.asarray(done), jnp.asarray(rem))
        if not admitted:
            return carry
        tokens, lens, done, rem = carry
        idx = jnp.asarray(admitted, jnp.int32)
        tokens = tokens.at[idx].set(jnp.asarray(self._tokens[admitted]))
        lens = lens.at[idx].set(jnp.asarray(self._lens[admitted]))
        done = done.at[idx].set(False)
        rem = rem.at[idx].set(jnp.asarray(
            [self._budget_left(s) for s in admitted], jnp.int32))
        return tokens, lens, done, rem

    def _process_block(self, meta, inflight, step_times, seq,
                       prefilled_since=False, polluted=False):
        """Fetch + bookkeep one finished horizon. Called AFTER the next
        horizon is dispatched, so the device→host wait overlaps it.
        `seq` is the round this runs in (its spans nest under that
        round's); what it measures goes into the FETCHED horizon's
        record. `polluted`: the next round compiled something inside
        this horizon's still-open window."""
        block_d, done_before_d, k, rids, had_prefill, rec = meta
        ids = {"seq": seq, "horizon": rec["seq"]}
        with _Phase("engine.fetch", rec, "fetch_wait_s", **ids):
            block = np.asarray(block_d)
            done_before = np.asarray(done_before_d)
        rec["t_fetched"] = time.perf_counter()
        with _Phase("engine.bookkeep", rec, "book_s", **ids):
            self.stats.decode_syncs += 1
            # pad ledger: the fused loop computed k*S positions; frozen
            # rows' ticks (done_before True) were filler — the device
            # mask is the one exact source (EOS freezes mid-horizon)
            pad_toks = int(done_before.sum())
            self.stats.tokens_dispatched += rec["tokens_dispatched"]
            self.stats.tokens_padded += pad_toks
            n_emitted = 0
            for s, rid in rids.items():
                inflight[s] = max(0, inflight[s] - k)
                if self._slot_req[s] != rid:
                    continue
                for j in range(k):
                    if done_before[j, s]:
                        break
                    tok = int(block[j, s])
                    self._outputs[rid].append(tok)
                    self.stats.tokens += 1
                    n_emitted += 1
                    if self.trace is not None:
                        self._trace_progress(rid)
                    self._lens[s] += 1
                    self._tokens[s] = tok
                    if (self.eos is not None and tok == self.eos) or \
                            len(self._outputs[rid]) >= self.max_new:
                        self._retire(s)
                        break
            # a window containing a prefill, the program's first
            # (compiling) dispatch, or another program's compile
            # landing inside this still-open window, is excluded from
            # the drift ledger (same pollution rule as the token
            # percentiles)
            dt = self._horizon_booked(
                rec, step_times,
                drift=not (had_prefill or prefilled_since or polluted),
                tokens=n_emitted, tokens_padded=pad_toks)
            # steady-state decode latency only: the block's dt window
            # spans its dispatch iteration AND the next iteration up to
            # this call, so a prefill in either (had_prefill at
            # dispatch, prefilled_since at processing) would make p99
            # a prefill number — exclude such blocks from the
            # percentiles (see _run_per_tick)
            if n_emitted and not had_prefill and not prefilled_since:
                self.stats.token_time_s.extend(
                    [dt / n_emitted] * n_emitted)

    def _run_multi(self, step_times=None, on_sync=None):
        """Horizon-scheduled drain: dispatch a K-tick device-resident
        block, then process the PREVIOUS block while the new one runs.
        Retirement is one horizon delayed — a slot that finishes inside
        block N stays frozen on device through block N+1 (done mask
        carried on device; its K/V writes route to the scratch page)
        and its pages are freed exactly once, when block N's results
        land on the host. Prefix-cache interplay inherits the same
        discipline: a retiring slot's shared pages are DECREF'd at
        block-processing time (parked, not reused), and eviction
        reclaims them only at a later admission — whose prefill writes
        are device-ordered after every in-flight horizon, so a fused
        horizon can never read a page that was re-written under it."""
        S = self.d.max_batch
        pending = None               # the in-flight horizon's meta
        carry = None                 # device (tokens, lens, done, rem)
        inflight = [0] * S           # dispatched-not-yet-processed ticks
        while (self._queue or pending is not None
               or any(r is not None for r in self._slot_req)):
            rec = self._begin_round()
            seq = rec["seq"]
            with span("engine.round", seq=seq):
                with _Phase("engine.admit", rec, "admit_s", seq=seq,
                            n=len(self._queue)):
                    before_p = self.stats.prefill_syncs
                    admitted = self._admit()
                    # a prefill ran iff the sync counter moved — NOT
                    # iff any request entered decode: a round whose
                    # every admission finishes AT prefill (EOS on the
                    # first token) returns an empty `admitted` but
                    # still paid a prefill forward, which must stay out
                    # of the steady-state token percentiles (same delta
                    # discipline as _run_per_tick)
                    prefilled = self.stats.prefill_syncs != before_p
                    for s in admitted:
                        # a freshly admitted slot starts from a clean
                        # device carry (_merge_carry), so ticks still
                        # in flight for the slot's PREVIOUS request
                        # must not gate its dispatch. Unreachable today
                        # (a fresh budget max_new-1 always exceeds the
                        # stale count, which is bounded by the retired
                        # request's remaining budget minus the
                        # processed block), but reset defensively: the
                        # rid check skips the old block's tokens and
                        # the max(0, ...) clamp absorbs the double
                        # subtraction.
                        inflight[s] = 0
                    carry = self._merge_carry(carry, admitted)
                with _Phase("engine.plan", rec, "plan_s", seq=seq):
                    # invariant: for a live non-admitted slot, the
                    # device-side `remaining` equals budget_left -
                    # inflight exactly (both count init budget minus
                    # dispatched ticks), so a slot excluded here is
                    # always already frozen on device — its ticks in
                    # another slot's block are filler, never lost
                    # tokens
                    disp = [s for s in range(S)
                            if self._slot_req[s] is not None
                            and self._budget_left(s) - inflight[s] > 0]
                    if disp:
                        k = self._horizon(disp, inflight)
                        if self._table_cache is None:
                            self._table_cache = self._table(
                                self._slot_pages, self.d)
                meta = None
                if disp:
                    tokens_d, lens_d, done_d, rem_d = carry
                    shape = ("decode", k, 1)
                    program = self.d.program_name(*shape, self.d.max_pages)
                    with _Phase("engine.dispatch", rec, "dispatch_s",
                                seq=seq, program=program):
                        out = self.d.decode_multi(
                            tokens_d, lens_d, self._table_cache, k,
                            kids=self._kids, done=done_d,
                            remaining=rem_d, eos=self.eos,
                            aids=self._aids)
                    carry = (out.tokens, out.lens, out.done,
                             out.remaining)
                    self.steps += k
                    self.stats.ticks += k
                    self.stats.occupancy.append(len(disp) / S)
                    self._note_resident()
                    for s in disp:
                        inflight[s] += k
                    self._horizon_dispatched(
                        rec, shape, program, k=k, w=1, t_tokens=None,
                        decode_rows=len(disp), prefill_rows=0,
                        disp_toks=k * S, width=self.d.max_pages,
                        need=self._need(0, inflight))
                    meta = (out.tokens_block, out.done_before, k,
                            {s: self._slot_req[s] for s in disp},
                            prefilled, rec)
                if pending is not None:
                    # a compile in THIS round lies inside the PENDING
                    # horizon's still-open measured window (processing
                    # closes after the next dispatch)
                    self._process_block(
                        pending, inflight, step_times, seq,
                        prefilled_since=prefilled,
                        polluted=meta is not None and rec["compiles"] > 0)
                    if on_sync is not None:
                        prev = pending[-1]
                        with _Phase("engine.on_sync", prev, "on_sync_s",
                                    seq=seq, horizon=prev["seq"]):
                            on_sync(self)
                pending = meta
        return dict(self._outputs)

    # -- ragged scheduling (chunked prefill INSIDE the decode horizon) --

    def _admit_ragged(self):
        """Admission without a prefill dispatch: mount the prefix-cache
        span (zero device work), allocate pages, hand the uncached
        suffix to the SCHEDULER — the suffix streams into the horizon
        w tokens per tick from the device-resident pend carry. Returns
        [(slot, rid, suffix), ...] for the carry merge."""
        admitted = self._gather_admissions()
        if not admitted:
            return []
        self._note_admitted(admitted, time.perf_counter())
        self._table_cache = None
        plans = []
        for slot, rid, ids, pages in admitted:
            start = self._cache_meta[rid][0] if self.cache is not None \
                else 0
            suffix = ids[start:]
            # setdefault: a RESUMED request (tenancy preemption) keeps
            # its generated prefix — the continuation appends to it
            self._outputs.setdefault(rid, [])
            self._lens[slot] = start
            self._tokens[slot] = 0
            self._kids[slot] = rid
            self._prompt_len[slot] = len(ids)
            self._after_admit(slot, len(ids))
            self.scheduler.admit(slot, len(suffix))
            self.stats.prefill_chunk_tokens += len(suffix)
            plans.append((slot, rid, suffix))
        return plans

    def _first_token(self, rid, slot):
        """A request's FIRST token just landed on the host: stamp TTFT
        (submit -> first token — comparable across the legacy and
        chunked paths, however many horizon boundaries the prefill
        spanned) and publish its freshly computed cache blocks (their
        writes are device-ordered before any future mount's reads)."""
        t0 = self._submit_t.pop(rid, None)
        if t0 is not None:
            self._note_ttft(rid, time.perf_counter() - t0)
        if self.trace is not None:
            self._trace_event("first_token", rid=rid)
        self._publish_blocks(rid, slot)
        # prompt fully consumed; the emitted token is not consumed yet
        self._lens[slot] = self._prompt_len[slot]

    def _merge_carry_ragged(self, carry, plans):
        """Device-resident mixed-horizon state: (tokens, lens, done,
        remaining, pend, pend_n). Newly admitted slots scatter their
        suffix into the pend buffer with device ops — the carry never
        round-trips through the host."""
        S = self.d.max_batch
        P = self.d.pend_capacity
        if carry is None:
            done = np.array([r is None for r in self._slot_req])
            rem = np.array([self._budget_left(s) if self._slot_req[s]
                            is not None else 0 for s in range(S)],
                           np.int32)
            pend = np.zeros((S, P), np.int32)
            pend_n = np.zeros(S, np.int32)
            for slot, _rid, suffix in plans:
                pend[slot, :len(suffix)] = suffix
                pend_n[slot] = len(suffix)
            return (jnp.asarray(self._tokens), jnp.asarray(self._lens),
                    jnp.asarray(done), jnp.asarray(rem),
                    jnp.asarray(pend), jnp.asarray(pend_n))
        if not plans:
            return carry
        tokens, lens, done, rem, pend, pend_n = carry
        idx = jnp.asarray([s for s, _, _ in plans], jnp.int32)
        rows = np.zeros((len(plans), P), np.int32)
        ns = np.zeros(len(plans), np.int32)
        for r, (slot, _rid, suffix) in enumerate(plans):
            rows[r, :len(suffix)] = suffix
            ns[r] = len(suffix)
        slots = [s for s, _, _ in plans]
        tokens = tokens.at[idx].set(jnp.asarray(self._tokens[slots]))
        lens = lens.at[idx].set(jnp.asarray(self._lens[slots]))
        done = done.at[idx].set(False)
        rem = rem.at[idx].set(jnp.asarray(
            [self._budget_left(s) for s in slots], jnp.int32))
        pend = pend.at[idx].set(jnp.asarray(rows))
        pend_n = pend_n.at[idx].set(jnp.asarray(ns))
        return tokens, lens, done, rem, pend, pend_n

    def _process_ragged_block(self, meta, inflight, step_times, seq,
                              polluted=False):
        """Fetch + bookkeep one finished mixed horizon (called AFTER
        the next horizon is dispatched, so the device->host wait
        overlaps it). The per-tick `emitted` mask separates real
        tokens from filler ticks AND from mid-prefill chunk ticks; a
        request's first emitted token triggers TTFT + cache
        publishing. No percentile exclusions here: every sync on this
        path is a decode-path sync by construction — chunk ticks are
        budgeted small enough to ride inside it, and their cost
        SHOULD show in the per-token tail (that honesty is what the
        stall bench measures). `seq` and `polluted` as in
        `_process_block`."""
        block_d, emitted_d, real_d, k, rids, emit_ticks, rec = meta
        ids = {"seq": seq, "horizon": rec["seq"]}
        with _Phase("engine.fetch", rec, "fetch_wait_s", **ids):
            block = np.asarray(block_d)
            emitted = np.asarray(emitted_d)
            real = np.asarray(real_d)
        rec["t_fetched"] = time.perf_counter()
        if real.ndim == 2:
            # the decoder's own counters ride the block, a column each
            # beside the real token count (`horizon_counters`)
            rec.update(zip(self.d.horizon_counters,
                           (int(c) for c in real[:, 1:].sum(axis=0))))
            real = real[:, 0]
        with _Phase("engine.bookkeep", rec, "book_s", **ids):
            # pad ledger: dispatched is the horizon's layout cost (k *
            # the packed t_tokens bucket); real is the
            # device's per-tick consumed-position count — exact even
            # when EOS froze a slot mid-horizon
            disp_toks = rec["tokens_dispatched"]
            pad_toks = disp_toks - int(real.sum())
            self.stats.tokens_dispatched += disp_toks
            self.stats.tokens_padded += pad_toks
            self.stats.decode_syncs += 1
            n_emitted = 0
            for s, (rid, gen) in rids.items():
                if self._slot_req[s] != rid or self._slot_gen[s] != gen:
                    # stale block of a retired/re-admitted slot: its
                    # emit ticks were already DISCARDED by the inflight
                    # reset at re-admission — subtracting them again
                    # would understate the new request's in-flight
                    # emissions, and unlike _run_multi's harmless
                    # scheduling slack, here inflight feeds
                    # _table_width's correctness-critical position
                    # bound. The GENERATION stamp matters beyond the
                    # rid: preemption (tenancy) can resume the SAME rid
                    # into the same slot while its pre-preemption block
                    # is still in flight — those tokens are regenerated
                    # post-resume and must not double-append
                    continue
                inflight[s] = max(0, inflight[s] - emit_ticks.get(s, 0))
                for j in range(k):
                    if not emitted[j, s]:
                        continue
                    tok = int(block[j, s])
                    if len(self._outputs[rid]) == \
                            self._emit_base.get(rid, 0):
                        # first token of THIS admission: TTFT (fresh
                        # requests only — a resume's _submit_t is long
                        # popped), cache publishing, the lens jump to
                        # the admitted prompt length
                        self._first_token(rid, s)
                    else:
                        self._lens[s] += 1
                    self._outputs[rid].append(tok)
                    self.stats.tokens += 1
                    n_emitted += 1
                    if self.trace is not None:
                        self._trace_progress(rid)
                    self._tokens[s] = tok
                    if (self.eos is not None and tok == self.eos) or \
                            len(self._outputs[rid]) >= self.max_new:
                        self._retire(s)
                        break
            # a compiling dispatch (this program's first, or another
            # program's compile landing inside this still-open window)
            # stays out of the drift ledger; steady ragged windows ARE
            # the honest tick (chunk cost included by design — see
            # token_time_s above)
            dt = self._horizon_booked(
                rec, step_times, drift=not polluted, tokens=n_emitted,
                tokens_padded=pad_toks)
            if n_emitted:
                self.stats.token_time_s.extend(
                    [dt / n_emitted] * n_emitted)

    def _table_width(self, live, plan, inflight):
        """Page-table columns this horizon can actually touch: the max
        over live slots of the position bound it may read or write,
        bucketed to a power of two (bounded compile count). Trailing
        table entries hold only causally-masked pages — an EXACT
        no-op in the ragged attention's online softmax (masked logits
        underflow to p = 0.0 and never move the running max), so
        slicing them off is bitwise-identical while making early
        chunk ticks of a long prompt pay a SHORT gather instead of
        the pool-capacity one (on TPU the kernel streams one page per
        grid step anyway; on CPU the reference's gather width is the
        mixed tick's dominant cost). Returns (width, need): the
        bucketed width and the page bound it was rounded up from."""
        ps = self.d.page_size
        bound = 1
        for s, rid in live.items():
            if self.scheduler.prefilling(s):
                # suffix_left was already decremented by plan():
                # positions consumed after this horizon, plus k emitted
                # tokens if the prompt finishes inside it
                pos = (self._prompt_len[s]
                       - self.scheduler.suffix_left(s) + plan.k + 1)
            else:
                # NOT host _lens: it lags at the cached start until the
                # first token is PROCESSED, while the device may already
                # sit at prompt_len + in-flight emissions. Outputs are
                # counted from this ADMISSION's base: a resumed
                # request's pre-preemption tokens are already inside
                # _prompt_len (they are the resume prompt's tail) and
                # must not widen the bound twice
                pos = (self._prompt_len[s]
                       + len(self._outputs.get(rid, ()))
                       - self._emit_base.get(rid, 0)
                       + inflight[s] + plan.k + 2)
            bound = max(bound, pos)
        need = min(self.d.max_pages, (bound + ps - 1) // ps + 1)
        width = 1
        while width < need:
            width *= 2
        return min(width, self.d.max_pages), need

    def _need(self, ticks, inflight=None):
        """Table columns that hold every position a horizon of the
        loops with a blocking prefill may reach: their `_lens` is the
        device's, less the ticks in flight (`inflight`, this horizon's
        among them) or those this dispatch makes (`ticks`); one more
        for the zero query a 1-wide window is padded with."""
        deepest = max(int(n) + (inflight[s] if inflight else 0)
                      for s, n in enumerate(self._lens)) + ticks + 1
        return min(self.d.max_pages, deepest // self.d.page_size + 1)

    def _run_ragged(self, step_times=None, on_sync=None):
        """Mixed-horizon drain: every scheduling round admits queued
        prompts STRAIGHT into the device carry (prefix-cache mount +
        page allocation only — no prefill dispatch, no prefill sync),
        then dispatches one `ragged_multi` block of k ticks in which
        decode rows emit a token per tick while prefilling rows
        consume w prompt tokens per tick, and processes the PREVIOUS
        block while the new one runs. One long prompt therefore
        costs every other slot at most ceil(suffix/w) slightly-longer
        ticks instead of one monolithic prefill stall — the
        throughput-under-load lever the ROADMAP names. Retirement
        keeps the one-horizon-delayed discipline of `_run_multi`
        (pages freed exactly once, at block-processing time; shared
        pages decref'd there, reusable only by later admissions whose
        writes are device-ordered after every in-flight horizon)."""
        S = self.d.max_batch
        sched = self.scheduler
        pending = None               # the in-flight horizon's meta
        carry = None                 # (tokens, lens, done, rem, pend, pend_n)
        inflight = [0] * S           # in-flight EMISSION ticks per slot
        while (self._queue or pending is not None
               or any(r is not None for r in self._slot_req)):
            rec = self._begin_round()
            seq = rec["seq"]
            with span("engine.round", seq=seq):
                with _Phase("engine.admit", rec, "admit_s", seq=seq,
                            n=len(self._queue)):
                    plans = self._admit_ragged()
                    for slot, _, _ in plans:
                        # fresh request in a recycled slot: stale
                        # in-flight ticks belong to the PREVIOUS
                        # request (the rid check skips its tokens) and
                        # must not gate this one
                        inflight[slot] = 0
                    carry = self._merge_carry_ragged(carry, plans)
                with _Phase("engine.plan", rec, "plan_s", seq=seq):
                    live = {s: self._slot_req[s] for s in range(S)
                            if self._slot_req[s] is not None}
                    plan = sched.plan(
                        live, {s: self._budget_left(s) for s in live},
                        inflight) if live else None
                    if plan is not None:
                        if self._table_cache is None:
                            self._table_cache = self._table(
                                self._slot_pages, self.d)
                        width, need = self._table_width(
                            live, plan, inflight)
                        t_tokens = plan.t_tokens
                        if t_tokens is None:
                            # a custom scheduler may build HorizonPlan
                            # without t_tokens: fall back to the
                            # dense-equivalent bucket here so the
                            # dispatch and the pad ledger below price
                            # the SAME layout
                            t_tokens = pow2_at_least(S * max(plan.w, 1))
                meta = None
                if plan is not None:
                    tokens_d, lens_d, done_d, rem_d, pend_d, pend_n_d = \
                        carry
                    # the jit key is (k, t, window, table width): a fresh
                    # combination compiles inside this window
                    shape = ("packed", plan.k, t_tokens)
                    program = self.d.program_name(
                        *shape, width, packed_window(plan.w, t_tokens))
                    with _Phase("engine.dispatch", rec, "dispatch_s",
                                seq=seq, program=program):
                        out = self.d.ragged_multi(
                            tokens_d, lens_d,
                            self._table_cache[:, :width], plan.k, plan.w,
                            pend_d, pend_n_d, kids=self._kids,
                            done=done_d, remaining=rem_d, eos=self.eos,
                            t_tokens=t_tokens, aids=self._aids)
                    carry = (out.tokens, out.lens, out.done,
                             out.remaining, out.pend, out.pend_n)
                    self.steps += plan.k
                    self.stats.ticks += plan.k
                    self.stats.prefill_chunks += plan.n_chunks
                    self.stats.occupancy.append(len(live) / S)
                    self._note_resident()
                    for s, e in plan.emit_ticks.items():
                        inflight[s] += e
                    # layout cost of this dispatch: the packed path
                    # pays the total-token bucket per tick
                    self._horizon_dispatched(
                        rec, shape, program, k=plan.k, w=plan.w,
                        t_tokens=t_tokens,
                        decode_rows=len(live) - plan.prefill_rows,
                        prefill_rows=plan.prefill_rows,
                        disp_toks=plan.k * t_tokens, width=width,
                        need=need)
                    meta = (out.tokens_block, out.emitted, out.real,
                            plan.k,
                            {s: (rid, self._slot_gen[s])
                             for s, rid in live.items()},
                            plan.emit_ticks, rec)
                if pending is not None:
                    # see _run_multi: a compile in this round lies in
                    # the pending horizon's window
                    self._process_ragged_block(
                        pending, inflight, step_times, seq,
                        polluted=meta is not None and rec["compiles"] > 0)
                    if on_sync is not None:
                        prev = pending[-1]
                        with _Phase("engine.on_sync", prev, "on_sync_s",
                                    seq=seq, horizon=prev["seq"]):
                            on_sync(self)
                pending = meta
        return dict(self._outputs)


def _refuse_unserved(decoder, asked):
    """Raise for an engine option (`asked`: {option: wanted}) that the
    decoder says it cannot serve (`engine_refusals`: {option: why}). The
    engine knows no decoder by name: it asks."""
    for option, why in decoder.engine_refusals.items():
        if asked.get(option):
            raise NotImplementedError(
                f"{type(decoder).__name__} does not serve {option}: {why}")


class SpeculativeEngine(ContinuousBatchingEngine):
    """Speculative decoding over the paged engine: a small DRAFT model
    proposes k tokens with k cheap decode ticks; the TARGET model scores
    all of them in ONE verify forward. Greedy configs accept the longest
    matching prefix (+ the target's token at the first mismatch) —
    output is EXACTLY the target's greedy decode; sampled configs (same
    temperature/top-k/top-p on both decoders) use rejection-sampling
    acceptance (_spec_accept), so emitted tokens are distributed exactly
    as target-only sampling. Either way: up to k-times fewer target
    forwards. Paged KV makes rollback free: `lens` is the source of
    truth, rejected positions are simply overwritten.

    Acceptance is capped at k-1 drafts so the draft cache (which holds
    proposals d1..d_{k-1}) never falls behind; when all k drafts match,
    the capped path still emits exactly d1..dk.
    """

    def __init__(self, decoder, draft_decoder, eos_token_id=None,
                 max_new_tokens=64, k=4, trace=None):
        for d in (decoder, draft_decoder):
            _refuse_unserved(d, {"speculation": True})
        if decoder.sampling != draft_decoder.sampling:
            raise ValueError(
                "speculative decoding needs the SAME sampling config on "
                "target and draft (acceptance compares their masked "
                f"distributions): {decoder.sampling} vs "
                f"{draft_decoder.sampling}")
        if draft_decoder.max_batch != decoder.max_batch or \
                draft_decoder.page_size != decoder.page_size:
            raise ValueError("draft/target max_batch and page_size must match")
        if decoder.lora is not None or draft_decoder.lora is not None:
            # verify() runs the base weights only — silently serving a
            # LoRA request through it would emit base-model tokens
            raise ValueError(
                "SpeculativeEngine does not support LoRA adapter banks "
                "(attach_adapters): the verify window does not gather "
                "adapters — use ContinuousBatchingEngine/TenantEngine")
        if decoder.kv_quant or draft_decoder.kv_quant:
            # out of scope for quantized pools (docs/serving.md):
            # verify windows write up to k positions past the accepted
            # length, and the twin-pool rollback discipline for
            # quantized bytes+scales — per-token int8 planes and
            # packed-nibble int4 group planes alike — is unproven;
            # refuse rather than risk a silent drift between the pools
            quant = decoder.kv_quant or draft_decoder.kv_quant
            raise ValueError(
                f"SpeculativeEngine does not support quantized KV "
                f"pools (kv_quant={quant!r}; int8 and int4 alike): "
                "use ContinuousBatchingEngine, or plain bf16 pools "
                "for speculation")
        # k_max=1: the verify cadence IS this engine's horizon — each
        # step() already moves a k-token window; the draft's ticks are
        # device-resident via decode_multi below. (No prefix_cache:
        # verify windows WRITE up to k positions past the accepted
        # length, which would dirty mounted shared pages — chunked
        # admission for the twin pools is an open item.)
        super().__init__(decoder, eos_token_id, max_new_tokens, k_max=1,
                         trace=trace)
        self.draft = draft_decoder
        self.k = int(k)
        self._draft_free = list(range(draft_decoder.num_pages - 2, -1, -1))
        self._draft_pages = [[] for _ in range(decoder.max_batch)]
        self._dlens = np.zeros(decoder.max_batch, np.int32)
        self.target_calls = 0

    def submit(self, prompt_ids):
        """Same as the base, with a +k margin: a verify window can write
        up to k positions past the final accepted length."""
        ids = np.asarray(prompt_ids._value if isinstance(prompt_ids, Tensor)
                         else prompt_ids).reshape(-1)
        if len(ids) == 0:
            raise ValueError(
                "prompt must contain at least one token (prefill "
                "samples the first generated token after the prompt's "
                "last position — an empty prompt has none)")
        total = len(ids) + self.max_new + self.k
        need = self._pages_for(total)
        limit = min(self.d.max_pages, self.draft.max_pages,
                    self.d.num_pages - 1, self.draft.num_pages - 1)
        if need > limit:
            raise ValueError(
                f"request needs {need} pages (prompt {len(ids)} + max_new "
                f"{self.max_new} + speculation margin {self.k}) but the "
                f"pools allow {limit}")
        if total > min(self.d.cfg.max_seq_len, self.draft.cfg.max_seq_len):
            raise ValueError(
                f"prompt {len(ids)} + max_new {self.max_new} + margin "
                f"{self.k} exceeds max_seq_len "
                f"{min(self.d.cfg.max_seq_len, self.draft.cfg.max_seq_len)}")
        return self._register_request([int(t) for t in ids])

    def _gather_admissions(self):
        admitted = []
        for slot in range(self.d.max_batch):
            if self._slot_req[slot] is not None or not self._queue:
                continue
            rid, ids = self._queue[0]
            # +k margin: a verify window may write up to k positions past
            # the final accepted length
            need = self._pages_for(len(ids) + self.max_new + self.k)
            if need > len(self._free) or need > len(self._draft_free) \
                    or need > self.d.max_pages \
                    or need > self.draft.max_pages:
                break
            self._queue.pop(0)
            pages = [self._free.pop() for _ in range(need)]
            dpages = [self._draft_free.pop() for _ in range(need)]
            self._occupy(slot, rid)
            self._slot_pages[slot] = pages
            self._draft_pages[slot] = dpages
            admitted.append((slot, rid, ids, pages))
        return admitted

    def _extra_prefill(self, admitted):
        self.draft.prefill_batch(           # draft's guesses discarded
            [(ids, self._draft_pages[slot])
             for slot, _, ids, _ in admitted],
            kids=[rid for _, rid, _, _ in admitted])

    def _after_admit(self, slot, prompt_len):
        self._dlens[slot] = prompt_len

    def _retire(self, slot):
        self._draft_free.extend(self._draft_pages[slot])
        self._draft_pages[slot] = []
        self._dlens[slot] = 0
        super()._retire(slot)

    def step(self):
        rec, seq = self._open, self._seq
        active, prefilled = self._step_admit(rec, seq)
        if not active:
            return 0
        k = self.k
        with _Phase("engine.plan", rec, "plan_s", seq=seq):
            if self._table_cache is None:    # slots changed since last tick
                self._table_cache = (
                    self._table(self._slot_pages, self.d),
                    self._table(self._draft_pages, self.draft))
            ttable, dtable = self._table_cache

        sampled = self.d.sampling is not None

        # draft proposes k tokens: K DEVICE-RESIDENT ticks in ONE
        # compiled loop (decode_multi) — the proposal chain feeds back
        # on device, so the k cheap ticks cost one dispatch + one fetch
        # instead of k host round-trips
        qrows = None
        # one spec step runs two programs (the draft's `decode_multi`
        # and the target's `verify_step`): the step has a name of its own
        program = f"spec_step_k{k}"
        with _Phase("engine.dispatch", rec, "dispatch_s", seq=seq,
                    program=program):
            out = self.draft.decode_multi(self._tokens, self._dlens,
                                          dtable, k, kids=self._kids,
                                          return_logits=sampled)
        S_all = self.d.max_batch
        if rec is not None:
            # one spec step is this engine's horizon: k draft ticks and
            # a (k+1)-wide verify window, priced by `_price_horizon`
            self._horizon_dispatched(
                rec, ("tick", 1, 1), program, k=1, w=1, t_tokens=None,
                decode_rows=len(active), prefill_rows=0,
                disp_toks=S_all * (2 * k + 1), width=self.d.max_pages,
                need=self._need(k), priced=not prefilled)
        # the draft fetch and the verify forward both block: the host
        # waits for the device through all of this phase
        with _Phase("engine.fetch", rec, "fetch_wait_s", seq=seq,
                    horizon=seq):
            proposals = np.asarray(out.tokens_block).T.astype(np.int32)
            if sampled and k > 1:
                # the k-th draft's distribution is never judged
                # (acceptance is capped at k-1): skip its transfer
                qp = self.draft._probs_of(out.logits_block[:k - 1])
                qrows = np.moveaxis(qp, 0, 1)          # [S, k-1, V]
            # target verifies [cur, d1..dk] in one forward
            window = np.concatenate(
                [self._tokens[:, None], proposals[:, :k]],
                axis=1)                                # [S, k+1]
            if sampled:
                tgt, prows = self.d.verify(window, self._lens, ttable,
                                           return_probs=True)
            else:
                tgt = self.d.verify(window, self._lens, ttable)  # [S, k+1]
        if rec is not None:
            rec["t_fetched"] = time.perf_counter()
        with _Phase("engine.bookkeep", rec, "book_s", seq=seq,
                    horizon=seq):
            self.stats.ticks += k
            self.stats.decode_syncs += 1
            self.target_calls += 1
            self.steps += 1
            self.stats.ticks += 1
            self.stats.decode_syncs += 1
            # pad ledger: one spec step computes k draft positions plus
            # a (k+1)-wide verify window per batch row; rows with no
            # request were padding (speculated-then-rejected drafts are
            # real work, not padding — they're the engine's gamble, not
            # the layout's)
            pad_toks = (S_all - len(active)) * (2 * k + 1)
            self.stats.tokens_dispatched += S_all * (2 * k + 1)
            self.stats.tokens_padded += pad_toks
            self.stats.occupancy.append(len(active) / S_all)
            self._note_resident()

            n_emitted = 0
            for s in active:
                rid = self._slot_req[s]
                if sampled:
                    rng = np.random.default_rng(
                        (self.d.seed * 1000003 + self.target_calls)
                        * 4093 + s)
                    a, tok = _spec_accept(
                        prows[s, :k],
                        qrows[s] if qrows is not None else
                        np.zeros((0, prows.shape[-1])),
                        proposals[s, :k - 1], rng)
                    emitted = [int(t) for t in proposals[s, :a]] + [tok]
                else:
                    a = 0
                    while a < k - 1 and proposals[s, a] == tgt[s, a]:
                        a += 1
                    emitted = [int(t) for t in proposals[s, :a]] + \
                        [int(tgt[s, a])]
                L = int(self._lens[s])
                self._lens[s] = L + a + 1
                self._dlens[s] = L + a + 1
                self._tokens[s] = emitted[-1]
                done = False
                n0 = len(self._outputs[rid])
                for t in emitted:
                    self._outputs[rid].append(t)
                    self.stats.tokens += 1
                    if self.trace is not None:
                        self._trace_progress(rid)
                    if (self.eos is not None and t == self.eos) or \
                            len(self._outputs[rid]) >= self.max_new:
                        done = True      # tokens speculated past the stop
                        break            # point are simply never appended
                n_emitted += len(self._outputs[rid]) - n0
                if done:
                    self._retire(s)
            if rec is not None:
                rec.update(tokens=n_emitted, tokens_padded=pad_toks)
        return len(active)

    def _price_horizon(self, k, w, prefill_rows, decode_rows=0,
                       serial=False):
        """One SPEC step's roofline price, overriding the plain decode
        tick: k device-resident draft ticks (draft pool HBM leg) + one
        (k+1)-position verify forward over the target (HBM vs window
        compute) + the step's TWO host syncs (draft fetch, verify
        fetch). Without this the per-tick loop would price a spec step
        as one target tick and the drift ledger would flag a correctly
        performing engine ~k-fold 'underpriced'. `serial=True` sums
        the verify legs instead of taking their max (the
        serialized-vs-mispriced verdict band, like the base engine)."""
        from ..cost_model import (decode_tick_roofline_s,
                                  measured_host_sync_s,
                                  ragged_tick_legs)
        if self._trace_price is None:
            self._trace_price = (self.d.step_hbm_bytes(),
                                 2.0 * self.d.cfg.num_params(),
                                 measured_host_sync_s())
            self._trace_draft_hbm = self.draft.step_hbm_bytes()
        hbm, fpt, sync = self._trace_price
        draft = self.k * decode_tick_roofline_s(self._trace_draft_hbm)
        hbm_s, compute_s = ragged_tick_legs(hbm, self.k + 1, fpt)
        verify = (hbm_s + compute_s) if serial else max(hbm_s, compute_s)
        return draft + verify + 2 * sync
