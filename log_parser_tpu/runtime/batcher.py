"""Cross-request dynamic micro-batching — the serving-side throughput
lever between the admission gate and the engine.

Without this layer every request runs the device pipeline alone: under
concurrent small parses the chip spends most of its time on per-request
dispatch overhead and padding, and the engine's ``state_lock`` turns N
clients into a serial stream (SURVEY.md §5.2). Continuous batching is the
standard fix in serving stacks, and shape-routed grouping before the
expensive matcher is exactly where dynamic-routing parsers like CelerLog
get their throughput (PAPERS.md).

Data flow (docs/ARCHITECTURE.md "Cross-request micro-batching"):

1. **submit** (caller thread): ingest + host-regex overrides — the same
   prepare work ``AnalysisEngine._prepare`` does, minus the device step —
   then the prepared corpus enqueues into a *bucket* keyed by its padded
   row count. Buckets exist so one flush compiles one ``[R, B, T]`` shape:
   row counts are already quantized (fractional power-of-two rungs × the
   engine's min-rows floor, ops/encode.py ``_pad_rows``), widths to
   power-of-two rungs, and R pads to the next power of two below
   ``batch_max`` — so the jit-shape space stays as bounded as the
   unbatched path's.
2. **scheduler** (one background thread): flushes a bucket when it is
   FULL (``batch_max`` queued), when the oldest entry has waited
   ``wait_ms``, or when the earliest enqueued request's admission
   DEADLINE approaches (a tight deadline must not sit out the coalescing
   window). Each flush stacks the bucket into one padded device batch and
   runs ONE vmapped fused program (ops/fused.py
   :class:`~log_parser_tpu.ops.fused.FusedBatchMatchScore`) through the
   engine's watchdog — per-request ``n_lines`` masks inside the vmap
   guarantee scores never bleed across requests.
3. **demux** (scheduler thread): per-request records resolve in ENQUEUE
   order — approx verification, then the frequency-coupled finish under
   ``engine.state_lock`` with the same save/rollback the unbatched path
   uses. The frequency read-before-record ordering is therefore exactly
   what a serial stream in enqueue order would produce. Failures stay
   per-request: a device-classified error falls back to the golden host
   path for THAT request only; a logic bug propagates to its caller and
   its batchmates never notice.

**Bisection** (``_resolve_records``): a device-classified fault on the
fused batched step no longer sinks the whole flush to golden. The batch
is split log₂-wise — each half retried as its own smaller device batch —
until the poison row(s) are isolated: the healthy majority is served
ON-DEVICE exactly as if the poison had never shared their flush, and
only the culprits take the golden fallback (which strikes their
fingerprint into ``runtime/quarantine.py`` so the NEXT arrival never
reaches the device step at all). Demux still runs in enqueue order over
the concatenated per-item outcomes, so frequency serial-equivalence is
untouched. A watchdog circuit-open error (``pre_run``) skips bisection —
every sub-batch would short-circuit identically — as does a non-device
logic error (it would reproduce deterministically on every split).

Chaos sites (runtime/faults.py): ``batcher`` fires at flush start (so
``batcher_slow`` delays a flush and ``batcher_raise`` fails a whole batch
into per-request fallback), ``quarantine`` fires per request inside the
batched device step keyed by the request's log blob (``match=`` poisons
one row of a healthy batch), ``bisect`` fires at each split decision
(``bisect_raise`` aborts isolation and fails the faulted sub-batch
whole), ``batcher_demux`` fires per request during demux (a dropped
demux slot fails one request, not the batch), and ``batcher_oversize`` —
when armed — makes the flush take EVERYTHING queued in the bucket,
ignoring ``batch_max`` (an oversized batch exercising the R-padding
ladder).
"""

from __future__ import annotations

import threading
import time
from typing import TYPE_CHECKING

import numpy as np

from log_parser_tpu import _clock as pclock
from log_parser_tpu.native.ingest import Corpus
from log_parser_tpu.ops.encode import _pad_rows
from log_parser_tpu.runtime import faults
from log_parser_tpu.runtime.linecache import (
    LineKeys,
    dedup_slots,
    group_rows,
    records_from_hits,
    regroup_exact,
    request_hits,
    slot_hits,
)
from log_parser_tpu.utils.trace import PhaseTrace, annotation

if TYPE_CHECKING:  # import cycle: engine imports nothing from here at boot
    from log_parser_tpu.models.analysis import AnalysisResult
    from log_parser_tpu.models.pod import PodFailureData

# A line-cache flush of several requests runs its residual in dispatches
# of at most this many rows (a 2,000-line request's rung), and every row
# rung up to it compiles on the first such flush at a line width.
FLUSH_CHUNK_ROWS = 2048


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


class _Pending:
    """One enqueued request: prepare outputs + the rendezvous the caller
    blocks on. ``result``/``error`` are written by the scheduler thread
    before ``done`` is set."""

    __slots__ = (
        "data", "start", "trace", "corpus", "om", "ov",
        "deadline", "enqueued_at", "done", "result", "error", "seq",
    )

    def __init__(self, data, start, trace, corpus, om, ov, deadline, seq):
        self.data = data
        self.start = start
        self.trace = trace
        self.corpus = corpus
        self.om = om
        self.ov = ov
        self.deadline = deadline  # monotonic seconds, or None
        self.enqueued_at = pclock.mono()
        self.done = threading.Event()
        self.result = None
        self.error: BaseException | None = None
        self.seq = seq


class MicroBatcher:
    """Background scheduler coalescing concurrent analyze() calls into one
    padded device batch per shape bucket. Created via
    ``engine.enable_batching()``; transports call ``engine.analyze_batched``
    which routes here."""

    def __init__(self, engine, wait_ms: float = 2.0, batch_max: int = 8):
        from log_parser_tpu.ops.fused import FusedBatchMatchScore

        self.engine = engine
        self.wait_s = max(0.0, float(wait_ms)) / 1e3
        self.batch_max = max(1, int(batch_max))
        self.program = FusedBatchMatchScore(engine.fused)
        self._cv = threading.Condition()
        self._queues: dict[int, list[_Pending]] = {}  # bucket rows -> FIFO
        self._closed = False
        self._seq = 0
        self._thread: threading.Thread | None = None
        # counters (GET /trace/last "batcher"; guarded by _cv)
        self.requests_batched = 0
        self.batches_flushed = 0
        self.last_batch_size = 0
        self.max_batch_seen = 0
        self.flush_full = 0
        self.flush_wait = 0
        self.flush_deadline = 0
        self.demux_errors = 0
        self.bisects = 0
        self.bisect_aborts = 0
        self.bisect_isolated = 0
        # the flush trace id dispatch spans attach to — scheduler-thread
        # only (set around _resolve_records; bisection retries run on
        # the same thread, so their dispatch spans land on the same
        # flush trace)
        self._active_flush: str | None = None
        # line widths whose residual rung ladder the bank ``_ladder_for``
        # has compiled (scheduler-thread only; ``_warm_ladder``)
        self._ladders: set[int] = set()
        self._ladder_for = None

    # ---------------------------------------------------------------- API

    def start(self) -> "MicroBatcher":
        self._thread = threading.Thread(
            target=self._scheduler, name="micro-batcher", daemon=True
        )
        self._thread.start()
        return self

    def close(self, timeout_s: float = 10.0) -> None:
        """Stop accepting work, flush what is queued, join the scheduler.
        Late submit() calls run unbatched through the engine."""
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout_s)

    def submit(
        self,
        data: "PodFailureData",
        deadline_ms: float | None = None,
        request_id: str | None = None,
    ):
        """Blocking analyze-through-the-batcher: prepare on THIS thread,
        coalesce on the scheduler, return this request's result (or raise
        its per-request error). Semantics match ``analyze_pipelined``
        request-for-request. ``request_id`` rides the request's
        PhaseTrace through the flush so the obs ring can attribute the
        shared device step back to the inbound X-Request-Id.

        The whole call sits inside the engine's request scope: a pattern
        reload that arrives after this request enqueued waits for its
        demux, so already-enqueued batches always finish on the banks
        they were prepared against."""
        with self.engine._request_scope():
            # quarantined fingerprints never enqueue: they would poison a
            # flush their batchmates share — straight to the host path
            fp = self.engine._quarantine_check(data)
            if fp is not None:
                start = pclock.mono()
                with self.engine.state_lock:
                    result = self.engine._serve_quarantined(data, fp)
                self.engine._note_golden(
                    start, "batched", request_id, "quarantined"
                )
                return result
            pending = self._enqueue(data, deadline_ms, request_id)
            if pending is None:  # closed: serve unbatched, same contract
                return self.engine.analyze_pipelined(
                    data, request_id=request_id
                )
            pending.done.wait()
            if pending.error is not None:
                raise pending.error
            return pending.result

    # ------------------------------------------------------------- enqueue

    def _enqueue(self, data, deadline_ms, request_id=None) -> _Pending | None:
        """Prepare (ingest + overrides) on the caller thread and queue the
        request into its shape bucket. Returns None when closed. A prepare
        failure takes the engine's normal fallback/propagate path — under
        ``state_lock``, exactly like ``_analyze``'s prepare except-arm."""
        start = pclock.mono()
        trace = PhaseTrace()
        trace.route = "batched"
        # always a concrete id: the flush span links its member traces
        # by this value, and a span-link must resolve even when the
        # client sent no X-Request-Id (obs/spans.py mints link span ids
        # deterministically from the trace id, so no lookup is needed)
        trace.request_id = request_id or self.engine.obs.new_request_id()
        try:
            with trace.phase("ingest"):
                faults.fire("ingest")
                corpus = Corpus(
                    data.logs or "", min_rows=self.engine._corpus_min_rows()
                )
                corpus.encoded  # materialize outside the scheduler
            with trace.phase("overrides"):
                overrides = self.engine._overrides(corpus)
        except Exception as exc:
            with self.engine.state_lock:
                result = self.engine._serve_fallback(
                    data, exc,
                    request_id=trace.request_id, start=start,
                    route="batched",
                )
            done = _Pending(data, start, trace, None, None, None, None, -1)
            done.result = result
            done.done.set()
            return done
        om, ov = overrides if overrides is not None else (None, None)
        deadline = (
            start + deadline_ms / 1e3
            if deadline_ms is not None and deadline_ms > 0
            else None
        )
        with self._cv:
            if self._closed:
                return None
            pending = _Pending(
                data, start, trace, corpus, om, ov, deadline, self._seq
            )
            self._seq += 1
            rows = corpus.encoded.u8.shape[0]
            self._queues.setdefault(rows, []).append(pending)
            self.requests_batched += 1
            self._cv.notify_all()
        return pending

    # ----------------------------------------------------------- scheduler

    def _flush_at(self, item: _Pending) -> float:
        """When this entry stops waiting for batchmates: its coalescing
        window closes at ``enqueued_at + wait_s``, but an admission
        deadline pulls the flush earlier — leaving a ``wait_s`` margin for
        the device step, floored at the enqueue time (a request that
        arrives nearly dead flushes immediately rather than never)."""
        at = item.enqueued_at + self.wait_s
        if item.deadline is not None:
            at = min(at, max(item.enqueued_at, item.deadline - self.wait_s))
        return at

    def _pick_flush(self, now: float):
        """(bucket, reason) ready to flush now, or (None, earliest time a
        bucket becomes ready). Caller holds ``_cv``."""
        soonest = None
        for rows, q in self._queues.items():
            if not q:
                continue
            if len(q) >= self.batch_max:
                return rows, "full"
            at = min(self._flush_at(i) for i in q)
            if at <= now:
                # deadline-pulled when the wait window alone wouldn't
                # have fired yet
                wait_only = min(i.enqueued_at for i in q) + self.wait_s
                return rows, ("deadline" if at < wait_only - 1e-9 else "wait")
            soonest = at if soonest is None else min(soonest, at)
        return None, soonest

    def _scheduler(self) -> None:
        while True:
            with self._cv:
                while True:
                    now = pclock.mono()
                    bucket, when = self._pick_flush(now)
                    if bucket is not None:
                        reason = when
                        break
                    if self._closed and not any(self._queues.values()):
                        return
                    self._cv.wait(
                        None if when is None else max(0.0, when - now)
                    )
                q = self._queues[bucket]
                take = min(len(q), self.batch_max)
                try:
                    # chaos: an armed oversize fault widens this flush to
                    # the whole bucket, past batch_max
                    faults.fire("batcher_oversize")
                except faults.InjectedFault:
                    take = len(q)
                items = q[:take]
                del q[:take]
                self.batches_flushed += 1
                self.last_batch_size = len(items)
                self.max_batch_seen = max(self.max_batch_seen, len(items))
                if reason == "full":
                    self.flush_full += 1
                elif reason == "deadline":
                    self.flush_deadline += 1
                else:
                    self.flush_wait += 1
            cpu_started = time.thread_time()
            try:
                self._flush(items, reason)
            except BaseException:  # pragma: no cover - must never kill the loop
                import logging

                logging.getLogger(__name__).exception(
                    "micro-batcher flush failed after demux; "
                    "requests were already resolved"
                )
            self.engine.obs.note_request_cpu(
                time.thread_time() - cpu_started, self.engine.obs_tenant,
                "batched",
            )

    # --------------------------------------------------------------- flush

    def _flush(self, items: list[_Pending], reason: str = "wait") -> None:
        engine = self.engine
        spans = engine.obs.spans
        # the flush is its own trace: it belongs to N request traces at
        # once, so it LINKS every member request (and every member
        # back-links it through trace.links) instead of parenting under
        # any single one — the fan-in the flat trace ring cannot express
        flush_id = engine.obs.new_request_id()
        flush_t0 = pclock.mono()
        now = pclock.mono()
        for item in items:
            wait_s = now - item.enqueued_at
            item.trace.add("batch_wait", wait_s)
            item.trace.links.append(flush_id)
            item.trace.span_attrs.update({"flush": flush_id})
            spans.annotate(
                item.trace.request_id, "enqueue", wait_s,
                attrs={"flush": flush_id, "reason": reason},
            )
        # the flush's device stages, shown in every member's trace as the
        # shared device phase is, and counted once (Obs.note_flush)
        stages = PhaseTrace()
        t0 = time.perf_counter()
        self._active_flush = flush_id
        try:
            # chaos at the flush boundary: batcher_slow delays the whole
            # batch; batcher_raise fails it into per-request fallback
            faults.fire("batcher")
            with annotation("engine.device"):
                resolved = self._resolve_records(items, stages)
        except Exception as exc:
            # pre-device failure (injected batcher fault, stacking bug):
            # every request takes the per-request fallback decision
            resolved = [exc] * len(items)
        finally:
            self._active_flush = None
        dt = time.perf_counter() - t0
        flush_stages = stages.stage_dict()
        for item in items:
            item.trace.add_shared("device", dt, flush_stages)
        engine.obs.note_flush(engine.obs_tenant, dt, flush_stages)
        demux_t0 = time.perf_counter()
        demux_errs = 0
        # demux in enqueue order: the frequency evolution equals a serial
        # stream's (read-before-record per request, under state_lock).
        # ``resolved`` holds per-item device records OR the exception that
        # survived bisection for that row — failures stay per-request.
        fallbacks = 0
        for item, recs in zip(items, resolved):
            if isinstance(recs, BaseException):
                fallbacks += 1
                # this row's (sub-)batch faulted: the engine's normal
                # fallback/propagate decision, individually — a device
                # error serves golden (and strikes quarantine), a logic
                # bug propagates to this caller alone
                try:
                    with engine.state_lock:
                        item.result = engine._serve_fallback(
                            item.data, recs,
                            request_id=item.trace.request_id,
                            start=item.start, route="batched",
                        )
                except BaseException as per_req:  # noqa: BLE001
                    item.error = per_req
                finally:
                    item.done.set()
                continue
            try:
                faults.fire("batcher_demux")
                with item.trace.phase("verify"):
                    recs = engine._verify_approx(item.corpus, recs)
                from log_parser_tpu.runtime.engine import _Prepared

                prepared = _Prepared(
                    item.start, item.trace, item.corpus, recs, item.data
                )
                with item.trace.phase("lock_wait"):
                    engine.state_lock.acquire()
                try:
                    saved_freq = engine.frequency._save_state()
                    try:
                        item.result = engine._finish(prepared)
                    except Exception as exc:
                        engine.frequency._load_state(saved_freq)
                        item.result = engine._serve_fallback(
                            item.data, exc,
                            request_id=item.trace.request_id,
                            start=item.start, route="batched",
                        )
                finally:
                    engine.state_lock.release()
            except BaseException as exc:  # noqa: BLE001 - delivered to caller
                with self._cv:
                    self.demux_errors += 1
                demux_errs += 1
                item.error = exc
            finally:
                item.done.set()
        demux_s = time.perf_counter() - demux_t0
        engine.obs.note_flush_demux(engine.obs_tenant, demux_s)
        spans.annotate(
            flush_id, "demux", demux_s,
            attrs={"requests": len(items), "errors": demux_errs,
                   "fallbacks": fallbacks},
        )
        # commit the flush trace whole (force=True: flushes are rare
        # relative to requests and are the one place fan-in causality
        # lives — sampling must never drop them)
        spans.end_trace(
            flush_id,
            duration_s=pclock.mono() - flush_t0,
            tenant=engine.obs_tenant,
            name="flush",
            attrs={
                "members": len(items),
                "reason": reason,
                "bucket": items[0].corpus.encoded.u8.shape[0],
            },
            links=[item.trace.request_id for item in items],
            force=True,
        )

    # ----------------------------------------------------------- bisection

    def _resolve_records(self, items: list[_Pending], stages: PhaseTrace,
                         depth: int = 0):
        """Per-item outcomes for one flush: device records on success, or
        the exception each row is charged with. On a device-classified
        fault the batch splits in half and each half retries as its own
        smaller device batch (log₂ extra steps), isolating poison row(s)
        so the healthy majority still serves ON-DEVICE. Outcomes
        concatenate in the original order, so the enqueue-order demux —
        and with it frequency serial-equivalence — is untouched."""
        from log_parser_tpu.runtime.engine import is_device_error

        if depth == 0 and self.engine.line_cache is not None:
            resolved = self._cached_batch(
                items, self.engine.line_cache, stages
            )
            if resolved is not None:
                return resolved
            # residual device step failed: retry the WHOLE flush on the
            # uncached vmapped path below, so bisection, per-row poison
            # isolation, and quarantine striking behave exactly cache-off
        try:
            return self._device_batch(items, stages)
        except Exception as exc:
            if len(items) == 1:
                if depth > 0:
                    with self._cv:
                        self.bisect_isolated += 1
                return [exc]
            if not is_device_error(exc):
                # deterministic logic error: every split reproduces it
                return [exc] * len(items)
            if getattr(exc, "pre_run", False):
                # watchdog circuit open — the device step never ran and
                # every sub-batch would short-circuit identically
                return [exc] * len(items)
            try:
                faults.fire("bisect")
            except faults.InjectedFault:
                with self._cv:
                    self.bisect_aborts += 1
                return [exc] * len(items)
            with self._cv:
                self.bisects += 1
            mid = len(items) // 2
            return self._resolve_records(
                items[:mid], stages, depth + 1
            ) + self._resolve_records(items[mid:], stages, depth + 1)

    def _cached_batch(self, items: list[_Pending], cache, stages: PhaseTrace):
        """Resolve one flush through the line cache: per-item lookups,
        ONE compacted residual cube dispatch for the unique misses across
        the WHOLE flush (the cross-request half of the dedup), host-side
        override splice + extraction per item. Returns per-item records,
        or None when the residual device step fails — the caller then
        retries the flush wholesale on the uncached path.

        A flush whose lines are all cache hits performs zero device
        dispatches, and the keyed poison fault fires only for items that
        actually contributed a residual row — a request served wholly
        from cache can never strike quarantine."""
        engine = self.engine
        # per-item array-speed dedup (linecache.dedup_slots), then one
        # content grouping of every item's unique lines into flush-global
        # slots (the cross-request half of the dedup). Per global slot,
        # the (item, line) the encode is sliced from prefers a storable
        # (non-needs_host) appearance: a truncated/replaced encode is
        # width-dependent and must neither populate the cache nor serve
        # another item's clean line.
        deds = [dedup_slots(item.corpus) for item in items]
        sizes = [d[2].rows.size for d in deds]
        offs = np.cumsum([0] + sizes)
        widths = np.array([i.corpus.encoded.u8.shape[1] for i in items])
        words = np.zeros(
            (int(offs[-1]), max(d[2].words.shape[1] for d in deds)),
            dtype=np.uint64,
        )
        for r, (_, _, k, _) in enumerate(deds):
            words[offs[r] : offs[r + 1], : k.words.shape[1]] = k.words[k.rows]
        lengths = np.concatenate([d[2].lengths for d in deds])
        probes = np.concatenate([d[2].probes for d in deds])
        storable = np.concatenate([d[2].storable for d in deds])
        src_item = np.repeat(np.arange(len(items)), sizes)
        src_line = np.concatenate([d[1] for d in deds])
        slot, first = group_rows(words, lengths, probes)
        long_rows = np.flatnonzero(~storable & (lengths >= widths[src_item]))
        if long_rows.size:
            slot, first = regroup_exact(
                slot, long_rows,
                lambda j: items[src_item[j]].corpus.line_key_bytes(
                    int(src_line[j])
                ),
            )
        U = first.size
        best = np.full(U, slot.size, dtype=np.int64)
        st = np.flatnonzero(storable)
        np.minimum.at(best, slot[st], st)
        rep = np.where(best < slot.size, best, first)
        keys = LineKeys(words, rep, lengths[rep], probes[rep], storable[rep])
        per_item = [
            slot[offs[r] : offs[r + 1]][d[0]] for r, d in enumerate(deds)
        ]
        all_slots = (
            np.concatenate(per_item) if per_item else np.zeros(0, dtype=np.int64)
        )
        counts = np.bincount(all_slots, minlength=U)
        found = cache.lookup(keys, counts)
        miss_slots = np.flatnonzero(found.row < 0)
        m_item = src_item[rep[miss_slots]]
        m_line = src_line[rep[miss_slots]]

        miner = engine.miner
        if miner is not None:
            # miss-stream tap: one non-blocking bounded-queue offer per
            # unique novel line (sampling + drop accounting live in the
            # tap); mining happens on the miner thread, never here
            for j, s in enumerate(miss_slots.tolist()):
                miner.tap.offer(
                    items[m_item[j]].corpus.line_key_bytes(int(m_line[j])),
                    int(counts[s]),
                )

        fresh = None
        if miss_slots.size:
            u = miss_slots.size
            T = int(widths.max())
            chunks = self._residual_chunks(u, len(items) == 1)
            # chunk c's lines sit at the head of its own padded block
            starts = np.cumsum([0] + [p for _, p in chunks])
            dest = np.concatenate([
                starts[c] + np.arange(n) for c, (n, _) in enumerate(chunks)
            ])
            pad = int(starts[-1])
            res_u8 = np.zeros((pad, T), dtype=np.uint8)
            res_len = np.zeros(pad, dtype=np.int32)
            contributed = np.unique(m_item).tolist()
            for r in contributed:
                j = np.flatnonzero(m_item == r)
                enc = items[r].corpus.encoded
                res_u8[dest[j], : enc.u8.shape[1]] = enc.u8[m_line[j]]
                res_len[dest[j]] = enc.lengths[m_line[j]]

            def _device_step():
                for r in contributed:
                    faults.fire("quarantine", key=items[r].data.logs or "")  # conlint: contained-by-caller (watchdog.run)
                faults.fire("device")  # conlint: contained-by-caller (watchdog.run)
                self._warm_ladder(T)
                return np.concatenate([
                    engine._run_cube(
                        res_u8[s : s + p], res_len[s : s + p], n,
                        trace=stages,
                    )[:n]
                    for (n, p), s in zip(chunks, starts.tolist())
                ])

            t0 = time.perf_counter()
            try:
                fresh = engine.watchdog.run(_device_step)
            except Exception as exc:
                self._dispatch_span(time.perf_counter() - t0, {
                    "rows": pad, "width": T, "lines": u,
                    "residual": True, "error": type(exc).__name__,
                })
                return None
            self._dispatch_span(time.perf_counter() - t0, {
                "rows": pad, "width": T, "lines": u, "residual": True,
                "dispatches": len(chunks),
                "wasteRatio": round((pad - u) / pad, 4) if pad else 0.0,
            })
            engine.obs.note_batch_residual(engine.obs_tenant, u, pad)
            cache.note_residual(u, int(counts[miss_slots].sum()) - u)
            with stages.stage("cache.populate"):
                cache.populate(keys.take(miss_slots), fresh, found)

        hits = slot_hits(found, miss_slots, fresh)
        out = []
        for r, item in enumerate(items):
            n = item.corpus.n_lines
            line, col = request_hits(hits, per_item[r], n, item.om, item.ov)
            out.append(
                records_from_hits(line, col, n, engine.bank, engine.tables)
            )
            engine.obs.note_extract_hits(line.size, engine.obs_tenant)
        engine._k_hint = max(r.n_matches for r in out)
        return out

    def _residual_chunks(self, u: int, lone: bool) -> list[tuple[int, int]]:
        """(lines, padded rows) of each dispatch of a ``u``-line residual.
        A lone request's residual runs at its own row rung, as the
        unbatched path runs it. A flush of several requests coalesces
        their misses, so its residual can reach rungs that no lone
        request reaches: it runs in dispatches of ``FLUSH_CHUNK_ROWS``
        rows and one at the rung of the rest, every one of them on the
        width's ladder (``_warm_ladder``). Rows are independent lines, so
        the split changes no bit of the result."""
        own = _pad_rows(u, self.engine._corpus_min_rows())
        if lone or u <= FLUSH_CHUNK_ROWS:
            return [(u, own)]
        chunks = [(FLUSH_CHUNK_ROWS, FLUSH_CHUNK_ROWS)] * (
            (u - 1) // FLUSH_CHUNK_ROWS
        )
        rest = u - FLUSH_CHUNK_ROWS * len(chunks)
        chunks.append((rest, _pad_rows(rest, self.engine._corpus_min_rows())))
        return chunks

    def _warm_ladder(self, width: int) -> None:
        """Compile the residual cube program at every row rung up to
        ``FLUSH_CHUNK_ROWS`` for ``width``, once per width and bank, on
        the first cached flush that reaches it: no later flush at that
        width compiles, whatever sizes came before it."""
        fused = self.engine.fused
        if self._ladder_for is not fused:
            # a new bank has its programs still to compile
            self._ladder_for = fused
            self._ladders = set()
        if width in self._ladders:
            return
        min_rows = self.engine._corpus_min_rows()
        rows = _pad_rows(1, min_rows)
        while rows <= FLUSH_CHUNK_ROWS:
            fused.cube_rows(np.zeros((rows, width), dtype=np.uint8),
                            np.zeros(rows, dtype=np.int32), 0)
            rows = _pad_rows(rows + 1, min_rows)
        self._ladders.add(width)

    def _device_batch(self, items: list[_Pending], stages: PhaseTrace):
        """Stack the bucket into one padded [R, B, T] batch, run the
        vmapped program through the watchdog, return per-item records.
        ``stages`` (a PhaseTrace) times the device stages."""
        engine = self.engine
        B = items[0].corpus.encoded.u8.shape[0]
        T = max(i.corpus.encoded.u8.shape[1] for i in items)
        R = _next_pow2(len(items))
        C = engine.bank.n_columns
        lines = np.zeros((R, B, T), dtype=np.uint8)
        lens = np.zeros((R, B), dtype=items[0].corpus.encoded.lengths.dtype)
        nlin = np.zeros((R,), dtype=np.int32)
        has_ov = any(i.om is not None for i in items)
        om = np.zeros((R, B, C), dtype=bool) if has_ov else None
        ov = np.zeros((R, B, C), dtype=bool) if has_ov else None
        for r, item in enumerate(items):
            enc = item.corpus.encoded
            # width padding is semantically neutral: bytes past a line's
            # length are already the zero padding byte at any width rung
            lines[r, :, : enc.u8.shape[1]] = enc.u8
            lens[r] = enc.lengths
            nlin[r] = item.corpus.n_lines
            if item.om is not None:
                om[r] = item.om
                ov[r] = item.ov
        # rows R >= len(items) are dummy slots: n_lines == 0 masks every
        # line invalid, so they produce zero matches at zero risk

        def _device_step():
            # chaos: a keyed quarantine fault poisons the row(s) whose log
            # blob contains match= — the fused step dies exactly as a real
            # poison pill would, exercising bisection end to end
            for item in items:
                faults.fire("quarantine", key=item.data.logs or "")  # conlint: contained-by-caller (watchdog.run)
            faults.fire("device")  # conlint: contained-by-caller (watchdog.run)
            return self.program.run(
                lines, lens, nlin, om, ov, k_hint=engine._k_hint,
                trace=stages,
            )

        t0 = time.perf_counter()
        try:
            recs_list = engine.watchdog.run(_device_step)
        except BaseException as exc:
            # a faulted dispatch still records its span — carrying the
            # fault site — before bisection splits the batch; each
            # retried sub-batch lands as another dispatch span on the
            # same flush trace
            self._dispatch_span(time.perf_counter() - t0, {
                "rows": B, "width": T, "batchSlots": R,
                "dummySlots": R - len(items),
                "error": type(exc).__name__,
            })
            raise
        attrs = engine._note_kernel_dispatch(
            B, width=T, batch_slots=R, dummy_slots=R - len(items)
        ) or {"rows": B, "width": T}
        self._dispatch_span(time.perf_counter() - t0, attrs)
        engine._k_hint = max(r.n_matches for r in recs_list)
        return recs_list[: len(items)]

    def _dispatch_span(self, duration_s: float, attrs: dict) -> None:
        """Stage one device-dispatch child span under the active flush
        trace (no-op for unbatched callers — their dispatch attrs ride
        the request trace via ``_run_device``/``_run_cube`` instead)."""
        fid = self._active_flush
        if fid is not None:
            self.engine.obs.spans.annotate(
                fid, "dispatch", duration_s, attrs=attrs
            )

    # ------------------------------------------------------- observability

    def stats(self) -> dict:
        with self._cv:
            return {
                "waitMs": self.wait_s * 1e3,
                # sampled by the obs engine collector through
                # METRIC_SAMPLES below — keep key renames in sync
                "batchMax": self.batch_max,
                "queueDepth": sum(len(q) for q in self._queues.values()),
                "buckets": sorted(
                    rows for rows, q in self._queues.items() if q
                ),
                "requestsBatched": self.requests_batched,
                "batchesFlushed": self.batches_flushed,
                "lastBatchSize": self.last_batch_size,
                "maxBatchSeen": self.max_batch_seen,
                "flushFull": self.flush_full,
                "flushWait": self.flush_wait,
                "flushDeadline": self.flush_deadline,
                "demuxErrors": self.demux_errors,
                "bisects": self.bisects,
                "bisectAborts": self.bisect_aborts,
                "bisectIsolated": self.bisect_isolated,
            }


# /metrics view over MicroBatcher.stats() — read by the obs engine
# collector at scrape time (log_parser_tpu/obs), never a second tally
METRIC_SAMPLES = (
    ("queueDepth", "logparser_batch_queue_depth", {}),
    ("requestsBatched", "logparser_requests_batched_total", {}),
    ("batchesFlushed", "logparser_batches_flushed_total", {}),
)
