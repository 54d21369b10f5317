"""AnalysisEngine — the TPU-backed replacement for the reference's
``AnalysisService.analyze`` (AnalysisService.java:50-122).

Pipeline per request:

1. ingest: fused Java-split + padded uint8 encode (native C++ scan when the
   extension is built, vectorized numpy otherwise) with lazy line
   materialization — AnalysisService.java:53 semantics without a million
   host string objects;
2. ONE fused device program: DFA-bank automaton execution over the line
   batch + integer factor-component extraction, compacted to K-capped
   match records (ops/fused.py). Host ``re`` verification only for
   device-inexact lines (non-ASCII / over-long) and automaton-unsupported
   regexes, injected as a cube override;
3. host finalizer: exact f64 seven-factor scores from the integer records
   (runtime/finalize.py) — better-than-device-f64 parity at O(matches)
   cost;
4. assemble ``AnalysisResult`` in discovery order (line-major, then
   pattern order — AnalysisService.java:89-113) with the same
   metadata/summary quirks as the reference.

Frequency state is the engine's only mutable state, mirrored from the
reference's ConcurrentHashMap (FrequencyTrackingService.java:25) but read
at batch granularity with exact per-match ordering recovered from the
record stream (read-before-record, ScoringService.java:84-88).
"""

from __future__ import annotations

import contextlib
import os
import random
import threading
import time
import uuid
from collections import deque
from typing import Callable

import numpy as np

from log_parser_tpu import _clock as pclock
from log_parser_tpu.config import ScoringConfig
from log_parser_tpu.golden.engine import (
    GoldenFrequencyTracker,
    build_metadata,
    build_summary,
    extract_context,
)
from log_parser_tpu.models.analysis import AnalysisResult, MatchedEvent
from log_parser_tpu.models.pattern import PatternSet
from log_parser_tpu.models.pod import PodFailureData
from log_parser_tpu.native.ingest import Corpus
from log_parser_tpu.obs import Obs
from log_parser_tpu.ops.encode import _pad_rows
from log_parser_tpu.ops.fused import FusedMatchScore, FusedStaticTables
from log_parser_tpu.runtime import faults
from log_parser_tpu.runtime.linecache import (
    DEFAULT_LINE_CACHE_MB,
    LineCache,
    dedup_slots,
    records_from_hits,
    request_hits,
    slot_hits,
)
from log_parser_tpu.ops.match import DfaBank, MatcherBanks
from log_parser_tpu.patterns.bank import PatternBank
from log_parser_tpu.runtime.finalize import FinalizedBatch, finalize_batch
from log_parser_tpu.runtime.quarantine import (
    DEFAULT_BREAKER_COOLDOWN_S,
    DEFAULT_CAPACITY,
    DEFAULT_STRIKES,
    DEFAULT_TTL_S,
    PatternBreakerBoard,
    QuarantineTable,
    fingerprint as quarantine_fingerprint,
)
from log_parser_tpu.utils.trace import NO_TRACE, PhaseTrace

# Substrings identifying plain RuntimeErrors raised by the device layer
# *before* jit execution starts (jax raises these from xla_bridge /
# PJRT client setup, not as JaxRuntimeError).
_DEVICE_ERROR_MARKERS = (
    "Unable to initialize backend",
    "failed to initialize",
    "DEADLINE_EXCEEDED",
    "UNAVAILABLE",
    "RESOURCE_EXHAUSTED",
    "Device or resource busy",
)


def _raised_in_device_layer(exc: BaseException) -> bool:
    """True when any traceback frame of ``exc`` (or of an exception in its
    cause/context chain) belongs to a jax/jaxlib module — i.e. the error
    genuinely originated in the device stack, not in engine code that
    happens to quote device-sounding text.

    The cause/context chain matters: jax's default traceback filtering
    (``jax_traceback_filtering='auto'``) strips jax-internal frames from
    the primary traceback and re-parents the unfiltered exception via
    ``__cause__``/``__context__`` — inspecting only ``__traceback__``
    would misclassify genuine device errors as logic bugs."""
    seen: set[int] = set()
    current: BaseException | None = exc
    while current is not None and id(current) not in seen:
        seen.add(id(current))
        tb = current.__traceback__
        while tb is not None:
            mod = tb.tb_frame.f_globals.get("__name__", "")
            if mod == "jax" or mod.startswith(("jax.", "jaxlib")):
                return True
            tb = tb.tb_next
        current = current.__cause__ or current.__context__
    return False


def is_device_error(exc: BaseException) -> bool:
    """True only for failures of the device/XLA layer itself — the class of
    error the golden fallback exists for (SURVEY.md §5.3). Logic bugs
    (TypeError in assembly, bad config, ...) must propagate: serving them
    from the host path would hide the bug and, for large batches, convert a
    fast failure into a multi-minute pure-Python crawl (the round-1
    BENCH_r01 rc=124 failure mode).

    A plain RuntimeError counts only when BOTH a known device-layer marker
    appears in its message AND the exception was raised from a jax/jaxlib
    frame — a non-device RuntimeError that merely quotes such text (e.g. a
    log line or downstream response embedded in the message) propagates
    (ADVICE.md r2)."""
    import jax.errors

    if isinstance(exc, DeviceHungError):
        return True
    if isinstance(exc, faults.InjectedDeviceFault):
        # injected device-layer chaos reacts exactly like a dead backend;
        # faults injected elsewhere (ingest/finalize/transport) are plain
        # InjectedFault and take the propagate-to-500 path of a logic bug
        return True
    if isinstance(exc, jax.errors.JaxRuntimeError):
        return True
    if isinstance(exc, RuntimeError):
        msg = str(exc)
        return any(marker in msg for marker in _DEVICE_ERROR_MARKERS) and (
            _raised_in_device_layer(exc)
        )
    return False


class DeviceHungError(RuntimeError):
    """The device step exceeded the watchdog timeout (or the breaker is
    open from a previous hang). Classified as a device error so the
    golden fallback serves the request.

    ``pre_run`` distinguishes the circuit-open short-circuit (this
    request's step never entered the device — it proves nothing about
    the request) from an actual timeout: only the latter counts as a
    quarantine strike, and the batcher skips bisecting the former (the
    sub-batches would short-circuit identically)."""

    pre_run = False


class DeviceWatchdog:
    """Hang protection for the device step (SURVEY.md §5.3).

    A *crashing* backend raises and the golden fallback already serves
    the request; a *wedged* backend (stuck runtime or driver) just
    never returns, hanging every request. With a timeout configured
    (``LOG_PARSER_TPU_DEVICE_TIMEOUT_S`` or ``--device-timeout``), the
    device step runs in a worker thread: on timeout the request raises
    :class:`DeviceHungError` (→ golden fallback) and the circuit opens,
    so subsequent requests fall back IMMEDIATELY instead of entering
    the wedged backend. Abandoned workers keep waiting; the circuit
    closes when the LAST outstanding worker responds (a smaller
    request completing while another is still stuck must not re-open
    the front door), and any late error is logged so the root cause of
    the wedge reaches the operator.

    Half-open recovery: waiting for the last outstanding worker alone
    would leave the circuit stuck open forever when a worker NEVER
    responds (a truly lost backend thread). After ``cooldown_s``
    (default: the timeout itself; ``LOG_PARSER_TPU_BREAKER_COOLDOWN_S``
    overrides) the breaker goes half-open: exactly one trial request is
    admitted to the device path. Success closes the circuit even with
    abandoned workers still pending; a timeout or error re-arms the
    cool-down and the circuit stays open.

    Default OFF (0): a first request legitimately spends tens of
    seconds in XLA compilation, and only an operator knows a deadline
    that separates that from a wedge. Hung worker threads cannot be
    cancelled (XLA holds the wait with the GIL released) — they leak
    until the backend responds, bounded by the number of requests
    already in flight when the wedge began; once the circuit is open
    no new ones are created.
    """

    def __init__(self, timeout_s: float, cooldown_s: float | None = None):
        self.timeout_s = timeout_s
        if cooldown_s is None:
            cooldown_s = float(
                os.environ.get("LOG_PARSER_TPU_BREAKER_COOLDOWN_S", "0")
            ) or timeout_s
        self.cooldown_s = cooldown_s
        self._lock = threading.Lock()
        self._open = False
        self._opened_at = 0.0
        self._probing = False  # at most one half-open trial at a time
        self._inflight = 0

    @property
    def circuit_open(self) -> bool:
        with self._lock:
            return self._open

    def run(self, fn):
        if self.timeout_s <= 0:
            return fn()
        probe = False
        with self._lock:
            if self._open:
                if (
                    self.cooldown_s > 0
                    and not self._probing
                    and pclock.mono() - self._opened_at >= self.cooldown_s
                ):
                    # half-open: this request is the single recovery trial
                    self._probing = True
                    probe = True
                else:
                    exc = DeviceHungError(
                        "device backend still hung from a previous timeout "
                        "(circuit open); serving from the host path"
                    )
                    exc.pre_run = True
                    raise exc
            self._inflight += 1
        result: list = []
        error: list = []
        done = threading.Event()
        finished = [False]  # worker bookkeeping ran (under self._lock)
        abandoned = [False]  # caller gave up on this worker

        def worker() -> None:
            try:
                result.append(fn())
            except BaseException as exc:  # noqa: BLE001 - re-raised below
                error.append(exc)
            finally:
                with self._lock:
                    finished[0] = True
                    self._inflight -= 1
                    if self._inflight == 0:
                        # every outstanding worker has been answered:
                        # the backend is responsive again
                        self._open = False
                    late = abandoned[0]
                done.set()
                if late and error:
                    import logging

                    logging.getLogger(__name__).error(
                        "Abandoned device step eventually failed "
                        "(the wedge's root cause): %r",
                        error[0],
                        exc_info=error[0],
                    )

        threading.Thread(
            target=worker, name="device-watchdog", daemon=True
        ).start()
        if not done.wait(self.timeout_s):
            with self._lock:
                if not finished[0]:
                    # genuinely still stuck: trip the breaker. A worker
                    # that completed in the wait/lock gap falls through
                    # and is harvested below instead (its finally can no
                    # longer be un-done by this set).
                    abandoned[0] = True
                    self._open = True
                    self._opened_at = pclock.mono()
                    if probe:
                        # failed trial: re-arm the cool-down, next probe
                        # waits a full period again
                        self._probing = False
                    raise DeviceHungError(
                        f"device step exceeded {self.timeout_s:g}s; "
                        "serving from the host path until the backend "
                        "responds"
                    )
            done.wait()  # finished[0] is True: done.set() is imminent
        if probe:
            with self._lock:
                self._probing = False
                if error:
                    # the backend RESPONDED (not wedged) but with an error:
                    # don't close on an error — re-arm the cool-down and
                    # let the inflight==0 bookkeeping decide as before
                    self._opened_at = pclock.mono()
                else:
                    # trial succeeded: the backend serves again. Close even
                    # with abandoned workers still pending — the stuck-open
                    # fix this probe exists for.
                    self._open = False
        if error:
            raise error[0]
        return result[0]


class KernelTierStats:
    """Counters for the Pallas union-DFA kernel tier (GET /trace/last
    ``kernel`` block). One note per device dispatch — engine direct path,
    line-cache residual cubes, and the micro-batcher's vmapped batches
    all report here — so operators can see whether traffic actually
    rides the kernel and why not when it doesn't (REASONS codes,
    ops/matchdfa_pallas.py)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.enabled = False
        self.reason = "off"
        self.geometry: dict | None = None
        self.kernel_batches = 0
        self.kernel_rows = 0
        self.xla_batches = 0

    def note(
        self,
        rows: int,
        active: bool,
        enabled: bool,
        reason: str,
        geometry: dict | None = None,
    ):
        with self._lock:
            self.enabled = enabled
            self.reason = reason
            self.geometry = geometry
            if not enabled:
                return
            if active:
                self.kernel_batches += 1
                self.kernel_rows += rows
            else:
                self.xla_batches += 1

    def stats(self) -> dict:
        with self._lock:
            return {
                "enabled": self.enabled,
                "reason": self.reason,
                "geometry": self.geometry,
                "kernelBatches": self.kernel_batches,
                "kernelRows": self.kernel_rows,
                "xlaBatches": self.xla_batches,
            }


_NULL_LOCK = contextlib.nullcontext()


class _Prepared:
    """One request's prepare-phase outputs, handed to the finish phase.
    ``data`` rides along so the finish phase can hand the original
    request to the shadow verifier."""

    __slots__ = ("start", "trace", "corpus", "recs", "data")

    def __init__(self, start, trace, corpus, recs, data=None):
        self.start = start
        self.trace = trace
        self.corpus = corpus
        self.recs = recs
        self.data = data


class AnalysisEngine:
    """Immutable compiled library + one fused device program + frequency state."""

    def __init__(
        self,
        pattern_sets: list[PatternSet],
        config: ScoringConfig | None = None,
        clock: Callable[[], float] = pclock.mono,
    ):
        self.config = config or ScoringConfig()
        # warm restarts must not re-pay multi-second XLA compiles
        from log_parser_tpu.utils.xlacache import enable_persistent_cache

        enable_persistent_cache()
        self.bank = PatternBank(pattern_sets)
        self.frequency = GoldenFrequencyTracker(self.config, clock=clock)

        self._host_cols = [
            i
            for i, c in enumerate(self.bank.columns)
            if c.dfa is None and c.exact_seqs is None
        ]
        self._device_cols = [
            i
            for i, c in enumerate(self.bank.columns)
            if c.dfa is not None or c.exact_seqs is not None
        ]
        # Host-column literal prefilter (VERDICT r3 #3): host-only
        # columns with required literals (lenient extraction,
        # bank._intern_column) get an AC pass over the device-encoded
        # bytes; only candidate lines pay host re. Literal-free host
        # columns keep the full per-request scan (warned at load).
        self._host_pref_cols: list[int] = []
        self._host_slow_cols: list[int] = []
        self._host_prefilter = None
        if self._host_cols:
            from log_parser_tpu.patterns.regex.ac import AhoCorasick

            lits: list[bytes] = []
            groups: list[int] = []
            for ci in self._host_cols:
                col = self.bank.columns[ci]
                if col.literals:
                    gi = len(self._host_pref_cols)
                    self._host_pref_cols.append(ci)
                    for lit in col.literals:
                        lits.append(lit.fold().text)
                        groups.append(gi)
                else:
                    self._host_slow_cols.append(ci)
            if self._host_pref_cols:
                self._host_prefilter = AhoCorasick.build_cached(lits, groups)
        # static per-pattern index tables (numpy, cheap); the full-bank
        # device programs below are built lazily — subclasses that override
        # _run_device (pattern sharding) never pay for them
        self.tables = FusedStaticTables(self.bank, self.config)
        self._matchers: MatcherBanks | None = None
        self._fused: FusedMatchScore | None = None
        # two concurrent _prepare calls (analyze_pipelined) must not both
        # build the lazy device programs — one multi-second compile each.
        # RLock: building `fused` takes the lock and then touches the
        # `matchers` property, which takes it again on the same thread
        self._init_lock = threading.RLock()
        self._golden = None
        # cheap insurance: a request whose device batch dies is re-served
        # from the golden host path (SURVEY.md §5.3). Disabled in the test
        # suite so device bugs can never hide behind the fallback.
        self.fallback_to_golden = (
            os.environ.get("LOG_PARSER_TPU_NO_FALLBACK") != "1"
        )
        # hang protection for a wedged (not crashing) backend — §5.3;
        # 0 disables (default: first-request XLA compiles are legitimate
        # long waits only the operator can bound)
        self.watchdog = DeviceWatchdog(
            float(os.environ.get("LOG_PARSER_TPU_DEVICE_TIMEOUT_S", "0"))
        )
        self._k_hint = 0  # previous request's match count → starting K bucket
        self._approx_pat_mask = None  # lazy — see _approx_patterns
        self._approx_sec = None  # lazy — see _approx_secondaries
        self._approx_token: tuple | None = None  # matcher identities the caches derive from
        # serializes frequency-coupled state (finish phase, admin routes,
        # golden fallback) across transports; the prepare phase (ingest +
        # device) deliberately runs OUTSIDE it — see analyze_pipelined
        self.state_lock = threading.Lock()
        # quiescence gate for hot pattern reload (runtime/reload.py):
        # every request enters _request_scope; apply_library waits for
        # active==0 and blocks NEW admissions while swapping, so in-flight
        # (and already-enqueued batched) requests finish on the old banks
        # and the next admission sees the new ones
        self._quiesce_cv = threading.Condition()
        self._active_requests = 0
        self._swap_pending = False
        self._scope_local = threading.local()
        # durable frequency state (runtime/journal.py) — None until
        # attach_journal(); reload bookkeeping for /trace/last
        self.journal = None
        self.reload_epoch = 0
        self.reload_count = 0
        self.reload_failures = 0
        self.last_reload_error: str | None = None
        # lint summary of the most recent reload attempt's candidate
        # library (runtime/reload.py lint_stage) — /trace/last "lint"
        self.last_lint: dict | None = None
        # observability (SURVEY.md §5.1/§5.5): per-phase timers and the full
        # factor breakdown of the most recent request
        self.last_trace: PhaseTrace | None = None
        self.trace_history: deque[PhaseTrace] = deque(maxlen=512)
        self.last_finalized: FinalizedBatch | None = None
        # observability plane (log_parser_tpu/obs): metrics registry +
        # request-trace ring + SLO tracker + profiler, rooted here so
        # every transport reaches one bundle through the engine it
        # already holds. Tenant engines REPLACE this with the primary's
        # bundle (runtime/tenancy.py) under their own tenant label.
        self.obs = Obs()
        self.obs_tenant = "default"
        self.obs.add_engine_collector(self)
        # how many requests this engine served from the golden host path
        # because the device layer failed (surfaced via GET /trace/last)
        self.fallback_count = 0
        # Pallas union-DFA kernel tier accounting (GET /trace/last)
        self.kernel_stats = KernelTierStats()
        # ... and how many were ROUTED there deliberately by admission
        # pressure (serve/admission.py ladder rung 2) — a separate counter,
        # because pressure routing is policy, not failure
        self.host_routed_count = 0
        # cross-request micro-batching scheduler (runtime/batcher.py);
        # None until enable_batching() — transports then route analyze
        # calls through analyze_batched
        self.batcher = None
        # exact-match line cache (runtime/linecache.py): None until
        # enable_line_cache() — repeat lines then skip the match cube
        self.line_cache = None
        # poison-request quarantine (runtime/quarantine.py): organic
        # device failures strike the request's fingerprint; at the
        # threshold repeats route straight to golden until TTL expiry
        self.quarantine = QuarantineTable(
            strikes=int(
                os.environ.get(
                    "LOG_PARSER_TPU_QUARANTINE_STRIKES", str(DEFAULT_STRIKES)
                )
            ),
            ttl_s=float(
                os.environ.get(
                    "LOG_PARSER_TPU_QUARANTINE_TTL_S", str(DEFAULT_TTL_S)
                )
            ),
            capacity=int(
                os.environ.get(
                    "LOG_PARSER_TPU_QUARANTINE_CAPACITY", str(DEFAULT_CAPACITY)
                )
            ),
            clock=clock,
        )
        # per-pattern circuit breakers tripped by shadow divergence: an
        # open breaker serves ONLY that pattern's columns from the exact
        # host regex (see _overrides) instead of degrading the engine
        self.breakers = PatternBreakerBoard(
            cooldown_s=float(
                os.environ.get(
                    "LOG_PARSER_TPU_PATTERN_BREAKER_COOLDOWN_S",
                    str(DEFAULT_BREAKER_COOLDOWN_S),
                )
            ),
            clock=clock,
        )
        self._breaker_map: dict[str, set[int]] | None = None
        self._breaker_map_bank = None
        # online shadow verification (ShadowVerifier below): sample
        # --shadow-rate of served requests, re-run on golden off the hot
        # path, compare scores at 1e-9; None until enable_shadow()
        self.shadow = None
        shadow_rate = float(os.environ.get("LOG_PARSER_TPU_SHADOW_RATE", "0") or 0)
        if shadow_rate > 0:
            self.enable_shadow(shadow_rate)
        # template miner (mining/): background consumer of the line-cache
        # miss stream; None until enable_miner()
        self.miner = None
        # chaos: pick up LOG_PARSER_TPU_FAULTS once per process (no-op
        # when unset or when a test installed a registry explicitly)
        faults.ensure_env()

    @property
    def skipped_patterns(self) -> list[tuple[str, str]]:
        return self.bank.skipped_patterns

    @property
    def matchers(self) -> MatcherBanks:
        if self._matchers is None:
            with self._init_lock:
                if self._matchers is None:
                    self._matchers = MatcherBanks(self.bank)
        return self._matchers

    @property
    def dfa_bank(self) -> DfaBank:
        return self.matchers.dfa_bank

    @property
    def fused(self) -> FusedMatchScore:
        if self._fused is None:
            with self._init_lock:
                if self._fused is None:
                    self._fused = FusedMatchScore(
                        self.bank, self.config, self.matchers
                    )
        return self._fused

    # -------------------------------------------------------------- overrides

    def _overrides(self, corpus: Corpus) -> tuple[np.ndarray, np.ndarray] | None:
        """Cube corrections the automaton path can't make itself: columns
        with no DFA (host regex over every line) and lines flagged
        device-inexact (non-ASCII bytes, over-long). None when the batch is
        fully device-exact — the common case, which then skips the
        override transfer entirely."""
        enc = corpus.encoded
        host_lines = np.flatnonzero(enc.needs_host[: corpus.n_lines])
        breaker_cols = self._breaker_columns()
        if not self._host_cols and not breaker_cols and len(host_lines) == 0:
            return None
        B = enc.u8.shape[0]
        n = corpus.n_lines
        mask = np.zeros((B, self.bank.n_columns), dtype=bool)
        val = np.zeros((B, self.bank.n_columns), dtype=bool)
        if self._host_cols:
            mask[:, self._host_cols] = True
            if self._host_slow_cols:
                # literal-free host columns: every line pays host re
                hosts = [
                    (c, self.bank.columns[c].host)
                    for c in self._host_slow_cols
                ]
                for i, line in enumerate(corpus.materialize()):
                    for col, host in hosts:
                        val[i, col] = bool(host.search(line))
            if self._host_pref_cols:
                # candidate lines only: AC over the folded device bytes
                # (required literals, so no true match escapes), plus
                # every needs_host line — truncated/non-ASCII encodings
                # can hide a literal from the device-side scan
                from log_parser_tpu.patterns.regex.ac import fold_lines_u8

                hits = self._host_prefilter.scan_lines(
                    fold_lines_u8(enc.u8[:n]), enc.lengths[:n]
                )
                cand_cols: list[np.ndarray] = []
                for gi in range(len(self._host_pref_cols)):
                    cand = ((hits[:, gi // 32] >> np.uint32(gi % 32)) & 1).astype(bool)
                    cand[host_lines] = True
                    cand_cols.append(np.flatnonzero(cand))
                needed = set()
                for cand in cand_cols:
                    needed.update(cand.tolist())
                text = {i: corpus.line(int(i)) for i in needed}
                for ci, cand in zip(self._host_pref_cols, cand_cols):
                    host = self.bank.columns[ci].host
                    for i in cand:
                        val[i, ci] = bool(host.search(text[int(i)]))
        if breaker_cols:
            # per-pattern breaker containment: an OPEN breaker's columns
            # are served from the exact host regex on every line — host
            # truth is exact, so a column shared with a healthy pattern
            # is corrected, never corrupted
            mask[:, breaker_cols] = True
            for i, line in enumerate(corpus.materialize()):
                for col in breaker_cols:
                    val[i, col] = bool(self.bank.columns[col].host.search(line))
        for i in host_lines:
            line = corpus.line(int(i))
            for col in self._device_cols:
                mask[i, col] = True
                val[i, col] = bool(self.bank.columns[col].host.search(line))
        return mask, val

    def _breaker_columns(self) -> list[int]:
        """Engine-bank columns of every pattern whose shadow breaker is
        currently OPEN (primary + secondary + sequence-event roles) —
        the override set that serves just those patterns from host truth.
        Empty in the steady state, so the common path costs one set
        check."""
        board = self.breakers
        if board is None:
            return []
        pids = board.overridden_patterns()
        if not pids:
            return []
        if self._breaker_map is None or self._breaker_map_bank is not self.bank:
            by_id: dict[str, set[int]] = {}
            for p, pat in enumerate(self.bank.patterns):
                by_id.setdefault(pat.id, set()).add(
                    int(self.bank.primary_columns[p])
                )
            for e in self.bank.secondaries:
                by_id.setdefault(
                    self.bank.patterns[e.pattern_idx].id, set()
                ).add(int(e.column))
            for s in self.bank.sequences:
                by_id.setdefault(
                    self.bank.patterns[s.pattern_idx].id, set()
                ).update(int(c) for c in s.event_columns)
            self._breaker_map = by_id
            self._breaker_map_bank = self.bank
        cols: set[int] = set()
        for pid in pids:
            cols.update(self._breaker_map.get(pid, ()))
        # columns with no DFA are already host-evaluated unconditionally
        cols.difference_update(self._host_cols)
        return sorted(cols)

    # ----------------------------------------------------- device-step hooks
    # ShardedEngine overrides these two to swap in the shard_map program;
    # everything else in analyze() is shared.

    def _approx_sources_token(self) -> tuple:
        """The matcher objects the approx caches derive from, compared by
        IDENTITY — overridden by engines with several device programs."""
        return (self.matchers,)

    def _check_approx_caches(self) -> None:
        """Drop the lazily-built approx caches whenever the matcher tier
        assignment they were computed from is replaced (ADVICE r4: tests
        swap ``self._matchers``; a stale cache would skip the host
        re-verification of truncated columns)."""
        token = self._approx_sources_token()
        prev = self._approx_token
        if (
            prev is None
            or len(prev) != len(token)
            or any(a is not b for a, b in zip(prev, token))
        ):
            self._approx_pat_mask = None
            self._approx_sec = None
            self._approx_token = token

    def _approx_patterns(self) -> np.ndarray:
        """bool [n_patterns]: patterns whose device-side primary column
        OVER-matches (truncated >31-position bitglush alternatives —
        ops/match.py approx_cols) and whose flagged events must be
        re-verified with the exact host regex before they count."""
        self._check_approx_caches()
        if self._approx_pat_mask is None:
            mask = np.zeros(max(1, self.bank.n_patterns), dtype=bool)
            for cols, bank, offset in self._approx_col_sources():
                if not cols:
                    continue
                cset = set(cols)
                for p in range(bank.n_patterns):
                    if int(bank.primary_columns[p]) in cset:
                        mask[offset + p] = True
            self._approx_pat_mask = mask
        return self._approx_pat_mask

    def _approx_col_sources(self):
        """(approx_cols, bank, global pattern offset) triples —
        overridden by engines whose device programs run on different
        banks (pattern sharding)."""
        return [(getattr(self.matchers, "approx_cols", []), self.bank, 0)]

    def _approx_global_cols(self) -> set:
        """Engine-bank column indexes whose device tier over-matches, in
        GLOBAL column coordinates — overridden by pattern sharding to
        translate block-local indexes."""
        return set(getattr(self.matchers, "approx_cols", []))

    def _approx_secondaries(self):
        """[(pattern_idx, slot, column, effective_window)] — secondary
        entries whose column may over-match on device, and whose record
        distances therefore need the exact host repair. Slot order
        mirrors FusedStaticTables.pat_sec (declaration order within the
        pattern). Conservative across sharded engines: an entry whose
        column is exact in the block that ran it still repairs cleanly
        (the claimed line verifies and the distance stands)."""
        self._check_approx_caches()
        if self._approx_sec is None:
            cols = self._approx_global_cols()
            out = []
            if cols:
                slot_of: dict[int, int] = {}
                for e in self.bank.secondaries:
                    j = slot_of.get(e.pattern_idx, 0)
                    slot_of[e.pattern_idx] = j + 1
                    if e.column in cols:
                        out.append(
                            (
                                e.pattern_idx,
                                j,
                                e.column,
                                min(
                                    self.config.proximity_max_window,
                                    e.window,
                                ),
                            )
                        )
            self._approx_sec = out
        return self._approx_sec

    def _verify_approx(self, corpus: Corpus, recs):
        """Exact host repair for approximate (truncated) device columns.
        Runs in ``_prepare`` — OUTSIDE the serialization lock — and
        before the frequency read, so counts, scores, ordering, and
        assembly all see exactly the reference's match/factor set
        (AnalysisService.java:93-95, ScoringService.java:315-347).

        Stage 1 (primary roles): drop records whose approximate primary
        column flagged a line the exact host regex rejects.
        Stage 2 (secondary roles): a truncated secondary only feeds the
        proximity distances. The device min-distance d names at most two
        lines (record line ± d); if either truly matches, d is exact
        (true hits are a subset of device hits, so the true minimum is
        never smaller). Otherwise both were prefix-only false positives
        and the true distance is recovered by an outward host scan
        bounded by the entry's effective window (beyond it the factor is
        zero either way)."""
        import dataclasses

        from log_parser_tpu.ops.fused import NO_HIT

        m = recs.n_matches
        if m == 0:
            return recs
        mask = self._approx_patterns()
        if mask.any():
            pat = recs.pattern[:m].astype(np.int64)
            cand = np.nonzero(mask[pat])[0]
            keep = np.ones(m, dtype=bool)
            for i in cand:
                col = self.bank.columns[
                    int(self.bank.primary_columns[int(pat[i])])
                ]
                keep[i] = (
                    col.host.search(corpus.line(int(recs.line[i])))
                    is not None
                )
            if not keep.all():
                m = int(keep.sum())
                recs = dataclasses.replace(
                    recs,
                    n_matches=m,
                    line=recs.line[: len(keep)][keep],
                    pattern=recs.pattern[: len(keep)][keep],
                    sec_dist=recs.sec_dist[: len(keep)][keep],
                    seq_ok=recs.seq_ok[: len(keep)][keep],
                    ctx_counts=recs.ctx_counts[: len(keep)][keep],
                )

        sec_entries = self._approx_secondaries()
        if not sec_entries or m == 0:
            return recs
        by_pattern: dict[int, list] = {}
        for p, j, col, w in sec_entries:
            by_pattern.setdefault(p, []).append((j, col, w))
        pat = recs.pattern[:m]
        approx_mask = np.zeros(max(1, self.bank.n_patterns), dtype=bool)
        approx_mask[list(by_pattern)] = True
        rows = np.flatnonzero(approx_mask[pat.astype(np.int64)])
        if rows.size == 0:
            return recs
        n = corpus.n_lines
        sec_dist = None  # copy-on-write
        for i in rows:
            line = int(recs.line[i])
            for j, col, w in by_pattern[int(pat[i])]:
                d = int(recs.sec_dist[i, j] if sec_dist is None else sec_dist[i, j])
                if d >= NO_HIT or d > w:
                    continue  # out of window: zero factor either way
                host = self.bank.columns[col].host
                if (
                    line - d >= 0
                    and host.search(corpus.line(line - d)) is not None
                ) or (
                    line + d < n
                    and host.search(corpus.line(line + d)) is not None
                ):
                    continue  # the claimed distance is exact
                if sec_dist is None:
                    sec_dist = recs.sec_dist[:m].copy()
                nd = NO_HIT
                for k in range(d + 1, w + 1):
                    if (
                        line - k >= 0
                        and host.search(corpus.line(line - k)) is not None
                    ) or (
                        line + k < n
                        and host.search(corpus.line(line + k)) is not None
                    ):
                        nd = k
                        break
                sec_dist[i, j] = nd
        if sec_dist is None:
            return recs
        return dataclasses.replace(
            recs,
            sec_dist=np.concatenate([sec_dist, recs.sec_dist[m:]], axis=0)
            if recs.sec_dist.shape[0] > m
            else sec_dist,
        )

    def _corpus_min_rows(self) -> int:
        return 8

    def _note_kernel_dispatch(self, batch_rows: int, width: int | None = None,
                              n_rows: int | None = None,
                              batch_slots: int | None = None,
                              dummy_slots: int | None = None) -> dict | None:
        """Kernel-tier + device-utilization accounting for one device
        dispatch: did the union groups ride the Pallas kernel for this
        cube batch size, and what did the dispatch cost (padded rows,
        dummy-slot waste, transition-plane bytes) — folded into the
        per-tenant ``logparser_device_*`` families. Returns the dispatch
        attributes the span store records (``dispatch`` span vocabulary,
        obs/spans.py), or None pre-boot."""
        m = self._matchers
        if m is None:
            return None
        enabled = m.multidfa_use_pallas
        active = (
            enabled
            and m.multidfa_pallas_reason != "no_tile"
            and m.dfa_kernel_active(batch_rows)
        )
        geometry = m.dfa_kernel_geometry
        self.kernel_stats.note(
            batch_rows,
            active,
            enabled,
            m.multidfa_pallas_reason,
            geometry,
        )
        tier = "kernel" if active else "xla"
        attrs: dict = {"tier": tier, "rows": batch_rows,
                       "kernelReason": m.multidfa_pallas_reason}
        if width is not None:
            attrs["width"] = width
        slots = batch_slots or 1
        dummies = dummy_slots or 0
        padded_rows = batch_rows * slots
        dummy_rows = batch_rows * dummies
        if batch_slots is not None:
            attrs["batchSlots"] = slots
            attrs["dummySlots"] = dummies
            waste = dummies / slots if slots else 0.0
        elif n_rows is not None and batch_rows:
            # unbatched: the waste is the row padding past the real lines
            waste = (batch_rows - n_rows) / batch_rows
        else:
            waste = None
        if n_rows is not None:
            attrs["lines"] = n_rows
        if waste is not None:
            attrs["wasteRatio"] = round(waste, 4)
        if geometry:
            if geometry.get("planeBytes") is not None:
                attrs["planeBytes"] = geometry["planeBytes"]
            if geometry.get("vmemPerStep") is not None:
                attrs["vmemPerStep"] = geometry["vmemPerStep"]
        self.obs.note_dispatch(
            self.obs_tenant, tier, padded_rows=padded_rows,
            dummy_rows=dummy_rows, waste=waste,
        )
        return attrs

    def _run_device(self, enc, n_lines: int, om, ov, trace=NO_TRACE):
        out = self.fused.run(
            enc.u8, enc.lengths, n_lines, om, ov, k_hint=self._k_hint,
            trace=trace,
        )
        attrs = self._note_kernel_dispatch(
            enc.u8.shape[0], width=enc.u8.shape[1], n_rows=n_lines
        )
        if trace is not NO_TRACE and attrs:
            trace.span_attrs.update(attrs)
        return out

    def _run_cube(self, lines_u8, lengths, n_rows: int,
                  trace=NO_TRACE) -> np.ndarray:
        """Cube-only device program for the line-cache residual batch:
        pre-override match bits for ``n_rows`` independent lines (no
        extraction — that replays on the host from cached + fresh rows
        together, runtime/linecache.py). ``trace`` (a PhaseTrace) times
        the device stages and carries the dispatch span attributes."""
        out = self.fused.cube_rows(lines_u8, lengths, n_rows, trace=trace)
        attrs = self._note_kernel_dispatch(
            lines_u8.shape[0], width=lines_u8.shape[1], n_rows=n_rows
        )
        if trace is not NO_TRACE and attrs:
            attrs = {**attrs, "residual": True}
            trace.span_attrs.update(attrs)
        return out

    # ------------------------------------------------------- golden fallback

    @property
    def golden_fallback(self):
        """Lazy golden (pure host) analyzer sharing this engine's frequency
        state — the insurance path when a device batch fails (SURVEY.md
        §5.3; the reference has no equivalent)."""
        if self._golden is None:
            from log_parser_tpu.golden.engine import GoldenAnalyzer

            self._golden = GoldenAnalyzer(self.bank.pattern_sets, self.config)
            self._golden.frequency = self.frequency
        return self._golden

    def _golden_serve(self, data: PodFailureData) -> AnalysisResult:
        """Run one request on the golden host path with the shared
        frequency tracker rolled back on ANY failure — golden records
        matches as it runs, and a request that dies partway through must
        not leak partial counts. Caller holds the lock (or is otherwise
        serialized)."""
        saved_freq = self.frequency._save_state()
        try:
            return self.golden_fallback.analyze(data)
        except Exception:
            self.frequency._load_state(saved_freq)
            raise

    # ------------------------------------------- durable state + hot reload

    @contextlib.contextmanager
    def _request_scope(self):
        """Count this thread as an active request for the duration.
        Re-entrant per thread (batched submit degrades to pipelined, which
        would otherwise self-deadlock against a pending swap); a pending
        :meth:`apply_library` blocks NEW top-level entries until the swap
        completes, and the swap waits until the count reaches zero."""
        local = self._scope_local
        if getattr(local, "depth", 0) > 0:
            local.depth += 1
            try:
                yield
            finally:
                local.depth -= 1
            return
        with self._quiesce_cv:
            while self._swap_pending:
                self._quiesce_cv.wait()
            self._active_requests += 1
        local.depth = 1
        try:
            yield
        finally:
            local.depth = 0
            with self._quiesce_cv:
                self._active_requests -= 1
                if self._active_requests == 0:
                    self._quiesce_cv.notify_all()

    def attach_journal(
        self,
        state_dir: str,
        *,
        fsync_ms: float = 50.0,
        snapshot_every: int = 512,
        wall=None,
    ):
        """Make frequency state durable: recover snapshot + journal tail
        from ``state_dir``, swap in a journaling tracker, start group-fsync
        and snapshot maintenance, and write the boot-baseline snapshot.
        Registers a best-effort ``atexit`` flush for non-serve embeddings
        (the serve path additionally flushes on SIGTERM drain).
        ``wall`` (tests) overrides the journal's wall clock so replayed
        ages are deterministic."""
        import atexit

        from log_parser_tpu.runtime.journal import (
            DurableFrequencyTracker,
            FrequencyJournal,
        )

        kw = {} if wall is None else {"wall": wall}
        journal = FrequencyJournal(
            state_dir, fsync_ms=fsync_ms, snapshot_every=snapshot_every, **kw
        )
        tracker = DurableFrequencyTracker(
            self.config, self.frequency.clock, journal
        )
        pre = self.frequency._save_state()
        if pre:
            # warm attach (tests, embeddings): fold pre-attach in-memory
            # entries into the recovered state; the _load_state barrier
            # makes the merged state the journal's new truth
            merged = tracker._save_state()
            for pid, ts in pre.items():
                merged[pid] = sorted(merged.get(pid, []) + list(ts))
            tracker._load_state(merged)
        with self.state_lock:
            self.frequency = tracker
            if self._golden is not None:
                self._golden.frequency = tracker
        self.journal = journal
        journal.start(tracker.snapshot, self.state_lock)
        # boot baseline: the recovered state becomes one durable snapshot
        # and the replayed tail is truncated away
        journal.snapshot_now()
        atexit.register(journal.flush)
        return journal

    def _install_library(self, source: "AnalysisEngine") -> None:
        """Transplant every library-derived component from ``source``
        (a fully-built engine of the same class family). Caller holds the
        state lock with the request gate quiesced. Subclasses with extra
        device programs (pattern sharding) extend this."""
        self.bank = source.bank
        self.tables = source.tables
        self._matchers = source._matchers
        self._fused = source._fused
        self._host_cols = source._host_cols
        self._device_cols = source._device_cols
        self._host_pref_cols = source._host_pref_cols
        self._host_slow_cols = source._host_slow_cols
        self._host_prefilter = source._host_prefilter
        self._golden = None  # lazily rebuilt against the new bank
        self._approx_pat_mask = None
        self._approx_sec = None
        self._approx_token = None
        self._k_hint = 0

    def apply_library(
        self,
        source: "AnalysisEngine",
        timeout_s: float = 30.0,
        pre_swap: Callable[[], None] | None = None,
    ) -> int:
        """Atomically swap this engine onto ``source``'s pattern library.

        Admission of new requests pauses, in-flight (and already-enqueued
        batched) requests drain on the OLD banks, then the swap happens
        under the state lock; frequency entries for pattern ids surviving
        into the new library carry over, the rest are dropped (their
        windowed history is meaningless without the pattern). ``pre_swap``
        runs inside the quiesced critical section — the distributed
        coordinator broadcasts the reload there so no request broadcast
        can interleave. Returns the new reload epoch."""
        deadline = pclock.mono() + timeout_s
        with self._quiesce_cv:
            if self._swap_pending:
                raise RuntimeError("another pattern reload is in progress")
            self._swap_pending = True
            try:
                while self._active_requests > 0:
                    remaining = deadline - pclock.mono()
                    if remaining <= 0:
                        raise TimeoutError(
                            f"reload quiesce timed out after {timeout_s:g}s "
                            f"({self._active_requests} request(s) in flight)"
                        )
                    self._quiesce_cv.wait(remaining)
            except BaseException:
                self._swap_pending = False
                self._quiesce_cv.notify_all()
                raise
        try:
            with self.state_lock:
                if pre_swap is not None:
                    pre_swap()
                self._install_library(source)
                survivors = set(self.bank.freq_ids)
                for pid in list(self.frequency._frequencies):
                    if pid not in survivors:
                        del self.frequency._frequencies[pid]
                if self.batcher is not None:
                    from log_parser_tpu.ops.fused import FusedBatchMatchScore

                    self.batcher.program = FusedBatchMatchScore(self.fused)
                if self.line_cache is not None:
                    # wholesale epoch invalidation INSIDE the quiesced
                    # swap: no request is in flight, so no populate racing
                    # the flush can resurrect an old library's bits — a
                    # stale hit across a pattern swap is structurally
                    # impossible (tests/test_linecache.py pins it)
                    self.line_cache.flush(n_columns=self.bank.n_columns)
                self.reload_epoch += 1
                if self.journal is not None:
                    # the carry-over pruning above bypassed the tracker's
                    # journaling overrides; one barrier records the truth
                    self.journal.append_barrier(self.frequency.snapshot())
        finally:
            with self._quiesce_cv:
                self._swap_pending = False
                self._quiesce_cv.notify_all()
        return self.reload_epoch

    # --------------------------------------------------------------- analyze

    def analyze(
        self, data: PodFailureData, request_id: str | None = None
    ) -> AnalysisResult:
        """Sequential analyze — the single-caller entry point (tests,
        benches, the golden-parity harness). Transport front-ends that
        serve concurrent requests use :meth:`analyze_pipelined`.
        ``request_id``: the propagated trace id (X-Request-Id) this
        request carries through the obs trace ring."""
        return self._analyze(data, _NULL_LOCK, request_id)

    def analyze_pipelined(
        self, data: PodFailureData, request_id: str | None = None
    ) -> AnalysisResult:
        """Thread-safe analyze: ingest + device execution (the prepare
        phase, which touches no shared mutable state) runs OUTSIDE
        ``state_lock``, so request N+1's ingest/device work overlaps
        request N's host finalize — the frequency read-before-record
        boundary is the only true serialization point (SURVEY.md §5.2;
        the reference serializes nothing and data-races instead)."""
        return self._analyze(data, self.state_lock, request_id)

    def enable_batching(self, wait_ms: float = 2.0, batch_max: int = 8):
        """Attach and start the cross-request micro-batching scheduler
        (runtime/batcher.py): concurrent ``analyze_batched`` calls coalesce
        into one padded vmapped device batch per shape bucket. Only the
        single-device fused program supports the leading request axis —
        sharded/distributed engines keep the unbatched path."""
        from log_parser_tpu.runtime.batcher import MicroBatcher

        self.batcher = MicroBatcher(
            self, wait_ms=wait_ms, batch_max=batch_max
        ).start()
        return self.batcher

    def enable_line_cache(self, mb: float = DEFAULT_LINE_CACHE_MB):
        """Attach the exact-match line cache (runtime/linecache.py):
        per-line pre-override match-bit rows keyed by the
        ingest-normalized line bytes themselves. Repeat lines skip the
        match cube; novel lines go to the device as a compacted residual
        batch and populate the cache on the way back. Single-device
        engines only — the residual program is the full-bank cube
        (sharded/distributed engines keep the uncached path; the serve
        layer gates the flag exactly like micro-batching)."""
        self.line_cache = LineCache(
            self.bank.n_columns, int(float(mb) * 1024 * 1024)
        )
        return self.line_cache

    def enable_shadow(self, rate: float, seed: int | None = None):
        """Attach and start the online shadow verifier: ``rate`` of
        served device/batched requests are re-run on the golden host path
        off the hot path (cloned frequency state, never double-counted)
        and compared at 1e-9; a divergence trips the divergent pattern's
        breaker (see :class:`ShadowVerifier`). ``seed`` pins the sampling
        RNG (``LOG_PARSER_TPU_SHADOW_SEED`` when None)."""
        if seed is None:
            seed = int(os.environ.get("LOG_PARSER_TPU_SHADOW_SEED", "0"))
        if self.shadow is not None:
            self.shadow.close()
        self.shadow = ShadowVerifier(self, rate, seed=seed).start()
        return self.shadow

    def enable_miner(
        self,
        *,
        mode: str = "review",
        sample: float = 1.0,
        min_support: int = 8,
        state_dir: str | None = None,
        capacity: int | None = None,
        shadow_rate: float | None = None,
        stability: int = 4,
        autostart: bool = True,
    ):
        """Attach the template miner (mining/): line-cache misses feed a
        sampled bounded tap, a background thread clusters them into
        token templates, and stable templates become candidate patterns
        behind the admission pipeline (``--mined-patterns``). Requires
        the line cache — without a miss stream there is nothing to mine
        (the serve layer gates the flag accordingly). ``autostart=False``
        leaves the worker unstarted so tests and tools drive
        :meth:`TemplateMiner.pump` deterministically."""
        from log_parser_tpu.mining.miner import TemplateMiner
        from log_parser_tpu.runtime.linecache import DEFAULT_TAP_CAPACITY

        if self.line_cache is None:
            raise RuntimeError("enable_miner requires enable_line_cache first")
        if self.miner is not None:
            self.miner.stop()
        if capacity is None:
            capacity = int(
                os.environ.get(
                    "LOG_PARSER_TPU_MINER_TAP_CAPACITY", str(DEFAULT_TAP_CAPACITY)
                )
            )
        kwargs = {} if shadow_rate is None else {"shadow_rate": shadow_rate}
        self.miner = TemplateMiner(
            self,
            mode=mode,
            sample=sample,
            min_support=min_support,
            state_dir=state_dir,
            capacity=capacity,
            stability=stability,
            **kwargs,
        )
        if autostart:
            self.miner.start()
        return self.miner

    def analyze_batched(
        self,
        data: PodFailureData,
        deadline_ms: float | None = None,
        request_id: str | None = None,
    ) -> AnalysisResult:
        """Thread-safe analyze through the micro-batcher: this request may
        share its device step with concurrent callers, with per-request
        results, fallback, and frequency semantics identical to
        :meth:`analyze_pipelined` (which it degrades to when batching is
        off). ``deadline_ms``: remaining budget — a tight deadline pulls
        this request's batch flush earlier."""
        batcher = self.batcher
        if batcher is None:
            return self.analyze_pipelined(data, request_id=request_id)
        return batcher.submit(data, deadline_ms, request_id=request_id)

    def analyze_host_routed(
        self, data: PodFailureData, request_id: str | None = None
    ) -> AnalysisResult:
        """Serve one request from the golden host path because the
        admission gate routed it there under pressure (ladder rung 2,
        serve/admission.py) — NOT because anything failed. Same frequency
        state, same rollback-on-failure invariant as the error fallback,
        separate counter."""
        start = pclock.mono()
        with self._request_scope(), self.state_lock:
            self.host_routed_count += 1
            result = self._golden_serve(data)
        self._note_golden(start, "host", request_id, "ok")
        return result

    def _note_golden(
        self, start: float, route: str, request_id: str | None,
        outcome: str, error: str | None = None,
    ) -> None:
        """Ring entry for a golden-host-served request (host-routed,
        quarantined, fallback) — no device phases to report, but the
        request id and wall time still belong in the obs ring."""
        trace = PhaseTrace()
        trace.route = route
        trace.request_id = request_id
        self.obs.note_served(
            trace, start, self.obs_tenant, outcome=outcome, error=error
        )

    def _analyze(
        self, data: PodFailureData, lock, request_id: str | None = None
    ) -> AnalysisResult:
        with self._request_scope():
            return self._analyze_in_scope(data, lock, request_id)

    def _analyze_in_scope(
        self, data: PodFailureData, lock, request_id: str | None = None
    ) -> AnalysisResult:
        start = pclock.mono()
        fp = self._quarantine_check(data)
        if fp is not None:
            with lock:
                result = self._serve_quarantined(data, fp)
            self._note_golden(start, "device", request_id, "quarantined")
            return result
        try:
            prepared = self._prepare(data)
        except Exception as exc:
            with lock:
                return self._serve_fallback(
                    data, exc, request_id=request_id, start=start
                )
        prepared.trace.request_id = request_id
        # lock WAIT is a traced phase: under concurrency the finish
        # phases serialize here, and a latency decomposition that omits
        # the wait would misattribute it to HTTP transport.
        # ``lock`` may be a real Lock (pipelined) or a nullcontext
        # (bare analyze), so enter/exit the context protocol directly.
        with prepared.trace.phase("lock_wait"):
            lock.__enter__()
        try:
            # roll frequency state back on ANY failure: a partially-run
            # request (e.g. one that died after recording its matches)
            # must not leave the tracker double-counted — whether golden
            # re-serves it or the client retries after a 500
            saved_freq = self.frequency._save_state()
            try:
                return self._finish(prepared)
            except Exception as exc:
                self.frequency._load_state(saved_freq)
                return self._serve_fallback(
                    data, exc,
                    request_id=request_id, start=prepared.start,
                    route=prepared.trace.route,
                )
        finally:
            lock.__exit__(None, None, None)

    def _quarantine_check(self, data: PodFailureData) -> str | None:
        """The request's fingerprint when it is actively quarantined,
        else None. The sha256 is only computed once any fingerprint is
        being tracked — the steady state pays one counter read."""
        q = self.quarantine
        if q is None or not q._table:
            return None
        fp = quarantine_fingerprint(data.logs or "")
        return fp if q.check(fp) else None

    def _serve_quarantined(self, data: PodFailureData, fp: str) -> AnalysisResult:
        """Serve a quarantined request straight from the golden host path
        — it never reaches the device step, the watchdog breaker, or a
        shared batch. Only when golden ALSO fails does the caller get a
        structured 429 + Retry-After (QuarantineRejected). Caller holds
        the lock."""
        from log_parser_tpu.runtime.quarantine import QuarantineRejected

        try:
            result = self._golden_serve(data)
        except Exception as exc:
            self.quarantine.note_rejected()
            raise QuarantineRejected(
                fp, self.quarantine.retry_after(fp)
            ) from exc
        self.quarantine.note_served()
        return result

    def _strike_worthy(self, exc: Exception) -> bool:
        """Does this device-classified failure accuse the REQUEST? Only
        organic CRASHES strike: injected backend chaos (device_raise)
        would quarantine innocent traffic, and a hang — circuit-open
        short-circuit or an actual watchdog timeout — accuses the
        BACKEND, whose containment is the watchdog breaker (an innocent
        request in flight when the device wedges, or the half-open probe
        itself, must stay device-eligible once the backend recovers).
        The injected poison pill (InjectedPoisonFault, the ``quarantine``
        fault site) is the deliberate exception — it simulates an
        organic poison."""
        if isinstance(exc, faults.InjectedPoisonFault):
            return True
        if isinstance(exc, faults.InjectedFault):
            return False
        if isinstance(exc, DeviceHungError):
            return False
        return True

    def _serve_fallback(
        self,
        data: PodFailureData,
        exc: Exception,
        request_id: str | None = None,
        start: float | None = None,
        route: str = "device",
    ) -> AnalysisResult:
        """Serve ``data`` from the golden host path if ``exc`` is a device
        failure and the fallback is enabled; re-raise otherwise. Caller
        holds the lock (frequency state is read and mutated here)."""
        if not self.fallback_to_golden or not is_device_error(exc):
            # logic bugs always propagate; device failures degrade to
            # the golden host path only when the fallback is enabled
            raise exc
        import logging

        self.fallback_count += 1
        if self._strike_worthy(exc):
            fp = quarantine_fingerprint(data.logs or "")
            if self.quarantine.strike(fp):
                logging.getLogger(__name__).warning(
                    "Quarantined request fingerprint %s… for %gs after "
                    "%d device-failure strike(s); repeats serve from the "
                    "host path without touching the device",
                    fp[:12],
                    self.quarantine.ttl_s,
                    self.quarantine.threshold,
                )
        logging.getLogger(__name__).exception(
            "Device batch failed (fallback #%d); serving this request "
            "from the golden host path",
            self.fallback_count,
        )
        # device-side observability does not describe this request
        self.last_trace = None
        self.last_finalized = None
        result = self._golden_serve(data)
        self._note_golden(
            start if start is not None else pclock.mono(),
            route, request_id, "fallback", error=type(exc).__name__,
        )
        return result

    def _prepare(self, data: PodFailureData) -> "_Prepared":
        """Ingest + overrides + the device batch: everything before the
        frequency read. Touches no shared mutable state beyond the
        ``_k_hint`` perf hint — safe to run concurrently with another
        request's :meth:`_finish`."""
        start = pclock.mono()
        trace = PhaseTrace()
        with trace.phase("ingest"):
            faults.fire("ingest")  # conlint: contained-by-caller (serve handler / batcher bisection)
            corpus = Corpus(data.logs or "", min_rows=self._corpus_min_rows())
            enc = corpus.encoded

        with trace.phase("overrides"):
            overrides = self._overrides(corpus)
        om, ov = overrides if overrides is not None else (None, None)

        cache = self.line_cache
        if cache is not None:
            return self._prepare_cached(data, start, trace, corpus, om, ov, cache)

        def _device_step():
            # chaos points INSIDE the watchdog worker: an injected hang
            # exercises the timeout/breaker exactly like a wedged backend;
            # the quarantine site is keyed by this request's content so a
            # match= spec can poison exactly one request
            faults.fire("quarantine", key=data.logs or "")  # conlint: contained-by-caller (watchdog.run)
            faults.fire("device")  # conlint: contained-by-caller (watchdog.run)
            return self._run_device(enc, corpus.n_lines, om, ov, trace=trace)

        with trace.phase("device"):
            recs = self.watchdog.run(_device_step)
        # capacity hint tracks the RAW device match count (the buffer the
        # device actually needs), before approx verification drops rows
        self._k_hint = recs.n_matches
        with trace.phase("verify"):
            recs = self._verify_approx(corpus, recs)
        return _Prepared(start, trace, corpus, recs, data)

    def _prepare_cached(
        self, data, start, trace, corpus, om, ov, cache: LineCache
    ) -> "_Prepared":
        """The routing-tier prepare path: per-line cache lookup, one
        compacted residual cube dispatch for the unique misses, then a
        sparse host replay of the extraction. A request whose lines are
        ALL cache hits never reaches the device step at all — it cannot
        trip the watchdog, cannot strike quarantine, and costs no device
        dispatch. The replay reads each unique line's set columns from its
        packed cache row or readback row, fans them out to the request's
        lines as ``(line, col)`` coordinates, splices the override cube in
        and builds the records from those coordinates
        (``linecache.records_from_hits``): no ``[n, C]`` matrix, and a
        cost that follows the hits. Parity with :meth:`_prepare` is exact:
        the cache holds PRE-override bit rows (width-independent — zero
        padding is automaton-neutral and ``needs_host`` lines are never
        populated), the request's override cube is re-applied here, and
        the replay mirrors the device extraction bit-for-bit."""
        enc = corpus.encoded
        n = corpus.n_lines
        with trace.phase("cache"):
            # dedup to unique lines FIRST: one device row per distinct
            # novel line (the in-request half of the dedup; the batcher
            # dedups across a whole flush the same way). Within one
            # request duplicate content always shares one needs_host
            # verdict (same bytes, same device width), so slot-level
            # bookkeeping indexed at the first appearance is exact.
            line_slot, rep_lines, keys, counts = dedup_slots(corpus)
            found = cache.lookup(keys, counts)
            miss_slots = np.flatnonzero(found.row < 0)

        fresh = None
        if miss_slots.size:
            miss_lines = rep_lines[miss_slots]
            u = miss_lines.size
            miner = self.miner
            if miner is not None:
                # miss-stream tap: one non-blocking bounded-queue offer
                # per unique novel line (sampling + drop accounting live
                # in the tap); the mining work itself happens on the
                # miner thread, never here
                cts = counts[miss_slots]
                for j, i in enumerate(miss_lines.tolist()):
                    miner.tap.offer(corpus.line_key_bytes(i), int(cts[j]))
            pad = _pad_rows(u, self._corpus_min_rows())
            res_u8 = np.zeros((pad, enc.u8.shape[1]), dtype=np.uint8)
            res_len = np.zeros(pad, dtype=np.int32)
            res_u8[:u] = enc.u8[miss_lines]
            res_len[:u] = enc.lengths[miss_lines]

            def _device_step():
                # same chaos points as the uncached path — the residual
                # IS this request's device step, so a keyed poison spec
                # fires (and strikes) exactly as before
                faults.fire("quarantine", key=data.logs or "")  # conlint: contained-by-caller (watchdog.run)
                faults.fire("device")  # conlint: contained-by-caller (watchdog.run)
                return self._run_cube(res_u8, res_len, u, trace=trace)

            with trace.phase("device"):
                fresh = self.watchdog.run(_device_step)[:u]
            cache.note_residual(u, int(counts[miss_slots].sum()) - u)
            # needs_host lines are never stored (their keys are not
            # storable): their truncated/replaced encode is width-
            # dependent, so their device bits are not a function of the
            # line content alone (their columns are overridden below)
            with trace.stage("cache.populate"):
                cache.populate(keys.take(miss_slots), fresh, found)

        with trace.phase("extract"):
            # the override splice (host-only columns, needs_host lines,
            # OPEN-breaker patterns) lands on cached and fresh rows alike,
            # which is what makes a breaker trip an exact per-pattern
            # invalidation
            line, col = request_hits(
                slot_hits(found, miss_slots, fresh),
                line_slot, n, om, ov,
            )
            recs = records_from_hits(line, col, n, self.bank, self.tables)
            self.obs.note_extract_hits(line.size, self.obs_tenant)
        self._k_hint = recs.n_matches
        with trace.phase("verify"):
            recs = self._verify_approx(corpus, recs)
        return _Prepared(start, trace, corpus, recs, data)

    def _finish(self, prepared: "_Prepared") -> AnalysisResult:
        """Frequency read → exact-f64 finalize → frequency record →
        assemble. Serialized under ``state_lock`` by concurrent callers:
        the read-before-record ordering (ScoringService.java:84-88) is
        only meaningful per-request-atomically."""
        start, trace, corpus, recs = (
            prepared.start,
            prepared.trace,
            prepared.corpus,
            prepared.recs,
        )
        # shadow sampling decides (and captures the pre-record tracker
        # state) HERE, under the lock: the golden re-run must read exactly
        # the windowed counts this request's finalize reads, cloned so it
        # can never double-count the live tracker
        shadow = self.shadow
        shadow_state = None
        if shadow is not None and prepared.data is not None and shadow.should_sample():
            shadow_state = self.frequency._save_state()
        # windowed frequency counts at batch start (pruned by the tracker);
        # "entry exists" is tracked separately — an expired window still has
        # an entry and takes the formula path, not the null early-return
        freq_base = np.zeros(max(1, self.bank.n_freq_slots), dtype=np.float64)
        freq_exists = np.zeros(max(1, self.bank.n_freq_slots), dtype=bool)
        with trace.stage("engine.frequency"):
            for slot, pid in enumerate(self.bank.freq_ids):
                freq_base[slot] = self.frequency.get_windowed_count(pid)
                freq_exists[slot] = self.frequency.has_entry(pid)

        with trace.phase("finalize"):
            faults.fire("finalize")  # conlint: contained-by-caller (serve handler / batcher bisection)
            fin = finalize_batch(
                self.bank, self.tables, self.config, recs, corpus.n_lines,
                freq_base, freq_exists,
            )

        # record this batch's matches (after the read — ScoringService.java:84-88);
        # bulk per slot: one list extend instead of count Python calls
        # inside the only lock every concurrent request shares. Zero-count
        # slots are skipped wholesale: record_pattern_matches(pid, 0)
        # early-returns without creating an entry, so on hit-heavy traffic
        # (few matched patterns per batch) this touches matched slots only
        with trace.stage("engine.frequency"):
            sbc = np.asarray(fin.slot_batch_counts[: self.bank.n_freq_slots])
            for slot in np.flatnonzero(sbc).tolist():
                self.frequency.record_pattern_matches(
                    self.bank.freq_ids[slot], int(sbc[slot])
                )

        # records are already in discovery order (line-major, then pattern)
        with trace.phase("assemble"):
            # one bulk ndarray→Python conversion per column instead of
            # three per-element __getitem__/int()/float() calls per event
            # (``.tolist()`` yields the same Python ints/floats those
            # casts produce, element for element)
            events: list[MatchedEvent] = []
            patterns = self.bank.patterns
            for line_idx, pat_i, score in zip(
                fin.line.tolist(), fin.pattern.tolist(), fin.scores.tolist()
            ):
                pattern = patterns[pat_i]
                events.append(
                    MatchedEvent(
                        line_number=line_idx + 1,
                        matched_pattern=pattern,
                        context=extract_context(corpus, line_idx, pattern),
                        score=score,
                    )
                )

            result = AnalysisResult(
                events=events,
                analysis_id=str(uuid.uuid4()),
                metadata=build_metadata(start, corpus.n_lines, self.bank.pattern_sets),
                summary=build_summary(events),
            )
        self.last_trace = trace
        # bounded history for latency decomposition (bench_latency emits
        # device-phase percentiles beside the HTTP p99, so a reader can
        # split engine time from transport — VERDICT r4 #7); deque
        # appends are thread-safe under concurrent _finish callers
        self.trace_history.append(trace)
        self.last_finalized = fin
        # per-phase histograms + the trace-ring entry for this request —
        # fed from the SAME PhaseTrace /trace/last exposes, so the two
        # surfaces can never disagree
        self.obs.note_served(
            trace, start, self.obs_tenant, n_lines=corpus.n_lines
        )
        if shadow_state is not None:
            shadow.submit(prepared.data, shadow_state, result)
        return result


class ShadowVerifier:
    """Online device-vs-golden verification off the hot path.

    The offline parity harness only proves parity for corpora someone
    thought to run; a silent device-vs-golden divergence on production
    traffic (a mistranslated regex corner, a tier bug on one byte
    sequence) would otherwise go unnoticed until the next offline run.
    This worker samples ``rate`` of served requests (decided under
    ``state_lock`` by a dedicated seeded RNG, so a sweep replays the same
    sampling decisions) and re-runs each on a golden analyzer whose
    frequency tracker is a CLONE of the pre-record state the device
    request read — the live tracker is never touched, so shadowing adds
    zero frequency drift and batched/unbatched scores stay bit-identical
    to a no-shadow run.

    Comparison is per event ``(line_number, pattern id, score)`` at 1e-9.
    On divergence: counters move (``/trace/last`` → ``shadow``),
    ``/q/health`` reports a DEGRADED ``shadow`` check, and the divergent
    pattern's breaker opens (:class:`PatternBreakerBoard`) — that pattern
    serves from the exact host regex while everything else stays
    on-device, then half-opens after the cool-down and the next forced
    shadow comparison closes or re-opens it.

    The ``shadow`` fault site fires in the worker per comparison; an
    injected raise is treated as a synthetic divergence on the request's
    first matched pattern (chaos drills the breaker ladder without
    needing a genuinely mistranslated pattern).
    """

    def __init__(
        self,
        engine: AnalysisEngine,
        rate: float,
        seed: int = 0,
        queue_max: int = 64,
        tolerance: float = 1e-9,
    ):
        self.engine = engine
        self.rate = min(1.0, max(0.0, float(rate)))
        self.tolerance = tolerance
        self.queue_max = max(1, int(queue_max))
        self._rng = random.Random(seed)
        self._cond = threading.Condition()
        self._jobs: deque = deque()
        self._pending = 0  # queued + in-flight comparisons
        self._closed = False
        self._thread: threading.Thread | None = None
        # counters (guarded by _cond; GET /trace/last "shadow")
        self.sampled = 0
        self.forced = 0
        self.compared = 0
        self.divergences = 0
        self.dropped = 0
        self.errors = 0
        self.last_divergence: dict | None = None
        # golden clone, rebuilt whenever the engine's bank is swapped
        self._golden = None
        self._golden_bank = None

    def start(self) -> "ShadowVerifier":
        self._thread = threading.Thread(
            target=self._worker, name="shadow-verifier", daemon=True
        )
        self._thread.start()
        return self

    def close(self, timeout_s: float = 10.0) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout_s)

    # ------------------------------------------------------------ sampling

    def should_sample(self) -> bool:
        """Called under ``state_lock`` (one RNG draw per served request —
        deterministic under a seed). A pending half-open breaker forces
        the sample so the probe actually resolves."""
        with self._cond:
            if self.engine.breakers.probe_pending():
                self.forced += 1
                self.sampled += 1
                return True
            if self.rate >= 1.0 or self._rng.random() < self.rate:
                self.sampled += 1
                return True
            return False

    def submit(self, data, freq_state: dict, result) -> None:
        """Hand one served request to the worker. Non-blocking: a full
        queue drops the sample (counted) rather than stalling serving."""
        events = [
            (e.line_number, e.matched_pattern.id, e.score)
            for e in result.events
        ]
        with self._cond:
            if self._closed:
                return
            if len(self._jobs) >= self.queue_max:
                self.dropped += 1
                return
            self._jobs.append((data, freq_state, events))
            self._pending += 1
            self._cond.notify_all()

    def drain(self, timeout_s: float = 30.0) -> bool:
        """Block until every submitted comparison has been processed
        (tests and sweeps; serving never calls this)."""
        with self._cond:
            return self._cond.wait_for(
                lambda: self._pending == 0, timeout_s
            )

    # -------------------------------------------------------------- worker

    def _worker(self) -> None:
        while True:
            with self._cond:
                while not self._jobs and not self._closed:
                    self._cond.wait()
                if not self._jobs and self._closed:
                    return
                data, freq_state, device_events = self._jobs.popleft()
            try:
                self._compare(data, freq_state, device_events)
            except Exception:
                import logging

                with self._cond:
                    self.errors += 1
                logging.getLogger(__name__).exception(
                    "shadow verification failed (the request was already "
                    "served; this affects only the comparison)"
                )
            finally:
                with self._cond:
                    self._pending -= 1
                    self._cond.notify_all()

    def _golden_clone(self):
        from log_parser_tpu.golden.engine import GoldenAnalyzer

        bank = self.engine.bank
        if self._golden is None or self._golden_bank is not bank:
            self._golden = GoldenAnalyzer(
                bank.pattern_sets,
                self.engine.config,
                clock=self.engine.frequency.clock,
            )
            self._golden_bank = bank
        return self._golden

    def _compare(self, data, freq_state, device_events) -> None:
        synthetic = False
        try:
            faults.fire("shadow")
        except faults.InjectedFault:
            synthetic = True
        diverged: set[str] = set()
        seen: set[str] = {pid for _, pid, _ in device_events}
        if synthetic:
            # chaos: declare the request's first matched pattern divergent
            if device_events:
                diverged.add(device_events[0][1])
        else:
            golden = self._golden_clone()
            from log_parser_tpu.golden.engine import GoldenFrequencyTracker

            tracker = GoldenFrequencyTracker(
                self.engine.config, clock=self.engine.frequency.clock
            )
            tracker._load_state(freq_state)
            golden.frequency = tracker
            gresult = golden.analyze(data)
            dev = {(ln, pid): s for ln, pid, s in device_events}
            gol = {
                (e.line_number, e.matched_pattern.id): e.score
                for e in gresult.events
            }
            seen |= {pid for _, pid in gol}
            for key in dev.keys() | gol.keys():
                if key not in dev or key not in gol:
                    diverged.add(key[1])
                elif abs(dev[key] - gol[key]) > self.tolerance:
                    diverged.add(key[1])
        with self._cond:
            self.compared += 1
            if diverged:
                self.divergences += 1
                self.last_divergence = {
                    "patterns": sorted(diverged),
                    "synthetic": synthetic,
                }
        if diverged:
            import logging

            logging.getLogger(__name__).error(
                "Shadow divergence on pattern(s) %s%s — opening per-"
                "pattern breaker(s); those patterns serve from the host "
                "regex until a clean half-open probe",
                sorted(diverged),
                " (synthetic, injected)" if synthetic else "",
            )
            for pid in diverged:
                self.engine.breakers.trip(pid)
        self.engine.breakers.resolve(seen, diverged)

    # ------------------------------------------------------- observability

    def stats(self) -> dict:
        with self._cond:
            payload = {
                "rate": self.rate,
                "sampled": self.sampled,
                "forced": self.forced,
                "compared": self.compared,
                "divergences": self.divergences,
                "dropped": self.dropped,
                "errors": self.errors,
                "queueDepth": len(self._jobs),
                "breakers": self.engine.breakers.stats(),
            }
            if self.last_divergence is not None:
                payload["lastDivergence"] = self.last_divergence
            return payload
