"""Per-phase timing + device profiler hooks.

The reference's only timing surface is the wall-clock ``processingTimeMs``
stamped into result metadata (AnalysisService.java:51,169); it has no
tracing or profiling subsystem (SURVEY.md §5.1). This framework keeps the
metadata field for API parity and adds:

- :class:`PhaseTrace` — cheap named-phase wall timers (ingest / overrides /
  device / finalize / assemble) collected per request, with the thread's
  CPU time beside each phase's wall and finer *stages* (transport, device
  copies, frequency state) that sit inside or between the phases; the
  engine exposes its latest as ``engine.last_trace``.
- :func:`annotation` — a host span on the JAX profiler's clock, so a
  device trace shows what the host was doing in each idle gap. Every
  phase and stage opens one under a constant name (``engine.<phase>``,
  or the stage's own name).
- :func:`profiler_trace` — context manager wrapping ``jax.profiler.trace``
  (TensorBoard-viewable device traces) gated by an output directory, so the
  hot path carries zero overhead when profiling is off.
"""

from __future__ import annotations

import contextlib
import sys
import threading
import time

_NULL = contextlib.nullcontext()


def annotation(name: str):
    """``jax.profiler.TraceAnnotation(name)``, or a no-op in a process
    that has not imported JAX (the router): an annotation never imports
    it. ``name`` must be a constant string."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NULL
    return jax.profiler.TraceAnnotation(name)


class PhaseTrace:
    """Named wall-clock phase timers for one request.

    Thread-safe: the micro-batcher (runtime/batcher.py) accumulates into a
    request's trace from both the submitting thread (ingest/overrides) and
    the scheduler thread (batch_wait/device/finish phases), so the
    read-modify-write accumulation is guarded — an unguarded ``get()+set``
    would drop one side's time under interleaving.

    Phases partition the engine's part of a request and feed
    ``logparser_phase_seconds``; a phase timed by :meth:`phase` also
    records the thread's CPU seconds (``logparser_phase_cpu_seconds_total``).
    Stages (:meth:`stage`) are finer spans that nest inside a phase or
    sit between phases, and feed ``logparser_stage_seconds`` only."""

    def __init__(self) -> None:
        self.phases: dict[str, float] = {}
        self.cpu: dict[str, float] = {}
        self.stages: dict[str, float] = {}
        self._lock = threading.Lock()
        # request identity for the obs trace ring (log_parser_tpu/obs):
        # the propagated X-Request-Id and the route that served it.
        # Write-once by the thread that creates/submits the request,
        # before any cross-thread handoff — no lock needed.
        self.request_id: str | None = None
        self.route: str = "device"
        # span-store carriers (obs/spans.py): the batcher's scheduler
        # thread appends the flush back-link and dispatch attributes
        # here; Obs.note_served folds them into the committed request
        # span. list.append / dict.update are single-bytecode atomic
        # and the reader runs strictly after demux hands the request
        # back, so no lock is needed.
        self.links: list = []
        self.span_attrs: dict = {}

    @contextlib.contextmanager
    def phase(self, name: str):
        # ``engine.<phase>``: the same few names on every request, with
        # no per-request metadata
        with annotation("engine." + name):
            t0 = time.perf_counter()
            c0 = time.thread_time()
            try:
                yield
            finally:
                self.add(name, time.perf_counter() - t0,
                         cpu=time.thread_time() - c0)

    def add(self, name: str, seconds: float, cpu: float | None = None) -> None:
        """Accumulate ``seconds`` into ``name`` (for callers that measured
        a span themselves — e.g. one shared device step attributed to every
        request of a coalesced batch). Such a phase ran on no thread of
        its own, so it records no CPU unless ``cpu`` is given."""
        with self._lock:
            self.phases[name] = self.phases.get(name, 0.0) + seconds
            if cpu is not None:
                self.cpu[name] = self.cpu.get(name, 0.0) + cpu

    @contextlib.contextmanager
    def stage(self, name: str):
        """Annotate and time one stage; ``name`` is a constant string
        (``device.upload``, ``transport.read``, ...)."""
        with annotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.add_stage(name, time.perf_counter() - t0)

    def add_stage(self, name: str, seconds: float) -> None:
        with self._lock:
            self.stages[name] = self.stages.get(name, 0.0) + seconds

    def add_stages(self, stages: dict[str, float]) -> None:
        """Accumulate another trace's stages (a batch flush's device
        stages, attributed to every request it served)."""
        with self._lock:
            for name, seconds in stages.items():
                self.stages[name] = self.stages.get(name, 0.0) + seconds

    @property
    def total(self) -> float:
        with self._lock:
            return sum(self.phases.values())

    def as_dict(self) -> dict[str, float]:
        """Seconds per phase, insertion-ordered."""
        with self._lock:
            return dict(self.phases)

    def cpu_dict(self) -> dict[str, float]:
        """Thread CPU seconds per phase timed by :meth:`phase`."""
        with self._lock:
            return dict(self.cpu)

    def stage_dict(self) -> dict[str, float]:
        """Seconds per stage."""
        with self._lock:
            return dict(self.stages)

    def __repr__(self) -> str:
        # same guard as total/as_dict: the batcher's scheduler thread
        # mutates phases while a submitter may be formatting this
        with self._lock:
            parts = ", ".join(
                f"{k}={v * 1e3:.2f}ms" for k, v in self.phases.items()
            )
        return f"PhaseTrace({parts})"


class _NoTrace:
    """The ``trace`` default of the device entries, which time stages
    only, where the caller keeps no :class:`PhaseTrace` (a sharded
    engine, a stream's carried scan): a stage still marks the profile,
    and records nothing."""

    @staticmethod
    def stage(name: str):
        return annotation(name)


NO_TRACE = _NoTrace()


@contextlib.contextmanager
def profiler_trace(log_dir: str | None):
    """``jax.profiler.trace`` when ``log_dir`` is set, else a no-op."""
    if not log_dir:
        yield
        return
    import jax

    with jax.profiler.trace(log_dir):
        yield
