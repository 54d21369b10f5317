"""Persistent XLA compilation cache wiring.

The fused device program costs seconds to tens of seconds to compile and
is recompiled from scratch on every process start — a server restart or
cron-driven batch job pays it again although neither the bank nor the
program changed. JAX's persistent compilation cache keys serialized
executables by HLO + platform, so enabling it turns every warm restart's
compile into a disk read.

Where the cache lives:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module
  sets no directory in code (it only sweeps it and lowers the
  thresholds below);
- otherwise a fixed path inside the checkout, ``<repo>/.cache/xla``
  (gitignored) — fixed because the path is part of the cache key's
  reach: a directory that moves never hits;
- ``LOG_PARSER_TPU_XLA_CACHE=0`` disables the cache (the test suite).

The thresholds below cache *every* compile, however small, and JAX's
persistent cache has no eviction — the directory grows without bound
across bank/shape changes. Entries are content-addressed and individually
deletable, so periodic cleanup is safe: ``find <dir> -atime +30 -delete``
(or wipe the directory; the only cost is one cold compile set).

Crash safety: :func:`verify_cache_integrity` sweeps the directory at
enable time, keeping a sha256 sidecar per entry under ``<dir>/.integrity``
(JAX never reads that subtree). An entry whose bytes no longer match its
recorded checksum — truncated by a crashed writer, bit-rotted, torn by a
non-atomic copy — is quarantined with a ``.corrupt`` suffix, which JAX
sees as a miss and recompiles; startup never fails on a poisoned cache.
First sight of an entry records its checksum, so the sweep detects
corruption *between* runs, not a writer that crashed before the very
first sweep (JAX itself publishes entries atomically). The sweep is
best-effort: any I/O failure logs and returns — never raises into boot.
"""

from __future__ import annotations

import hashlib
import logging
import os

log = logging.getLogger(__name__)

_configured = False
# process-lifetime counters fed by JAX's monitoring events (registered in
# enable_persistent_cache); surfaced at GET /trace/last "compileCache"
# (docs/OPS.md) and in the bench artifact's boot story
_cache_dir: str | None = None
_hits = 0
_requests = 0
_listener_registered = False


def _on_event(event: str, **kwargs) -> None:
    global _hits, _requests
    if event == "/jax/compilation_cache/cache_hits":
        _hits += 1
    elif event == "/jax/compilation_cache/compile_requests_use_cache":
        _requests += 1


def stats() -> dict:
    """GET /trace/last ``compileCache`` block (docs/OPS.md): whether the
    persistent cache is wired, where, and this process's hit/miss tally
    (misses = cacheable compile requests that went to XLA)."""
    return {
        "dir": _cache_dir,
        "enabled": _cache_dir is not None,
        "compileHits": _hits,
        "compileMisses": max(0, _requests - _hits),
    }


def verify_cache_integrity(path: str) -> dict[str, int]:
    """Checksum-sweep a persistent-cache directory (see module docstring).
    Returns ``{"checked": n, "recorded": n, "quarantined": n}``."""
    from log_parser_tpu.runtime import faults

    counts = {"checked": 0, "recorded": 0, "quarantined": 0}
    side_dir = os.path.join(path, ".integrity")
    try:
        # chaos point: an injected cache fault aborts the sweep, which
        # must read as "cache cold", never as a boot failure
        faults.fire("cache")
        if not os.path.isdir(path):
            return counts
        os.makedirs(side_dir, exist_ok=True)
        entries = set()
        for name in sorted(os.listdir(path)):
            full = os.path.join(path, name)
            if name == ".integrity" or not os.path.isfile(full):
                continue
            if name.endswith((".corrupt", ".tmp")):
                continue
            # JAX pairs each immutable "-cache" payload with a "-atime"
            # marker it rewrites on every hit — mutation is its normal
            # behavior, so checksumming it would quarantine healthy entries
            if name.endswith("-atime"):
                continue
            entries.add(name)
            counts["checked"] += 1
            digest = hashlib.sha256()
            with open(full, "rb") as f:
                for chunk in iter(lambda: f.read(1 << 20), b""):
                    digest.update(chunk)
            want = digest.hexdigest()
            sidecar = os.path.join(side_dir, name + ".sum")
            if not os.path.exists(sidecar):
                tmp = sidecar + ".tmp"
                with open(tmp, "w") as f:
                    f.write(want + "\n")
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(tmp, sidecar)
                counts["recorded"] += 1
            elif open(sidecar).read().split()[0] != want:
                log.warning(
                    "XLA cache entry %s fails its checksum; quarantined "
                    "(.corrupt) — it will recompile on next use", name
                )
                os.replace(full, full + ".corrupt")
                os.unlink(sidecar)
                counts["quarantined"] += 1
        # sidecars whose entry is gone (cleanup, eviction) are dropped so
        # the subtree cannot grow without bound either
        for name in os.listdir(side_dir):
            if name.endswith(".sum") and name[: -len(".sum")] not in entries:
                os.unlink(os.path.join(side_dir, name))
    except Exception as exc:  # best-effort by contract
        log.warning("XLA cache integrity sweep aborted: %s", exc)
    return counts


# <repo>/.cache/xla: utils/ -> log_parser_tpu/ -> repo root
DEFAULT_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".cache",
    "xla",
)


def cache_dir() -> str | None:
    """Where the persistent cache lives for this process's environment,
    or None when ``LOG_PARSER_TPU_XLA_CACHE=0`` disables it."""
    if os.environ.get("LOG_PARSER_TPU_XLA_CACHE", "").strip() == "0":
        return None
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR


def enable_persistent_cache() -> None:
    """Idempotently point JAX at the persistent compilation cache."""
    global _configured, _cache_dir, _listener_registered
    if _configured:
        return
    _configured = True
    path = cache_dir()
    if path is None:
        return
    try:
        import jax

        os.makedirs(path, exist_ok=True)
        verify_cache_integrity(path)
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", path)
        # cache everything, however small or quick: warm restarts should
        # replay the whole compile set, including tier probes and admin
        # paths (JAX's defaults skip sub-second compiles)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        if not _listener_registered:
            # hit/miss telemetry rides JAX's own monitoring events — the
            # compiler records one event per cacheable compile request
            # and one per disk hit (jax/_src/compiler.py)
            jax.monitoring.register_event_listener(_on_event)
            _listener_registered = True
        _cache_dir = path
    except Exception as exc:  # pragma: no cover - cache is best-effort
        log.info("persistent XLA cache unavailable: %s", exc)
