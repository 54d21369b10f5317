"""ctypes bindings for the native runtime library (native/log_parser_native.cpp).

The shared object is compiled on demand with ``g++ -O3`` and cached next to
the source, keyed by a sha256 of the source (a stamp file beside the
``.so``): a fresh copy of the tree sets mtimes arbitrarily, so only the
content says whether the binary was built from this source. The build
product is never committed. Every caller must tolerate
``get_lib() is None`` (no toolchain, compile failure) and fall back to the
pure-Python path — the native layer is an accelerator, never a requirement.
"""

from __future__ import annotations

import ctypes
import fcntl
import hashlib
import logging
import os
import re
import subprocess
import threading
from pathlib import Path

log = logging.getLogger(__name__)

_SRC = Path(__file__).resolve().parents[2] / "native" / "log_parser_native.cpp"
_SO = _SRC.parent / "build" / "log_parser_native.so"
# sha256 of the source the .so was built from
_STAMP = _SO.with_name(_SO.name + ".sha256")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False
# WHY the fallback is running, recorded once at first get_lib() and
# surfaced at GET /trace/last "native" (docs/OPS.md) — a GLIBCXX mismatch
# on this host class used to require PERF.md archaeology to diagnose
_load_error: str | None = None
# the symbol-level diagnosis for the GLIBCXX case (see glibcxx_triage):
# stats() carries it so /trace/last and tools/check_native.py agree
_load_triage: dict | None = None

_GLIBCXX_RE = re.compile(rb"GLIBCXX_(\d+(?:\.\d+)+)")


def _glibcxx_versions(path) -> list[tuple[int, ...]]:
    """Every GLIBCXX_x.y.z version tag embedded in ``path``, sorted.
    Reading .dynstr as raw bytes needs no ELF tooling and matches what
    ``strings … | grep GLIBCXX`` shows an operator."""
    try:
        data = Path(path).read_bytes()
    except OSError:
        return []
    return sorted({
        tuple(int(part) for part in m.group(1).split(b"."))
        for m in _GLIBCXX_RE.finditer(data)
    })


def _fmt_glibcxx(v: tuple[int, ...]) -> str:
    return "GLIBCXX_" + ".".join(str(p) for p in v)


def find_libstdcxx() -> str | None:
    """The libstdc++ this process would dlopen against: the copy already
    mapped in (JAX links it) wins; otherwise scan the usual soname dirs."""
    try:
        with open("/proc/self/maps", encoding="utf-8", errors="replace") as f:
            for line in f:
                path = line.rsplit(None, 1)[-1]
                if "libstdc++" in os.path.basename(path):
                    return path
    except OSError:
        pass
    dirs = [d for d in os.environ.get("LD_LIBRARY_PATH", "").split(os.pathsep)
            if d]
    dirs += [
        "/usr/lib/x86_64-linux-gnu", "/lib/x86_64-linux-gnu",
        "/usr/lib/aarch64-linux-gnu", "/lib/aarch64-linux-gnu",
        "/usr/lib64", "/usr/lib", "/usr/local/lib",
    ]
    for d in dirs:
        p = os.path.join(d, "libstdc++.so.6")
        if os.path.exists(p):
            return p
    return None


def glibcxx_triage(so_path=None) -> dict:
    """Required-vs-provided GLIBCXX symbol versions: which versions the
    prebuilt .so asks for, which the host's libstdc++ actually exports,
    and the gap. This is the whole diagnosis for the classic 'built on a
    newer distro' failure — tools/check_native.py prints it, and a load
    failure records it into stats()."""
    so_path = str(so_path or _SO)
    provider = find_libstdcxx()
    required = _glibcxx_versions(so_path)
    provided = _glibcxx_versions(provider) if provider else []
    missing = [v for v in required if provided and v > max(provided)]
    return {
        "so": so_path,
        "libstdcxx": provider,
        "required": [_fmt_glibcxx(v) for v in required],
        "provided": [_fmt_glibcxx(v) for v in provided],
        "missing": [_fmt_glibcxx(v) for v in missing],
    }


def _src_digest() -> str:
    return hashlib.sha256(_SRC.read_bytes()).hexdigest()


def _stamp() -> str | None:
    try:
        return _STAMP.read_text().strip()
    except OSError:
        return None


def _compile(digest: str) -> bool:
    """Build ``_SO`` from ``_SRC`` and stamp it with ``digest``. Written
    to a private temp name and renamed into place, so a process that
    loaded the old binary keeps its mapping and no reader sees half a
    file."""
    global _load_error
    tmp = _SO.with_name(f"{_SO.name}.{os.getpid()}.tmp")
    cmd = [
        "g++", "-O3", "-std=c++17", "-shared", "-fPIC",
        str(_SRC), "-o", str(tmp),
    ]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=300)
    except (OSError, subprocess.TimeoutExpired) as e:
        log.warning("native compile failed to launch: %s", e)
        _load_error = f"compile failed to launch: {e}"
        return False
    if proc.returncode != 0:
        log.warning("native compile failed:\n%s", proc.stderr)
        _load_error = f"compile failed: {proc.stderr.strip()[:500]}"
        return False
    os.replace(tmp, _SO)
    stamp_tmp = _STAMP.with_name(f"{_STAMP.name}.{os.getpid()}.tmp")
    stamp_tmp.write_text(digest + "\n")
    os.replace(stamp_tmp, _STAMP)
    return True


def _ensure_built() -> bool:
    """Rebuild unless the stamp matches the source's digest. One
    builder at a time (test workers start together): the others wait
    on the lock and then find the stamp current."""
    digest = _src_digest()
    if _SO.exists() and _stamp() == digest:
        return True
    _SO.parent.mkdir(parents=True, exist_ok=True)
    with open(_SO.parent / ".build.lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if _SO.exists() and _stamp() == digest:
            return True
        return _compile(digest)


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    i8p = ctypes.POINTER(ctypes.c_int8)
    i32p = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    u8p = ctypes.POINTER(ctypes.c_uint8)

    lib.lpn_split_scan.argtypes = [u8p, ctypes.c_int64, i64p]
    lib.lpn_split_scan.restype = ctypes.c_int64
    lib.lpn_split_fill.argtypes = [
        u8p, ctypes.c_int64, ctypes.c_int64, u8p, ctypes.c_int64,
        i32p, u8p, i64p, i64p, ctypes.c_int64,
    ]
    lib.lpn_split_fill.restype = None
    lib.lpn_split_lengths.argtypes = [u8p, ctypes.c_int64, ctypes.c_int64, i32p]
    lib.lpn_split_lengths.restype = None

    lib.lpn_dfa_build.argtypes = [
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
        i64p, i8p, i32p,            # eps CSR
        i64p, i32p, i32p,           # trans CSR
        u8p, ctypes.c_int32, u8p,   # bytesets, n_bytesets, word mask
        ctypes.c_int32, ctypes.c_int32,  # max_states, do_minimize
        i32p, i32p, i32p, i32p,     # out n_states, n_classes, start, err
    ]
    lib.lpn_dfa_build.restype = ctypes.c_void_p
    lib.lpn_dfa_read.argtypes = [ctypes.c_void_p, i32p, i32p, u8p]
    lib.lpn_dfa_read.restype = None
    lib.lpn_dfa_free.argtypes = [ctypes.c_void_p]
    lib.lpn_dfa_free.restype = None

    u32p = ctypes.POINTER(ctypes.c_uint32)
    lib.lpn_multi_dfa_build.argtypes = [
        ctypes.c_int32, ctypes.c_int32,
        i64p, i8p, i32p,            # eps CSR
        i64p, i32p, i32p,           # trans CSR
        u8p, ctypes.c_int32, u8p,   # bytesets, n_bytesets, word mask
        i32p, ctypes.c_int32,       # finals, n_patterns
        ctypes.c_int32, ctypes.c_int32,  # max_states, do_minimize
        i32p, i32p, i32p, i32p, i32p,  # out n_states/n_classes/n_words/start/err
    ]
    lib.lpn_multi_dfa_build.restype = ctypes.c_void_p
    lib.lpn_multi_dfa_read.argtypes = [
        ctypes.c_void_p, i32p, i32p, i32p, u32p, u32p,
    ]
    lib.lpn_multi_dfa_read.restype = None
    lib.lpn_multi_dfa_free.argtypes = [ctypes.c_void_p]
    lib.lpn_multi_dfa_free.restype = None

    lib.lpn_regex_batch_build.argtypes = [
        u8p, i64p, u8p, ctypes.c_int32,      # blob, offs, ci flags, n
        u8p,                                  # word mask
        ctypes.c_int32, ctypes.c_int32,       # max_states, do_minimize
    ]
    lib.lpn_regex_batch_build.restype = ctypes.c_void_p
    lib.lpn_regex_batch_get.argtypes = [
        ctypes.c_void_p, ctypes.c_int32, i32p, i32p, i32p,
    ]
    lib.lpn_regex_batch_get.restype = ctypes.c_int32
    lib.lpn_regex_batch_read.argtypes = [
        ctypes.c_void_p, ctypes.c_int32, i32p, i32p, u8p,
    ]
    lib.lpn_regex_batch_read.restype = None
    lib.lpn_regex_batch_extract_totals.argtypes = [
        ctypes.c_void_p, i64p, i64p, i64p, i64p, i64p,
    ]
    lib.lpn_regex_batch_extract_totals.restype = None
    lib.lpn_regex_batch_extract_all.argtypes = [
        ctypes.c_void_p,
        i8p, i32p, i64p, u8p, u8p,   # lit status/counts/offs/ci/blob
        i8p, i32p, i32p, i32p, u8p,  # seq status/counts/lens/pos_counts/blob
    ]
    lib.lpn_regex_batch_extract_all.restype = None
    lib.lpn_regex_batch_free.argtypes = [ctypes.c_void_p]
    lib.lpn_regex_batch_free.restype = None

    lib.lpn_ac_build.argtypes = [
        u8p, i64p, i32p, ctypes.c_int32, ctypes.c_int32,  # blob, offs, groups, n, n_groups
        i32p, i32p, i32p,                                  # out nodes/classes/words
    ]
    lib.lpn_ac_build.restype = ctypes.c_void_p
    lib.lpn_ac_read.argtypes = [ctypes.c_void_p, i32p, i32p, u32p, u8p]
    lib.lpn_ac_read.restype = None
    lib.lpn_ac_free.argtypes = [ctypes.c_void_p]
    lib.lpn_ac_free.restype = None
    return lib


def get_lib() -> ctypes.CDLL | None:
    """The bound native library, or None when unavailable."""
    global _lib, _tried, _load_error
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("LOG_PARSER_TPU_NO_NATIVE"):
            _load_error = "disabled by LOG_PARSER_TPU_NO_NATIVE"
            return None
        try:
            # a prebuilt .so without source alongside (container runtime
            # stage, no toolchain) is loaded as-is; staleness only applies
            # when the source is present to rebuild from
            if _SRC.exists():
                if not _ensure_built():
                    return None
            elif not _SO.exists():
                _load_error = f"no prebuilt library at {_SO} and no source to build"
                return None
            _lib = _bind(ctypes.CDLL(str(_SO)))
        except OSError as e:
            # the GLIBCXX case lands here: the .so links a newer
            # libstdc++ than the host ships (PERF.md §10)
            log.warning("native library unavailable: %s", e)
            global _load_triage
            if "GLIBCXX" in str(e):
                tri = glibcxx_triage()
                _load_triage = tri
                gap = (
                    f"needs {', '.join(tri['missing'])}; host "
                    f"{tri['libstdcxx'] or 'libstdc++ (not found)'} tops "
                    f"out at "
                    f"{tri['provided'][-1] if tri['provided'] else '?'}"
                    if tri["missing"]
                    else str(e)[:200]
                )
                _load_error = (
                    f"glibcxx mismatch: {gap} — rebuild on this host "
                    "(python tools/check_native.py --rebuild) or use the "
                    "Dockerfile native-rebuild stage"
                )
            else:
                _load_error = f"load failed: {e}"
            _lib = None
        except AttributeError as e:
            # a prebuilt .so from an older source revision lacks newly
            # added symbols — fall back to pure Python, never crash
            log.warning("native library is stale (missing symbol): %s", e)
            _load_error = f"stale library (missing symbol): {e}"
            _lib = None
    return _lib


def available() -> bool:
    return get_lib() is not None


def stats() -> dict:
    """GET /trace/last ``native`` block (docs/OPS.md): which ingest path
    this process is running, and — when the scalar fallback is active —
    the recorded reason the shared object refused to load."""
    lib = get_lib()
    doc = {
        "available": lib is not None,
        "loadError": _load_error,
    }
    if _load_triage is not None:
        doc["glibcxx"] = _load_triage
    return doc
