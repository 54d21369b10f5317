"""Triage the native scanner library without booting the server.

The classic failure is a prebuilt ``log_parser_native.so`` carried from
a newer build host: dlopen refuses it with a one-line ``GLIBCXX_x.y.z
not found`` and the process silently runs the scalar fallback at a
fraction of the ingest rate. This tool prints the whole diagnosis in
one shot:

    python tools/check_native.py            # table + load attempt
    python tools/check_native.py --json     # machine-readable
    python tools/check_native.py --rebuild  # force a from-source rebuild

- which GLIBCXX symbol versions the .so REQUIRES (read straight from
  its .dynstr, same list ``strings … | grep GLIBCXX`` shows);
- which versions the host's libstdc++ PROVIDES (the copy already mapped
  into this process wins — that is the one dlopen will use);
- the gap, the toolchain available for a rebuild, and the actual load
  attempt's outcome (the same reason string ``logparser_native_loaded``
  exposes on /metrics and GET /trace/last reports under ``native``).

Exit code: 0 when the library loads, 1 when it doesn't, 2 when a
requested ``--rebuild`` fails. In a container, the Dockerfile's
``native-rebuild`` stage runs the same from-source path so the shipped
.so always matches the image's own libstdc++.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from log_parser_tpu import native  # noqa: E402


def triage(rebuild: bool = False) -> dict:
    doc: dict = {
        "source": str(native._SRC),
        "source_exists": native._SRC.exists(),
        "so": str(native._SO),
        "so_exists": native._SO.exists(),
        "toolchain": shutil.which("g++"),
    }
    if rebuild:
        # the loader's own from-source build, as a fresh checkout runs it
        for path in (native._SO, native._STAMP):
            try:
                path.unlink()
            except OSError:
                pass
        doc["rebuild_rc"] = 0 if native._ensure_built() else 1
        if doc["rebuild_rc"]:
            doc["rebuild_stderr"] = native._load_error
    doc["glibcxx"] = native.glibcxx_triage()
    # the real load attempt, exactly as the server would do it at boot
    doc["loaded"] = native.available()
    doc["load_error"] = native.stats().get("loadError")
    return doc


def render(doc: dict) -> None:
    g = doc["glibcxx"]

    def row(k, v):
        print(f"{k:<22} {v}")

    row("source", f"{doc['source']}"
        f"{'' if doc['source_exists'] else '  (MISSING)'}")
    row("shared object", f"{doc['so']}"
        f"{'' if doc['so_exists'] else '  (MISSING)'}")
    row("toolchain (g++)", doc["toolchain"] or "not found")
    row("host libstdc++", g["libstdcxx"] or "not found")
    row("required GLIBCXX", ", ".join(g["required"]) or "(none read)")
    provided = g["provided"]
    row("provided GLIBCXX",
        f"… up to {provided[-1]} ({len(provided)} versions)"
        if provided else "(none read)")
    if g["missing"]:
        row("MISSING", ", ".join(g["missing"]))
    if "rebuild_rc" in doc:
        row("rebuild", "ok" if doc["rebuild_rc"] == 0
            else f"FAILED (rc={doc['rebuild_rc']})")
        if doc.get("rebuild_stderr"):
            print(doc["rebuild_stderr"])
    row("load attempt", "ok — native scanner active" if doc["loaded"]
        else f"FAILED: {doc['load_error']}")
    if not doc["loaded"] and g["missing"]:
        print(
            "\nthe .so was built against a newer libstdc++ than this "
            "host ships.\nFix: rerun with --rebuild (needs g++), or "
            "build inside the image via the Dockerfile native-rebuild "
            "stage."
        )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="diagnose the native scanner's GLIBCXX linkage")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the triage as JSON")
    ap.add_argument("--rebuild", action="store_true",
                    help="force a from-source rebuild before the load "
                         "attempt")
    args = ap.parse_args(argv)
    doc = triage(rebuild=args.rebuild)
    if args.as_json:
        print(json.dumps(doc, indent=2))
    else:
        render(doc)
    if args.rebuild and doc.get("rebuild_rc") != 0:
        return 2
    return 0 if doc["loaded"] else 1


if __name__ == "__main__":
    sys.exit(main())
