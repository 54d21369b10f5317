"""The system under test, in the harness's own process (which holds the
chips): the engine as ``python -m log_parser_tpu.serve --pattern-dir
<library>`` builds it with the configuration's ``serve`` keys, behind
``serve.http.make_server`` on localhost, serving on a thread.

Besides the served answers the harness reads only what the program
exposes to any operator: ``GET /metrics`` (phase histograms, line-cache
counters, fallback counters) and the request-trace ring, whose ``seq``
is the order in which requests were finalized.
"""

from __future__ import annotations

import dataclasses
import re
import threading
import urllib.request

_SAMPLE = re.compile(r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{(.*)\})? (\S+)$')
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse_metrics(text: str) -> dict:
    """Prometheus text → {(name, ((label, value), ...)): value}."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE.match(line)
        if not m:
            continue
        labels = tuple(sorted(_LABEL.findall(m.group(3) or "")))
        out[(m.group(1), labels)] = float(m.group(4))
    return out


class Served:
    """``config``'s engine behind the HTTP server, built from its
    ``serve`` keys as ``serve`` builds it from the flags of the same
    names: ``sharded`` (``--sharded``: the line-batch axis over every
    chip the process holds, with no line cache and no batching, as
    ``serve`` warns), else the one-chip engine with ``batching``
    (``--batching``) and ``line_cache_mb``; ``golden_fallback`` sets the
    engine's fallback."""

    def __init__(self, config: dict, pattern_dir: str):
        from log_parser_tpu.config import ScoringConfig
        from log_parser_tpu.patterns import load_pattern_directory
        from log_parser_tpu.runtime import AnalysisEngine
        from log_parser_tpu.serve.http import make_server

        serve = config["serve"]
        scoring = dataclasses.replace(
            ScoringConfig.from_env(), pattern_directory=pattern_dir
        )
        pattern_sets = load_pattern_directory(pattern_dir)
        if serve.get("sharded"):
            from log_parser_tpu.parallel import ShardedEngine, make_mesh

            self.engine = ShardedEngine(pattern_sets, scoring, mesh=make_mesh())
        else:
            self.engine = AnalysisEngine(pattern_sets, scoring)
            if serve.get("batching") == "on":
                # serve's default wait and batch size
                self.engine.enable_batching()
            if serve["line_cache_mb"] > 0:
                self.engine.enable_line_cache(serve["line_cache_mb"])
        self.engine.fallback_to_golden = serve["golden_fallback"] == "on"
        self.server = make_server(self.engine, "127.0.0.1", 0)
        self.port = self.server.server_address[1]
        self.thread = threading.Thread(
            target=self.server.serve_forever, name="bench-http", daemon=True
        )
        self.thread.start()

    def _get(self, path: str) -> bytes:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{self.port}{path}", timeout=120
        ) as r:
            return r.read()

    def scrape(self) -> dict:
        return parse_metrics(self._get("/metrics").decode())

    def finalize_order(self) -> list[tuple[int, str, str]]:
        """(seq, request id, outcome) of every request the ring holds,
        in the order the engine finalized them."""
        entries = self.engine.obs.ring.recent()
        return sorted(
            (e["seq"], e["requestId"], e.get("outcome", "")) for e in entries
        )

    def close(self) -> None:
        self.server.shutdown()
        self.server.server_close()
        self.thread.join(timeout=60)
        self.engine = None
        self.server = None
