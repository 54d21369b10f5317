"""Extended engine-vs-golden parity sweep.

Reuses the suite's own generators (tests/test_engine_parity.py) over an
arbitrary seed range — the suite pins small seed sets for CI speed; this
tool runs the long tail on demand. Every seed builds a random pattern
library, then runs corpora through BOTH a device engine (CPU backend,
fallback disabled) and the pure-host golden analyzer, asserting
event-for-event equality and score deltas <= 1e-9 with evolving
cross-request frequency state.

Three modes:
- default: single-device ``AnalysisEngine`` — mirrors
  ``test_random_library_parity`` (suite seeds 0..7).
- ``--sharded``: ``ShardedEngine`` over the virtual 8-device mesh
  (shard_map halos, all_gather chains, cross-shard frequency prefix) —
  mirrors ``test_random_parity_small_batches`` (suite seeds 1000..1003;
  pass raw offsets, the tool adds nothing).
- ``--pattern-sharded``: ``PatternShardedEngine`` with per-seed block
  counts (the pattern-axis / TP-analogue path, stable (line, pattern)
  merge) — mirrors ``test_pattern_sharded.test_random_parity_vs_golden``
  (suite seeds 9000..9002 x n_blocks {1,3,4}).
- ``--long``: single-device engine under the default tier plan (bit tiers
  on) with >31-char-literal libraries and prefix-poisoned corpora — the
  bitglush truncation + host verify / distance-repair paths; mirrors
  ``test_random_long_literal_parity_bit_policy`` (suite seeds
  31000..31005).
- ``--admin``: NOT a parity sweep — a rejection sweep over the admin
  surface. An in-process ``ParseServer`` takes seeded malformed bodies
  (broken YAML, wrong JSON shapes, negative/NaN ages, oversized
  payloads) on ``POST /patterns/reload`` and ``POST /frequency/restore``
  and every response must be 400/409/413 with the engine provably
  untouched: same bank object, same frequency stats, same reload epoch.
- ``--ingest``: NOT a parity sweep — a robustness sweep over the parse
  ingest path. An in-process ``ParseServer`` takes seeded hostile
  ``POST /parse`` traffic — invalid-UTF-8 raw bodies, NUL bytes, lone
  surrogates (``\\udXXX`` escapes survive json.loads unpaired),
  control-character soup, binary-ish blobs, and multi-MiB single lines —
  and every request must answer 200 or a structured 4xx JSON error,
  never an unhandled 500; on every reject the engine must be provably
  untouched (same bank object, same frequency stats). Runs with fallback
  DISABLED, so a hostile input that faults the device step surfaces as a
  500 finding instead of hiding behind golden.
- ``--stream``: adversarial-chunking sweep over the streaming session
  layer (runtime/stream.py). Seeded corpora — CRLF endings, multi-byte
  UTF-8, raw invalid bytes, NULs, control soup — are fed through
  sessions under hostile chunkings (1-byte chunks, empty chunks, splits
  inside UTF-8 sequences and inside ``\\r\\n``); every session must
  produce only well-formed frames, end in exactly one terminal ``final``
  (or structured ``error``) frame, release its admission slot, and the
  final result must be bit-identical to one-shot ``analyze()`` on the
  reassembled blob with serially-equivalent frequency state. A periodic
  raw-socket pass sends garbage HTTP chunk framing at
  ``POST /parse/stream`` and must get a structured ``bad-frame`` error
  frame with the server still healthy — a wedged session/server is the
  finding.
- ``--miner``: NOT a parity sweep — a robustness sweep over the template
  miner (log_parser_tpu/mining/). Seeded hostile miss lines — invalid
  UTF-8, NULs, 1 MB single lines, regex-metacharacter soup, control
  bytes — go through the REAL pipeline (tap offer → pump → cluster →
  synthesize → vet) at ``min_support=1``: the miner must never raise
  (``errors`` stays 0), the serving bank must stay object-identical in
  review mode, and every regex the synthesizer emits must re-parse
  through the bank's own compile entry points (``compile_java_regex``,
  ``classify_regex`` off the skipped tier).

- ``--router``: NOT a parity sweep — a robustness sweep over the fleet
  router front-door (log_parser_tpu/fleet/router.py). A real router
  proxies to a real in-process backend while seeded hostile traffic
  hits the edge: hostile ``X-Tenant`` headers (traversal, control soup,
  overlong ids — refused 400 AT the router, never forwarded), hostile
  request bodies and paths (relayed verbatim, the backend's verdict
  passed through), malformed ``POST /fleet/override`` bodies (400 with
  the ring provably untouched), and raw-socket garbage at the router
  port. After every seed the router must still answer ``/q/health`` UP,
  the ring must still hold its backend, and a clean ``POST /parse``
  must still round-trip — a wedged or 5xx-ing router is the finding.

Usage: python tools/fuzz_sweep.py [--start N] [--end M]
       [--sharded | --pattern-sharded | --long | --admin | --ingest |
        --stream | --miner | --router | --quick]
(defaults per mode: 8..200 single-device, 1004..1054 sharded,
9003..9053 pattern-sharded, 31006..31056 long — a bare run reproduces
the documented records below; --end exclusive)
``--quick`` is the CI tier: the first 5 seeds of EVERY mode in one
process (~2 min), run as a workflow job after the suite so a parity
regression in any engine mode fails the PR (VERDICT r4 #5).
Record (round-4 engine, 2026-07-30): default seeds 8..199 (192 libraries,
576 corpora) clean; sharded seeds 1004..1053 (50 libraries) clean;
pattern-sharded seeds 9003..9052 (50 libraries, n_blocks cycling 1/3/4)
clean.
Record (round-4 engine, 2026-07-31, truncation/repair build): long seeds
31006..31055 (50 libraries, 150 corpora) clean; default 8..199 (192
libraries, 576 corpora), sharded 1004..1053, and pattern-sharded
9003..9052 all re-run clean on the same build.
Record (round-5 engine, 2026-08-01 — native batched regex pipeline,
pack-file cache, exact bitglush pricing, \\Q quoting): ALL FOUR full
sweeps clean — default 8..199 (192 libraries), sharded 1004..1053,
pattern-sharded 9003..9052, long 31006..31055.
Record (round-9 engine, 2026-08-05 — streaming session layer): stream
seeds 61000..61049 (50 corpora x 3 chunkings, periodic garbage-framing
passes) clean.
"""

from __future__ import annotations

import argparse
import os
import random
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
# append-if-missing (the conftest idiom), NOT setdefault: a pre-set
# XLA_FLAGS would otherwise silently drop the 8-device topology and turn
# the --sharded sweep into a vacuous 1-device pass (make_mesh slices
# devices[:n] without complaint)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["LOG_PARSER_TPU_NO_FALLBACK"] = "1"

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.join(_REPO, "tests"))


def main() -> int:
    if sys.flags.optimize:
        # the parity checks (assert_results_match, shared with the test
        # suite) are assert-based; -O would strip them and report a
        # vacuous clean pass
        sys.exit("refusing to run under python -O: parity asserts would be stripped")
    ap = argparse.ArgumentParser()
    ap.add_argument("--start", type=int, default=None)
    ap.add_argument("--end", type=int, default=None)
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--sharded", action="store_true")
    mode.add_argument("--pattern-sharded", action="store_true")
    mode.add_argument("--long", action="store_true")
    mode.add_argument("--admin", action="store_true")
    mode.add_argument("--ingest", action="store_true")
    mode.add_argument("--stream", action="store_true")
    mode.add_argument("--miner", action="store_true")
    mode.add_argument("--router", action="store_true")
    mode.add_argument(
        "--quick",
        action="store_true",
        help="CI tier: 5 seeds of EVERY mode (VERDICT r4 #5 — a parity "
        "regression in any engine mode fails the PR, not a future "
        "manual sweep); --start/--end are ignored",
    )
    args = ap.parse_args()
    if args.quick:
        rc = 0
        for m in ("default", "sharded", "pattern-sharded", "long"):
            start = _MODE_DEFAULTS[m][0]
            print(f"== quick sweep: {m} seeds {start}..{start + 4}", flush=True)
            rc |= run_sweep(m, start, start + 5)
        start = _MODE_DEFAULTS["admin"][0]
        print(f"== quick sweep: admin seeds {start}..{start + 4}", flush=True)
        rc |= run_admin_sweep(start, start + 5)
        start = _MODE_DEFAULTS["ingest"][0]
        print(f"== quick sweep: ingest seeds {start}..{start + 4}", flush=True)
        rc |= run_ingest_sweep(start, start + 5)
        start = _MODE_DEFAULTS["stream"][0]
        print(f"== quick sweep: stream seeds {start}..{start + 4}", flush=True)
        rc |= run_stream_sweep(start, start + 5)
        start = _MODE_DEFAULTS["miner"][0]
        print(f"== quick sweep: miner seeds {start}..{start + 4}", flush=True)
        rc |= run_miner_sweep(start, start + 5)
        start = _MODE_DEFAULTS["router"][0]
        print(f"== quick sweep: router seeds {start}..{start + 4}", flush=True)
        rc |= run_router_sweep(start, start + 5)
        return rc
    if args.router:
        start, end = _MODE_DEFAULTS["router"]
        if args.start is not None:
            start = args.start
        if args.end is not None:
            end = args.end
        return run_router_sweep(start, end)
    if args.miner:
        start, end = _MODE_DEFAULTS["miner"]
        if args.start is not None:
            start = args.start
        if args.end is not None:
            end = args.end
        return run_miner_sweep(start, end)
    if args.stream:
        start, end = _MODE_DEFAULTS["stream"]
        if args.start is not None:
            start = args.start
        if args.end is not None:
            end = args.end
        return run_stream_sweep(start, end)
    if args.ingest:
        start, end = _MODE_DEFAULTS["ingest"]
        if args.start is not None:
            start = args.start
        if args.end is not None:
            end = args.end
        return run_ingest_sweep(start, end)
    if args.admin:
        start, end = _MODE_DEFAULTS["admin"]
        if args.start is not None:
            start = args.start
        if args.end is not None:
            end = args.end
        return run_admin_sweep(start, end)
    m = (
        "sharded"
        if args.sharded
        else "pattern-sharded"
        if args.pattern_sharded
        else "long"
        if args.long
        else "default"
    )
    # per-mode defaults: a bare run reproduces the documented record,
    # and each mode's seed space stays disjoint from the suite's pinned
    # seeds and the other modes' sweeps
    start, end = _MODE_DEFAULTS[m]
    if args.start is not None:
        start = args.start
    if args.end is not None:
        end = args.end
    return run_sweep(m, start, end)


_MODE_DEFAULTS = {
    "default": (8, 200),
    "sharded": (1004, 1054),
    "pattern-sharded": (9003, 9053),
    "long": (31006, 31056),
    "admin": (41000, 41050),
    "ingest": (51000, 51050),
    "stream": (61000, 61050),
    "miner": (71000, 71024),
    "router": (81000, 81050),
}


def _admin_reload_bodies(rng: "random.Random") -> list[bytes]:
    """Seeded malformed YAML for POST /patterns/reload. Every body is
    malformed BY SHAPE (not by luck), so a 200 is always a real finding:
    the engine swapped banks on garbage."""
    junk = "".join(rng.choice("abcxyz(){}<>|&*?!") for _ in range(rng.randrange(1, 12)))
    n = rng.randrange(1, 9)
    return [
        b"\xff\xfe" + junk.encode() * n,                   # not UTF-8 -> 400
        b"{unclosed: [" + junk.encode(),                   # YAML error
        f"- {rng.randrange(1 << 30)}\n- {n}\n".encode(),   # docs: list of ints
        f"{junk}: [unbalanced\n".encode(),                 # YAML error
        f"scalar-{junk}".encode(),                         # non-mapping doc
        f"name: {junk}\npatterns: {n}\n".encode(),         # patterns not a list
        f"patterns:\n- {junk}\n- {n}\n".encode(),          # members not mappings
        b"#" * ((4 << 20) + 1 + n),                        # > _ADMIN_MAX_BODY -> 413
    ]


def _admin_restore_bodies(rng: "random.Random") -> list[bytes]:
    """Seeded malformed JSON for POST /frequency/restore: wrong shapes,
    negative/NaN ages, bad envelopes, oversized."""
    pid = "".join(rng.choice("abcdefgh") for _ in range(rng.randrange(1, 8)))
    neg = -rng.random() - 1e-6
    return [
        b"not json " + pid.encode(),                       # parse error
        b"[1, 2, 3]",                                      # not a mapping
        f'{{"{pid}": 1}}'.encode(),                        # value not a list
        f'{{"{pid}": ["x", 1]}}'.encode(),                 # non-numeric age
        f'{{"{pid}": [{neg}]}}'.encode(),                  # negative age
        f'{{"{pid}": [NaN]}}'.encode(),                    # NaN never >= 0
        f'{{"ages": {{"{pid}": [{neg}]}}, "epoch": 0}}'.encode(),  # bad envelope
        f'{{"ages": "{pid}", "epoch": 0}}'.encode(),       # envelope, ages not dict
        b'{"' + pid.encode() + b'": [' + b"0," * (3 << 20) + b"0]}",  # oversized
    ]


def run_admin_sweep(start: int, end: int) -> int:
    """Fuzz the admin mutation surface of an in-process ParseServer: every
    malformed body must be rejected (400/409/413) and the engine must be
    bit-for-bit untouched — same bank object identity, same frequency
    stats, same reload epoch. Explicit raises (not asserts) so the
    startup -O guard is belt-and-braces here."""
    import json
    import random
    import threading
    import urllib.error
    import urllib.request

    from log_parser_tpu.config import ScoringConfig
    from log_parser_tpu.models.pod import PodFailureData
    from log_parser_tpu.patterns import load_pattern_directory
    from log_parser_tpu.runtime import AnalysisEngine
    from log_parser_tpu.runtime.reload import PatternReloader
    from log_parser_tpu.serve.http import make_server

    pattern_dir = os.path.join(_REPO, "log_parser_tpu", "patterns", "builtin")
    engine = AnalysisEngine(load_pattern_directory(pattern_dir), ScoringConfig())
    server = make_server(engine, "127.0.0.1", 0)
    server.reloader = PatternReloader(engine, pattern_dir)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{server.server_address[1]}"

    def post(path: str, body: bytes) -> int:
        if len(body) > (4 << 20):
            # the server 413s from Content-Length alone, before draining
            # the body; urllib would die on the resulting broken pipe, so
            # declare the length raw and never send the payload
            import socket

            host, port = server.server_address[:2]
            with socket.create_connection((host, port), timeout=60) as sock:
                sock.sendall(
                    b"POST %s HTTP/1.1\r\nHost: fuzz\r\n"
                    b"Content-Length: %d\r\nConnection: close\r\n\r\n"
                    % (path.encode(), len(body))
                )
                raw = b""
                while True:
                    chunk = sock.recv(65536)
                    if not chunk:
                        break
                    raw = raw + chunk
            return int(raw.split(b" ", 2)[1])
        req = urllib.request.Request(
            url + path, data=body, headers={"Content-Type": "application/json"}
        )
        try:
            with urllib.request.urlopen(req, timeout=60) as resp:
                resp.read()
                return resp.status
        except urllib.error.HTTPError as e:
            e.read()
            return e.code

    # prime real frequency state so "stats unchanged" is a non-vacuous check
    engine.analyze(
        PodFailureData(
            pod={"metadata": {"name": "fuzz-admin"}},
            logs="INFO boot\njava.lang.OutOfMemoryError: heap\nINFO after",
        )
    )
    base_bank = engine.bank
    base_stats = json.dumps(
        engine.frequency.get_frequency_statistics(), sort_keys=True
    )
    base_epoch = engine.reload_epoch

    t0 = time.time()
    fails: list[tuple[int, str]] = []
    try:
        for seed in range(start, end):
            rng = random.Random(seed)
            cases = [("/patterns/reload", b) for b in _admin_reload_bodies(rng)]
            cases += [("/frequency/restore", b) for b in _admin_restore_bodies(rng)]
            for path, body in cases:
                try:
                    status = post(path, body)
                    if status not in (400, 409, 413):
                        raise AssertionError(
                            f"{path} accepted garbage with {status}: {body[:80]!r}"
                        )
                    if engine.bank is not base_bank:
                        raise AssertionError(f"{path} swapped the bank on a reject")
                    stats = json.dumps(
                        engine.frequency.get_frequency_statistics(), sort_keys=True
                    )
                    if stats != base_stats:
                        raise AssertionError(
                            f"{path} mutated frequency state on a reject: "
                            f"{stats} != {base_stats}"
                        )
                    if engine.reload_epoch != base_epoch:
                        raise AssertionError(f"{path} bumped the reload epoch")
                except Exception as exc:  # noqa: BLE001 - recorded, sweep continues
                    fails.append((seed, repr(exc)[:300]))
                    print(f"SEED {seed} FAILED: {exc!r}", flush=True)
            if seed % 20 == 0:
                print(f"seed {seed} done ({time.time() - t0:.0f}s)", flush=True)
    finally:
        server.shutdown()
        server.server_close()
    print(f"DONE admin seeds {start}..{end - 1} fails: {fails} "
          f"({time.time() - t0:.0f}s)")
    return 1 if fails else 0


def _ingest_logs_cases(rng: "random.Random") -> list[str]:
    """Seeded hostile log blobs for POST /parse — valid JSON strings whose
    CONTENT is hostile to the ingest/encode path: NULs, lone surrogates,
    control soup, binary-ish bytes, and one multi-MiB single line."""
    n = rng.randrange(1, 6)
    junk = "".join(chr(rng.randrange(0x20, 0x7F)) for _ in range(16))
    return [
        # content NUL bytes mid-line (needs_host NUL rule)
        f"INFO {junk}\nbad\x00line\x00here\nINFO after" * n,
        # lone surrogates: json.dumps escapes them, json.loads round-trips
        # them unpaired — the str the engine sees cannot utf-8 encode
        f"lead \ud800 trail\n{junk}\npair \udfff\ud800 reversed",
        # control-character soup + carriage returns
        "".join(chr(rng.randrange(0, 32)) for _ in range(64)) + "\n" + junk,
        # binary-ish: every latin-1 code point, shuffled
        "".join(map(chr, rng.sample(range(256), 256))) * n,
        # multi-MiB single line, no newline (capped-width tail re-match)
        junk * ((2 << 20) // len(junk)),
        # empty and whitespace-only corpora
        rng.choice(["", " ", "\n" * rng.randrange(1, 9), "\x00"]),
    ]


def run_ingest_sweep(start: int, end: int) -> int:
    """Fuzz the parse ingest path of an in-process ParseServer: hostile
    bodies must answer 200 or a STRUCTURED 4xx (JSON with an "error" key),
    never an unhandled 500, and a reject must leave the engine untouched.
    Fallback stays disabled (module env), so a device fault caused by
    hostile input is a 500 finding, not a silent golden save."""
    import json
    import random
    import threading
    import urllib.error
    import urllib.request

    from log_parser_tpu.config import ScoringConfig
    from log_parser_tpu.patterns import load_pattern_directory
    from log_parser_tpu.runtime import AnalysisEngine
    from log_parser_tpu.serve.http import make_server

    pattern_dir = os.path.join(_REPO, "log_parser_tpu", "patterns", "builtin")
    engine = AnalysisEngine(load_pattern_directory(pattern_dir), ScoringConfig())
    server = make_server(engine, "127.0.0.1", 0)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{server.server_address[1]}/parse"

    def post(body: bytes) -> tuple[int, bytes]:
        req = urllib.request.Request(
            url, data=body, headers={"Content-Type": "application/json"}
        )
        try:
            with urllib.request.urlopen(req, timeout=120) as resp:
                return resp.status, resp.read()
        except urllib.error.HTTPError as e:
            return e.code, e.read()

    def freq_stats() -> str:
        return json.dumps(
            engine.frequency.get_frequency_statistics(), sort_keys=True
        )

    base_bank = engine.bank
    t0 = time.time()
    fails: list[tuple[int, str]] = []
    try:
        for seed in range(start, end):
            rng = random.Random(seed)
            bodies: list[bytes] = [
                # raw invalid UTF-8 / non-JSON bodies -> 400
                bytes(rng.randrange(128, 256) for _ in range(rng.randrange(1, 64))),
                b"\xff\xfe{" + bytes([rng.randrange(256)]) * 8,
                b"[1,2,3]",                       # JSON, wrong shape
                b'{"pod": null, "logs": "x"}',    # null pod -> 400
            ] + [
                json.dumps(
                    {"pod": {"metadata": {"name": f"fuzz-{seed}"}}, "logs": logs}
                ).encode("utf-8")
                for logs in _ingest_logs_cases(rng)
            ]
            for body in bodies:
                before = freq_stats()
                try:
                    status, payload = post(body)
                    if status == 200:
                        continue  # legitimate parse; state may evolve
                    if not 400 <= status < 500:
                        raise AssertionError(
                            f"unstructured failure {status}: {body[:80]!r}"
                        )
                    err = json.loads(payload)
                    if not isinstance(err, dict) or "error" not in err:
                        raise AssertionError(
                            f"4xx without structured error: {payload[:120]!r}"
                        )
                    if engine.bank is not base_bank:
                        raise AssertionError("reject swapped the bank")
                    if freq_stats() != before:
                        raise AssertionError(
                            f"reject mutated frequency state: {body[:80]!r}"
                        )
                except Exception as exc:  # noqa: BLE001 - recorded, sweep continues
                    fails.append((seed, repr(exc)[:300]))
                    print(f"SEED {seed} FAILED: {exc!r}", flush=True)
            if seed % 10 == 0:
                print(f"seed {seed} done ({time.time() - t0:.0f}s)", flush=True)
    finally:
        server.shutdown()
        server.server_close()
    print(f"DONE ingest seeds {start}..{end - 1} fails: {fails} "
          f"({time.time() - t0:.0f}s)")
    return 1 if fails else 0


def _stream_corpus(rng: "random.Random") -> bytes:
    """Seeded hostile byte corpus for the stream sweep: LF/CRLF mixes,
    multi-byte UTF-8, raw invalid bytes, NULs, control characters,
    over-budget lines, and real matching lines — ending sometimes on a
    dangling ``\\r`` or a truncated multi-byte sequence."""
    parts: list[bytes] = []
    for _ in range(rng.randrange(2, 14)):
        kind = rng.randrange(7)
        if kind == 0:
            parts.append(b"java.lang.OutOfMemoryError: Java heap space")
        elif kind == 1:
            parts.append(
                ("café über 你好 \U0001f600"
                 * rng.randrange(1, 3)).encode()
            )
        elif kind == 2:  # invalid UTF-8 runs -> U+FFFD, split-invariantly
            parts.append(
                bytes(rng.randrange(128, 256)
                      for _ in range(rng.randrange(1, 24)))
            )
        elif kind == 3:  # content NUL + control bytes (needs_host lines)
            parts.append(b"bad\x00nul" + bytes([rng.randrange(1, 32)]) * 4)
        elif kind == 4:
            parts.append(
                "".join(chr(rng.randrange(0x20, 0x7F))
                        for _ in range(rng.randrange(0, 40))).encode()
            )
        elif kind == 5:  # may exceed the per-line device budget
            parts.append(b"x" * rng.randrange(100, 5000))
        else:
            parts.append(b"OutOfMemoryError unable to create new native thread")
        parts.append(rng.choice([b"\n", b"\r\n"]))
    blob = b"".join(parts)
    if rng.random() < 0.3:
        blob = blob[: -rng.randrange(1, 3)]  # dangling tail / lone \r
    if rng.random() < 0.25:
        blob += "€".encode()[: rng.randrange(1, 3)]  # truncated sequence
    return blob


def _stream_chunkings(
    rng: "random.Random", data: bytes
) -> list[list[bytes]]:
    """Adversarial chunkings of one corpus: byte-at-a-time, random chunks
    with empties interspersed, and cuts placed exactly at every non-ASCII
    byte and every ``\\r``/``\\n`` — guaranteed splits inside multi-byte
    sequences and inside ``\\r\\n`` pairs."""
    outs: list[list[bytes]] = []
    if len(data) <= 400:
        outs.append([data[i : i + 1] for i in range(len(data))])
    chunks: list[bytes] = []
    i = 0
    while i < len(data):
        if rng.random() < 0.15:
            chunks.append(b"")
        n = rng.randrange(1, 17)
        chunks.append(data[i : i + n])
        i += n
    chunks.append(b"")
    outs.append(chunks)
    cuts = sorted(
        {i for i, b in enumerate(data) if b >= 0x80 or b in (0x0D, 0x0A)}
        | {0, len(data)}
    )
    outs.append([data[a:b] for a, b in zip(cuts, cuts[1:]) if a < b])
    return outs


def run_stream_sweep(start: int, end: int) -> int:
    """Fuzz the streaming session layer under adversarial chunkings: every
    session must produce only well-formed frames, terminate in exactly one
    ``final`` (or structured ``error``) frame, release its admission slot,
    and close bit-identical to one-shot ``analyze()`` on the reassembled
    blob — with frequency state staying serially equivalent between the
    streamed engine and a reference engine fed the same blobs. A periodic
    raw-socket pass throws garbage HTTP chunk framing at
    ``POST /parse/stream`` and must get a structured ``bad-frame`` error
    with the server still answering ``/health`` — a wedged session or
    server is the finding."""
    import json
    import random
    import socket
    import threading
    import urllib.request

    from tests.conftest import FakeClock

    from log_parser_tpu.config import ScoringConfig
    from log_parser_tpu.models.pod import PodFailureData
    from log_parser_tpu.patterns import load_pattern_directory
    from log_parser_tpu.runtime import AnalysisEngine
    from log_parser_tpu.runtime.stream import FRAME_TYPES
    from log_parser_tpu.serve.admission import shared_gate
    from log_parser_tpu.serve.http import make_server

    pattern_dir = os.path.join(_REPO, "log_parser_tpu", "patterns", "builtin")
    sets = load_pattern_directory(pattern_dir)
    engine = AnalysisEngine(sets, ScoringConfig(), clock=FakeClock())
    ref = AnalysisEngine(sets, ScoringConfig(), clock=FakeClock())
    server = make_server(engine, "127.0.0.1", 0)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    mgr = server.get_stream_manager()
    host, port = server.server_address[:2]

    def events_of(result_dict: dict) -> list[tuple]:
        return [
            (e["lineNumber"], e["matchedPattern"]["id"], e["score"])
            for e in result_dict.get("events", [])
        ]

    def run_session(chunks: list[bytes]) -> list[dict]:
        sess = mgr.open()
        frames: list[dict] = []
        for c in chunks:
            frames += sess.feed(c)
            if sess.closed:
                break
        if not sess.closed:
            frames += sess.close()
        if not sess.closed:
            raise AssertionError("session wedged: close() left it open")
        return frames

    def garbage_framing_pass() -> None:
        with socket.create_connection((host, port), timeout=60) as sock:
            sock.sendall(
                b"POST /parse/stream HTTP/1.1\r\nHost: fuzz\r\n"
                b"Transfer-Encoding: chunked\r\n\r\n"
                b"3\r\nOOM\r\nZZZ\r\n"
            )
            raw = b""
            while True:
                part = sock.recv(65536)
                if not part:
                    break
                raw += part
        body = raw.split(b"\r\n\r\n", 1)[1]
        err = [
            json.loads(ln)
            for ln in body.splitlines()
            if ln.strip() and json.loads(ln).get("type") == "error"
        ]
        if not err or err[-1]["reason"] != "bad-frame":
            raise AssertionError(f"garbage framing not contained: {body!r}")
        with urllib.request.urlopen(
            f"http://{host}:{port}/health", timeout=60
        ) as resp:
            if resp.status != 200:
                raise AssertionError("server unhealthy after garbage framing")

    t0 = time.time()
    fails: list[tuple[int, str]] = []
    try:
        for seed in range(start, end):
            rng = random.Random(seed)
            try:
                data = _stream_corpus(rng)
                blob = data.decode("utf-8", errors="replace")
                for chunks in _stream_chunkings(rng, data):
                    frames = run_session(chunks)
                    for f in frames:
                        if not isinstance(f, dict) or f.get("type") not in FRAME_TYPES:
                            raise AssertionError(f"malformed frame: {f!r}")
                    terminal = [f for f in frames if f["type"] in ("final", "error")]
                    if len(terminal) != 1 or frames[-1] is not terminal[0]:
                        raise AssertionError(
                            f"bad termination: {[f['type'] for f in frames]}"
                        )
                    if terminal[0]["type"] == "error":
                        continue  # structured failure is a legal outcome
                    want = ref.analyze(
                        PodFailureData(
                            pod={"metadata": {"name": "fuzz-stream"}}, logs=blob
                        )
                    ).to_dict(drop_none=True)
                    got = terminal[0]["result"]
                    if events_of(got) != events_of(want):
                        raise AssertionError(
                            f"replay divergence: {events_of(got)} != "
                            f"{events_of(want)}"
                        )
                ef = engine.frequency.get_frequency_statistics()
                rf = ref.frequency.get_frequency_statistics()
                if ef != rf:
                    raise AssertionError(
                        f"frequency stats diverge: {ef} != {rf}"
                    )
                if mgr.stats()["openSessions"] != 0:
                    raise AssertionError("leaked open session")
                if shared_gate(engine).stats()["inflight"] != 0:
                    raise AssertionError("leaked admission slot")
                if seed % 10 == 0:
                    garbage_framing_pass()
            except Exception as exc:  # noqa: BLE001 - recorded, sweep continues
                fails.append((seed, repr(exc)[:300]))
                print(f"SEED {seed} FAILED: {exc!r}", flush=True)
            if seed % 10 == 0:
                print(f"seed {seed} done ({time.time() - t0:.0f}s)", flush=True)
    finally:
        server.shutdown()
        server.server_close()
        mgr.shutdown()
    print(f"DONE stream seeds {start}..{end - 1} fails: {fails} "
          f"({time.time() - t0:.0f}s)")
    return 1 if fails else 0


def run_sweep(mode: str, start: int, end: int) -> int:
    from test_engine_parity import (  # the suite's generators ARE the spec
        assert_results_match,
        random_library,
        random_logs,
        random_long_library,
        random_long_logs,
    )
    from tests.conftest import FakeClock

    from log_parser_tpu.config import ScoringConfig
    from log_parser_tpu.golden import GoldenAnalyzer
    from log_parser_tpu.models.pod import PodFailureData
    from log_parser_tpu.parallel import (
        PatternShardedEngine,
        ShardedEngine,
        make_mesh,
    )
    from log_parser_tpu.runtime import AnalysisEngine

    mesh = make_mesh(8) if mode == "sharded" else None
    t0 = time.time()
    fails: list[tuple[int, str]] = []
    for seed in range(start, end):
        rng = random.Random(seed)
        # construction inside the guard: a library the compiler rejects
        # is exactly the kind of find the sweep records, not an abort.
        # Config variation, corpus counts, and the end-of-seed
        # frequency-stats check mirror the corresponding suite test
        # exactly (rng call order included, so seed N here draws the
        # same library the suite's seed N would).
        try:
            if mode == "sharded":
                sets = random_library(rng, rng.randrange(2, 6))
                config = ScoringConfig(frequency_threshold=rng.choice([2.0, 10.0]))
                engine = ShardedEngine(sets, config, mesh=mesh, clock=FakeClock())
                n_runs, lines_lo, lines_hi = 2, 5, 90
            elif mode == "pattern-sharded":
                sets = random_library(rng, rng.randrange(3, 7))
                config = ScoringConfig(frequency_threshold=rng.choice([2.0, 10.0]))
                engine = PatternShardedEngine(
                    sets,
                    config,
                    n_blocks=(1, 3, 4)[seed % 3],
                    clock=FakeClock(),
                )
                n_runs, lines_lo, lines_hi = 2, 20, 200
            elif mode == "long":
                sets = random_long_library(rng, rng.randrange(2, 6))
                config = ScoringConfig(proximity_max_window=rng.choice([5, 100]))
                engine = AnalysisEngine(sets, config, clock=FakeClock())
                # guard against a vacuous pass: the mode exists to fuzz
                # the bit tier's truncation/repair paths
                assert engine.matchers.bitglush is not None
                n_runs, lines_lo, lines_hi = 3, 5, 80
            else:
                sets = random_library(rng, rng.randrange(2, 8))
                config = ScoringConfig(
                    frequency_threshold=rng.choice([2.0, 10.0]),
                    proximity_max_window=rng.choice([5, 100]),
                )
                engine = AnalysisEngine(sets, config, clock=FakeClock())
                n_runs, lines_lo, lines_hi = 3, 5, 120
            golden = GoldenAnalyzer(sets, config, clock=FakeClock())
            gen_logs = random_long_logs if mode == "long" else random_logs
            for _ in range(n_runs):  # frequency state must evolve identically
                logs = gen_logs(rng, rng.randrange(lines_lo, lines_hi))
                data = PodFailureData(pod={"metadata": {"name": "fuzz"}}, logs=logs)
                assert_results_match(engine.analyze(data), golden.analyze(data))
            # explicit raise, not assert: python -O would strip an
            # assert (the startup guard below protects the suite-shared
            # assert-based checks too)
            ef = engine.frequency.get_frequency_statistics()
            gf = golden.frequency.get_frequency_statistics()
            if ef != gf:
                raise AssertionError(f"frequency stats diverge: {ef} != {gf}")
        except Exception as exc:  # noqa: BLE001 - recorded, sweep continues
            fails.append((seed, repr(exc)[:300]))
            print(f"SEED {seed} FAILED: {exc!r}", flush=True)
        if seed % 20 == 0:
            print(f"seed {seed} done ({time.time() - t0:.0f}s)", flush=True)
    print(f"DONE {mode} seeds {start}..{end - 1} fails: {fails} "
          f"({time.time() - t0:.0f}s)")
    return 1 if fails else 0


def _miner_hostile_lines(rng: "random.Random") -> list[bytes]:
    """Seeded hostile miss lines: everything a real corrupted log stream
    or an adversarial tenant could push through the line cache."""
    meta = b".*+?()[]{}|\\^$"
    cases = [
        # invalid UTF-8 runs
        bytes(rng.randrange(128, 256) for _ in range(rng.randrange(1, 200))),
        # NUL-riddled line
        b"abc\x00def \x00\x00 ghi" * rng.randrange(1, 8),
        # 1 MB single line (tokenizer must truncate, never choke)
        bytes([rng.randrange(33, 127)]) * (1 << 20),
        # regex metacharacter soup — the synthesizer must escape or demote
        bytes(rng.choice(meta) for _ in range(rng.randrange(4, 120))),
        # metachar tokens with whitespace structure (clusterable!)
        b" ".join(
            bytes(rng.choice(meta) for _ in range(rng.randrange(1, 12)))
            for _ in range(rng.randrange(2, 10))
        ),
        # control-character soup
        bytes(rng.randrange(0, 32) for _ in range(rng.randrange(1, 100))),
        # plausible template line with hostile slot values
        b"evict shard \xff\xfe\x00 after "
        + bytes([rng.randrange(256)]) * rng.randrange(1, 30),
        # whitespace-only and empty
        b" \t \t " * rng.randrange(1, 5),
        b"",
        # very many tokens (over MAX_TOKENS -> skipped, not mined)
        b"tok " * rng.randrange(40, 200),
    ]
    rng.shuffle(cases)
    return cases


def run_miner_sweep(start: int, end: int) -> int:
    """Fuzz the template miner (log_parser_tpu/mining/): hostile miss
    lines through the real tap → pump → cluster → synthesize → vet
    pipeline at ``min_support=1``. Findings: the miner raised (``errors``
    moved), the serving bank changed in review mode, or a synthesized
    regex failed the bank's own compile entry points."""
    import random

    from log_parser_tpu.analysis.tiers import classify_regex
    from log_parser_tpu.config import ScoringConfig
    from log_parser_tpu.golden.javacompat import compile_java_regex
    from log_parser_tpu.mining.synthesize import synthesize, template_regex
    from log_parser_tpu.mining.templates import TemplateClusterer
    from log_parser_tpu.runtime import AnalysisEngine

    from helpers import make_pattern, make_pattern_set

    engine = AnalysisEngine(
        [make_pattern_set([
            make_pattern("oom", regex="OutOfMemoryError", confidence=0.9),
            make_pattern("conn", regex="Connection refused", confidence=0.7),
        ])],
        ScoringConfig(),
    )
    engine.enable_line_cache(4)
    engine.enable_miner(
        mode="review", min_support=1, stability=0, autostart=False
    )
    base_bank = engine.bank
    t0 = time.time()
    fails: list[tuple[int, str]] = []
    for seed in range(start, end):
        rng = random.Random(seed)
        try:
            lines = _miner_hostile_lines(rng)
            # the real pipeline: offer -> pump (cluster/synthesize/vet)
            for line in lines:
                engine.miner.tap.offer(line)
            engine.miner.pump()
            stats = engine.miner.stats()
            if stats["errors"]:
                raise AssertionError(f"miner raised internally: {stats}")
            if engine.bank is not base_bank:
                raise AssertionError("review-mode miner swapped the bank")
            # independent synthesis check: EVERY promotable hostile
            # cluster's regex must re-parse through the bank's own
            # compile entry points
            cl = TemplateClusterer(min_support=1, stability=0)
            for line in lines:
                cl.observe(line)
            for cluster in cl.promotable():
                regex = template_regex(cluster.template)
                compile_java_regex(regex)  # raises on a bad emit
                pred = classify_regex(regex)
                if pred.tier == "skipped":
                    raise AssertionError(
                        f"synthesized regex off every tier "
                        f"({pred.reason_code}): {regex[:120]!r}"
                    )
                synthesize(cluster)  # full candidate must build too
        except Exception as exc:  # noqa: BLE001 - recorded, sweep continues
            fails.append((seed, repr(exc)[:300]))
            print(f"SEED {seed} FAILED: {exc!r}", flush=True)
        if seed % 10 == 0:
            print(f"seed {seed} done ({time.time() - t0:.0f}s)", flush=True)
    engine.miner.stop()
    print(f"DONE miner seeds {start}..{end - 1} fails: {fails} "
          f"({time.time() - t0:.0f}s)")
    return 1 if fails else 0


def _router_tenant_headers(rng: "random.Random") -> list[str]:
    """Hostile X-Tenant values. urllib refuses header injection itself,
    so the corpus stays latin-1-printable — the interesting surface is
    the edge validator, not the client library."""
    # the trailing "|" is outside [A-Za-z0-9._-], so the soup is always
    # invalid no matter what the prefix draws
    soup = "".join(
        rng.choice("abz09._-/\\~!$%&*()+=:;'\"<>?|{}[] ")
        for _ in range(rng.randrange(1, 40))
    ) + "|"
    return [
        "../evil",                          # traversal
        "..",                               # bare dots
        "a" * rng.randrange(65, 200),       # over the 64-char id bound
        "UPPER CASE",                       # space + case
        "acme/../default",                  # embedded traversal
        ".hidden",                          # leading dot
        "-dash-lead",                       # leading dash is refused
        soup,
        "%2e%2e%2fescape",                  # encoded traversal
        "tab\tin\ttenant",
    ]


def _router_garbage(rng: "random.Random") -> list[bytes]:
    return [
        bytes(rng.randrange(256) for _ in range(rng.randrange(1, 128))),
        b"GET / HTTP/9.9\r\n\r\n",
        b"POST /parse HTTP/1.1\r\nContent-Length: 99999999\r\n\r\nxx",
        b"\r\n\r\n\r\n",
        b"POST /parse HTTP/1.1\r\nTransfer-Encoding: chunked\r\n\r\nZZZ\r\n",
    ]


def run_router_sweep(start: int, end: int) -> int:
    """Fuzz the fleet-router front-door: hostile tenants are refused 400
    AT the edge (never proxied), hostile bodies/paths relay the
    backend's own verdict, malformed /fleet/override bodies answer 400
    with the ring untouched, raw-socket garbage never wedges the
    listener — and after every seed the router still routes."""
    import json
    import random
    import socket
    import threading
    import urllib.error
    import urllib.request

    from log_parser_tpu.config import ScoringConfig
    from log_parser_tpu.fleet.router import make_router
    from log_parser_tpu.patterns import load_pattern_directory
    from log_parser_tpu.runtime import AnalysisEngine
    from log_parser_tpu.serve.http import make_server

    pattern_dir = os.path.join(_REPO, "log_parser_tpu", "patterns", "builtin")
    engine = AnalysisEngine(load_pattern_directory(pattern_dir), ScoringConfig())
    backend = make_server(engine, "127.0.0.1", 0)
    threading.Thread(target=backend.serve_forever, daemon=True).start()
    backend_url = f"http://127.0.0.1:{backend.server_address[1]}"
    router = make_router("127.0.0.1", 0, [backend_url], down_after=5)
    threading.Thread(target=router.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{router.server_address[1]}"
    parse_body = json.dumps(
        {"pod": {"metadata": {"name": "fuzz"}}, "logs": "INFO boot"}
    ).encode()

    def req(path: str, body: bytes | None = None,
            headers: dict | None = None) -> tuple[int, bytes]:
        r = urllib.request.Request(
            url + path, data=body,
            headers={"Content-Type": "application/json", **(headers or {})},
        )
        try:
            with urllib.request.urlopen(r, timeout=60) as resp:
                return resp.status, resp.read()
        except urllib.error.HTTPError as e:
            return e.code, e.read()

    def ring_fingerprint() -> str:
        stats = router.ring.stats()
        return json.dumps(
            {"backends": stats["backends"], "overrides": stats["overrides"]},
            sort_keys=True,
        )

    t0 = time.time()
    fails: list[tuple[int, str]] = []
    try:
        for seed in range(start, end):
            rng = random.Random(seed)
            try:
                for tenant in _router_tenant_headers(rng):
                    try:
                        status, payload = req(
                            "/parse", parse_body, {"X-Tenant": tenant}
                        )
                    except ValueError:
                        continue  # urllib itself refused the header value
                    if status != 400:
                        raise AssertionError(
                            f"hostile tenant {tenant[:40]!r} answered "
                            f"{status}, want 400 at the edge"
                        )
                    err = json.loads(payload)
                    if "error" not in err:
                        raise AssertionError(
                            f"400 without structured error: {payload[:120]!r}"
                        )
                # hostile bodies and paths relay the backend verdict —
                # anything but a router-minted 5xx is acceptable
                hostile = bytes(
                    rng.randrange(256) for _ in range(rng.randrange(1, 256))
                )
                for path, body in (
                    ("/parse", hostile),
                    ("/parse", b"[]"),
                    (f"/no-such-{seed}", None),
                ):
                    status, _ = req(path, body)
                    if status >= 500:
                        raise AssertionError(
                            f"{path} answered {status} with the backend up"
                        )
                # malformed override bodies: 400, ring untouched
                ring_before = ring_fingerprint()
                for body in (
                    b"not json",
                    b"[]",
                    b"{}",
                    json.dumps({"tenant": "../evil",
                                "backend": backend_url}).encode(),
                    json.dumps({"tenant": "acme",
                                "backend": "http://10.0.0.1:1"}).encode(),
                    hostile,
                ):
                    status, _ = req("/fleet/override", body)
                    if status != 400:
                        raise AssertionError(
                            f"override fuzz answered {status}, want 400"
                        )
                if ring_fingerprint() != ring_before:
                    raise AssertionError("override fuzz mutated the ring")
                # raw-socket garbage must never wedge the listener
                for garbage in _router_garbage(rng):
                    with socket.create_connection(
                        ("127.0.0.1", router.server_address[1]), timeout=10
                    ) as s:
                        s.sendall(garbage)
                        s.settimeout(5)
                        try:
                            s.recv(4096)
                        except (socket.timeout, OSError):
                            pass
                # the router still routes after every hostile pass
                status, _ = req("/q/health")
                if status != 200:
                    raise AssertionError(f"health {status} after fuzz")
                status, _ = req("/parse", parse_body)
                if status != 200:
                    raise AssertionError(f"clean parse {status} after fuzz")
            except Exception as exc:  # noqa: BLE001 - recorded, sweep continues
                fails.append((seed, repr(exc)[:300]))
                print(f"SEED {seed} FAILED: {exc!r}", flush=True)
            if seed % 10 == 0:
                print(f"seed {seed} done ({time.time() - t0:.0f}s)", flush=True)
    finally:
        router.shutdown()
        router.server_close()
        backend.shutdown()
        backend.server_close()
    print(f"DONE router seeds {start}..{end - 1} fails: {fails} "
          f"({time.time() - t0:.0f}s)")
    return 1 if fails else 0


if __name__ == "__main__":
    sys.exit(main())
