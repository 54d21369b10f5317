"""Multi-tenant serving (runtime/tenancy.py): the isolation contract.

The anchor is interleaved-traffic parity: a tenant's responses under
interleaved multi-tenant traffic must be bit-identical to a dedicated
single-tenant engine run of its subsequence alone — unbatched, batched,
and streaming, line cache on and off. Around it: per-tenant state
non-bleed (frequency, line cache, quarantine), the quota 429 envelope
(Retry-After + ``tenant rate``/``tenant inflight``/``tenant queue``
reasons, plus the futile 413 ``tenant burst`` shed with NO Retry-After
for requests larger than the bucket's whole capacity), the resolve
lease (a pinned context is eviction-proof from resolution to the
transport's release), tenant-scoped hot reload that provably never quiesces another
tenant's engine, LRU eviction/rebuild under a bank budget, id
validation, and per-tenant line-cache isolation.
"""

from __future__ import annotations

import json
import threading
import urllib.error
import urllib.request

import pytest

from log_parser_tpu.config import ScoringConfig
from log_parser_tpu.models.pod import PodFailureData
from log_parser_tpu.patterns import load_pattern_directory
from log_parser_tpu.runtime import AnalysisEngine
from log_parser_tpu.runtime.stream import StreamManager
from log_parser_tpu.runtime.tenancy import (
    DEFAULT_TENANT,
    TenantError,
    TenantQuota,
    TenantRegistry,
)
from log_parser_tpu.serve import make_server
from log_parser_tpu.serve.admission import AdmissionController, AdmissionRejected

from helpers import make_pattern, make_pattern_set

# two tenants with DIFFERENT libraries over the same traffic: outputs
# must differ between tenants (separate banks) while each stays
# bit-identical to its dedicated engine
ACME_YAML = """
metadata:
  library_id: acme-lib
patterns:
  - id: oom
    name: Out of memory
    severity: CRITICAL
    primary_pattern:
      regex: OutOfMemoryError
      confidence: 0.9
  - id: err
    name: Errors
    severity: LOW
    primary_pattern:
      regex: "\\\\bERROR\\\\b"
      confidence: 0.5
"""

GLOBEX_YAML = """
metadata:
  library_id: globex-lib
patterns:
  - id: conn
    name: Connection refused
    severity: HIGH
    primary_pattern:
      regex: "Connection refused"
      confidence: 0.7
  - id: err
    name: Errors
    severity: MEDIUM
    primary_pattern:
      regex: "\\\\bERROR\\\\b"
      confidence: 0.6
"""

TRAFFIC = [
    "INFO boot\njava.lang.OutOfMemoryError: heap\nan ERROR here",
    "Connection refused by peer\nINFO ok",
    "ERROR twice\nERROR again\nOutOfMemoryError",
    "nothing to see",
    "Connection refused\njava.lang.OutOfMemoryError: metaspace\nERROR",
    "INFO a\nINFO b\nan ERROR here",
]


@pytest.fixture()
def root(tmp_path):
    for tid, text in (("acme", ACME_YAML), ("globex", GLOBEX_YAML)):
        d = tmp_path / "tenants" / tid
        d.mkdir(parents=True)
        (d / "lib.yaml").write_text(text)
    return str(tmp_path / "tenants")


def _default_engine() -> AnalysisEngine:
    return AnalysisEngine(
        [make_pattern_set([make_pattern("base", regex="BASE")], "base-lib")],
        ScoringConfig(),
    )


def _registry(root, **kw) -> TenantRegistry:
    return TenantRegistry(_default_engine(), root=root, **kw)


def _dedicated(root, tid, setup=None) -> AnalysisEngine:
    eng = AnalysisEngine(
        load_pattern_directory(f"{root}/{tid}"), ScoringConfig()
    )
    if setup is not None:
        setup(eng, tid)
    return eng


def _events(result) -> list[tuple]:
    d = result.to_dict(drop_none=True)
    return [
        (e["lineNumber"], e["matchedPattern"]["id"], e["score"])
        for e in d.get("events", [])
    ] + [
        (d["summary"]["significantEvents"], d["summary"]["highestSeverity"])
    ]


def _data(blob: str) -> PodFailureData:
    return PodFailureData(pod={"metadata": {"name": "t"}}, logs=blob)


# --------------------------------------------- interleaved-traffic parity


class TestInterleavedParity:
    @pytest.mark.parametrize("cache", [False, True], ids=["nocache", "cache"])
    def test_unbatched(self, root, cache):
        setup = (
            (lambda eng, tid: eng.enable_line_cache(8)) if cache else None
        )
        reg = _registry(root, engine_setup=setup)
        try:
            ded = {t: _dedicated(root, t, setup) for t in ("acme", "globex")}
            for i, blob in enumerate(TRAFFIC):
                tid = ("acme", "globex")[i % 2]
                got = _events(reg.resolve(tid).engine.analyze(_data(blob)))
                want = _events(ded[tid].analyze(_data(blob)))
                assert got == want, (tid, blob)
            # same traffic, different libraries: the tenants' outputs for
            # the shared ERROR line differ — banks are really separate
            a = _events(reg.resolve("acme").engine.analyze(_data(TRAFFIC[0])))
            g = _events(reg.resolve("globex").engine.analyze(_data(TRAFFIC[0])))
            assert a != g
        finally:
            reg.shutdown()

    def test_batched(self, root):
        def setup(eng, tid):
            eng.enable_batching(wait_ms=1.0, batch_max=4)

        reg = _registry(root, engine_setup=setup)
        try:
            ded = {t: _dedicated(root, t, setup) for t in ("acme", "globex")}
            try:
                for i, blob in enumerate(TRAFFIC):
                    tid = ("acme", "globex")[i % 2]
                    got = _events(
                        reg.resolve(tid).engine.analyze_batched(_data(blob))
                    )
                    want = _events(ded[tid].analyze_batched(_data(blob)))
                    assert got == want, (tid, blob)
            finally:
                for eng in ded.values():
                    eng.batcher.close()
        finally:
            reg.shutdown()

    def test_streaming(self, root):
        reg = _registry(root)
        try:
            ded = {t: _dedicated(root, t) for t in ("acme", "globex")}
            mgrs = {
                t: StreamManager(reg.resolve(t).engine)
                for t in ("acme", "globex")
            }
            dmgrs = {t: StreamManager(ded[t]) for t in ("acme", "globex")}
            try:
                blob = ("\n".join(TRAFFIC) + "\n").encode()
                chunks = [blob[i : i + 37] for i in range(0, len(blob), 37)]
                sess = {t: m.open() for t, m in mgrs.items()}
                dsess = {t: m.open() for t, m in dmgrs.items()}
                # interleave: both tenants' sessions advance chunk by chunk
                for c in chunks:
                    for t in ("acme", "globex"):
                        assert [
                            f["type"] for f in sess[t].feed(c)
                        ] == [f["type"] for f in dsess[t].feed(c)]
                for t in ("acme", "globex"):
                    got = sess[t].close()[-1]
                    want = dsess[t].close()[-1]
                    assert got["type"] == want["type"] == "final"
                    # analysisId / timing metadata are request-unique;
                    # the contract is on events + summary
                    for k in ("events", "summary"):
                        assert got["result"].get(k) == want["result"].get(k), t
            finally:
                for m in (*mgrs.values(), *dmgrs.values()):
                    m.shutdown()
        finally:
            reg.shutdown()


# ------------------------------------------------------ state non-bleed


class TestNonBleed:
    def test_frequency(self, root):
        reg = _registry(root)
        try:
            for _ in range(3):
                reg.resolve("acme").engine.analyze(_data("an ERROR here"))
            acme = reg.resolve("acme").engine.frequency
            globex = reg.resolve("globex").engine.frequency
            assert acme.get_frequency_statistics().get("err", 0) >= 3
            assert globex.get_frequency_statistics().get("err", 0) == 0
            assert (
                reg.default_context.engine.frequency
                .get_frequency_statistics().get("err", 0) == 0
            )
        finally:
            reg.shutdown()

    def test_line_cache(self, root):
        reg = _registry(
            root, engine_setup=lambda eng, tid: eng.enable_line_cache(8)
        )
        try:
            blob = TRAFFIC[0]
            reg.resolve("acme").engine.analyze(_data(blob))
            reg.resolve("acme").engine.analyze(_data(blob))
            reg.resolve("globex").engine.analyze(_data(blob))
            acme = reg.resolve("acme").engine.line_cache.stats()
            globex = reg.resolve("globex").engine.line_cache.stats()
            assert acme["hits"] > 0
            # globex saw the blob ONCE: its (separate) cache has no hits
            assert globex["hits"] == 0
        finally:
            reg.shutdown()

    def test_quarantine(self, root):
        reg = _registry(root)
        try:
            q = reg.resolve("acme").engine.quarantine
            fp = "deadbeef"
            for _ in range(10):
                if q.strike(fp):
                    break
            assert q.stats()["active"] >= 1
            assert reg.resolve("globex").engine.quarantine.stats()["active"] == 0
        finally:
            reg.shutdown()


# --------------------------------------------------------- quota ladder


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


class TestQuota:
    def test_rate_bucket_sheds_429(self):
        clk = _Clock()
        gate = AdmissionController(clock=clk)
        q = TenantQuota(lines_per_s=2.0, clock=clk)  # 4-token bucket
        assert gate.acquire(tenant=q, lines=3) == "device"
        gate.release(tenant=q)
        with pytest.raises(AdmissionRejected) as exc:
            gate.acquire(tenant=q, lines=3)
        assert exc.value.reason == "tenant rate"
        assert exc.value.status == 429
        assert exc.value.retry_after_s >= 1
        assert gate.stats()["shedTenant"] == 1
        assert q.stats()["shedRate"] == 1
        # the bucket refills with time: admitted again after 1s
        clk.t += 1.0
        assert gate.acquire(tenant=q, lines=3) == "device"
        gate.release(tenant=q)

    def test_inflight_cap_sheds_429(self):
        gate = AdmissionController()
        q = TenantQuota(max_inflight=1)
        gate.acquire(tenant=q, lines=1)
        with pytest.raises(AdmissionRejected) as exc:
            gate.acquire(tenant=q, lines=1)
        assert exc.value.reason == "tenant inflight"
        assert exc.value.status == 429
        assert q.stats()["shedInflight"] == 1
        gate.release(tenant=q)
        assert gate.acquire(tenant=q, lines=1) == "device"
        gate.release(tenant=q)

    def test_queue_share_sheds_429(self):
        gate = AdmissionController(max_inflight=1, max_queue=8)
        other = TenantQuota()
        gate.acquire(tenant=other, lines=1)  # saturate the global slot
        q = TenantQuota(max_queued=1)
        q.queued = 1  # the tenant's queue share is already taken
        with pytest.raises(AdmissionRejected) as exc:
            gate.acquire(tenant=q, lines=1)
        assert exc.value.reason == "tenant queue"
        assert exc.value.status == 429
        assert q.stats()["shedQueue"] == 1
        gate.release(tenant=other)

    def test_oversize_request_sheds_413_futile(self):
        """A request declaring more lines than the bucket can EVER hold
        (capacity = lines_per_s × burst) must not get a small finite
        Retry-After — that used to send the client into a permanent 429
        loop. It sheds 413 ``tenant burst`` with retry_after_s == 0."""
        clk = _Clock()
        gate = AdmissionController(clock=clk)
        q = TenantQuota(lines_per_s=2.0, clock=clk)  # 4-token bucket
        with pytest.raises(AdmissionRejected) as exc:
            gate.acquire(tenant=q, lines=5)
        assert exc.value.reason == "tenant burst"
        assert exc.value.status == 413
        assert exc.value.retry_after_s == 0
        assert "retrying will not help" in str(exc.value)
        assert q.stats()["shedOversize"] == 1
        assert q.stats()["shedRate"] == 0
        # time cannot help: the same request is still futile much later
        clk.t += 3600.0
        with pytest.raises(AdmissionRejected) as exc:
            gate.acquire(tenant=q, lines=5)
        assert exc.value.status == 413
        # a request that fits the whole burst still admits normally
        assert gate.acquire(tenant=q, lines=4) == "device"
        gate.release(tenant=q)

    def test_streams_bypass_the_bucket(self):
        # a session open carries lines=0: the bucket never debits
        clk = _Clock()
        gate = AdmissionController(clock=clk)
        q = TenantQuota(lines_per_s=1.0, clock=clk)
        for _ in range(5):
            gate.acquire(tenant=q, lines=0)
            gate.release(tenant=q)
        assert q.stats()["shedRate"] == 0


# ------------------------------------------------- HTTP quota envelope


def _post(url, payload, headers=None):
    req = urllib.request.Request(
        url,
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            return resp.status, json.loads(resp.read()), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


class TestHTTPQuotaEnvelope:
    def _serve(self, reg):
        server = make_server(reg.default_engine, "127.0.0.1", 0, tenants=reg)
        port = server.server_address[1]
        threading.Thread(target=server.serve_forever, daemon=True).start()
        return server, f"http://127.0.0.1:{port}/parse"

    def test_429_with_retry_after(self, root):
        # 4-token bucket for acme only: its 3-line request fits ONCE,
        # then the drained bucket sheds with a real retry window, while
        # globex and the default tenant are unbounded. The refill rate is
        # deliberately slow (0.2/s: the 2-token shortfall takes 10s to
        # recover) so a loaded host can't refill the bucket in the wall
        # clock between the two posts.
        reg = _registry(
            root,
            quota_factory=lambda tid: TenantQuota(
                lines_per_s=0.2 if tid == "acme" else 0.0,
                burst_s=20.0,
            ),
        )
        server, url = self._serve(reg)
        payload = {"pod": {"metadata": {"name": "q"}}, "logs": TRAFFIC[0]}
        try:
            assert _post(url, payload, {"X-Tenant": "acme"})[0] == 200
            status, body, headers = _post(
                url, payload, {"X-Tenant": "acme"}
            )
            assert status == 429, body
            assert body == {"error": "overloaded", "reason": "tenant rate"}
            assert int(headers["Retry-After"]) >= 1
            assert _post(url, payload, {"X-Tenant": "globex"})[0] == 200
            assert _post(url, payload)[0] == 200
        finally:
            server.shutdown()
            server.server_close()
            reg.shutdown()

    def test_oversize_request_is_413_without_retry_after(self, root):
        # 2-token bucket: acme's 3-line request can NEVER fit — the shed
        # must say so (413, no Retry-After) instead of promising a
        # retry window that will never help
        reg = _registry(
            root,
            quota_factory=lambda tid: TenantQuota(
                lines_per_s=1.0 if tid == "acme" else 0.0
            ),
        )
        server, url = self._serve(reg)
        payload = {"pod": {"metadata": {"name": "q"}}, "logs": TRAFFIC[0]}
        try:
            status, body, headers = _post(
                url, payload, {"X-Tenant": "acme"}
            )
            assert status == 413, body
            assert body == {"error": "overloaded", "reason": "tenant burst"}
            assert "Retry-After" not in headers
            assert _post(url, payload, {"X-Tenant": "globex"})[0] == 200
        finally:
            server.shutdown()
            server.server_close()
            reg.shutdown()


# ------------------------------------------------ tenant-scoped reload


class TestTenantReload:
    def test_reload_never_touches_other_tenants(self, root):
        """The pin for 'tenant hot reload completes while another
        tenant's requests are served': run acme's reload WHILE holding
        globex's engine.state_lock and while a thread hammers globex
        traffic. A global quiesce would deadlock on the held lock; the
        tenant-scoped one completes and bumps only acme's epoch."""
        reg = _registry(root)
        try:
            ctx_a = reg.resolve("acme")
            ctx_g = reg.resolve("globex")
            stop = threading.Event()
            errors: list[Exception] = []

            def hammer():
                while not stop.is_set():
                    try:
                        ctx_g.engine.analyze(_data(TRAFFIC[1]))
                    except Exception as exc:  # pragma: no cover
                        errors.append(exc)
                        return

            t = threading.Thread(target=hammer, daemon=True)
            t.start()
            done = threading.Event()
            out: dict = {}

            def reload_a():
                out["envelope"] = ctx_a.reloader().reload()
                ctx_a.note_reloaded()
                done.set()

            with ctx_g.engine.state_lock:
                r = threading.Thread(target=reload_a, daemon=True)
                r.start()
                assert done.wait(timeout=60), (
                    "tenant reload stalled behind another tenant's lock"
                )
            stop.set()
            t.join(timeout=30)
            assert not errors, errors
            assert ctx_a.engine.reload_epoch == 1
            assert ctx_g.engine.reload_epoch == 0
            assert reg.default_context.engine.reload_epoch == 0
        finally:
            reg.shutdown()


# ------------------------------------------------- residency / eviction


class TestResidency:
    def test_evict_and_rebuild_under_budget(self, root):
        probe = _registry(root)
        try:
            bank_bytes = probe.resolve("acme").bank_bytes
        finally:
            probe.shutdown()
        reg = _registry(root, budget_mb=bank_bytes * 1.5 / 2**20)
        try:
            first = reg.resolve("acme")
            assert _events(first.engine.analyze(_data(TRAFFIC[0])))
            first.unpin()  # request finished: the resolve lease ends
            # over budget: acme (LRU, idle) evicted
            reg.resolve("globex").unpin()
            assert reg.evicted == 1
            assert reg.context_if_resident("acme") is None
            rebuilt = reg.resolve("acme")  # rebuilds (and evicts globex)
            assert reg.rebuilds == 1
            assert rebuilt is not first
            # the rebuilt engine answers identically
            assert _events(rebuilt.engine.analyze(_data(TRAFFIC[0]))) == (
                _events(_dedicated(root, "acme").analyze(_data(TRAFFIC[0])))
            )
        finally:
            reg.shutdown()

    def test_busy_tenants_are_never_evicted(self, root):
        reg = _registry(root, budget_mb=0.001)  # everything is over budget
        try:
            ctx = reg.resolve("acme")
            ctx.unpin()  # lease released: quota state alone drives this
            ctx.quota.inflight = 1  # in-flight request holds the engine
            reg.resolve("globex").unpin()
            assert reg.context_if_resident("acme") is ctx  # deferred
            ctx.quota.inflight = 0
            # next resolve evicts the idle LRU
            reg.resolve("globex").unpin()
            assert reg.context_if_resident("acme") is None
        finally:
            reg.shutdown()

    def test_resolve_lease_pins_until_released(self, root):
        """The resolve→acquire window (review finding): a request holds
        its context from resolve() until the transport's release, with
        quota.inflight/queued still zero. Another tenant's resolve in
        that window must NOT evict and close() the engine out from
        under it — the pin makes the context busy for its whole life."""
        reg = _registry(root, budget_mb=0.001)  # everything is over budget
        try:
            ctx = reg.resolve("acme")  # pinned, no quota state yet
            assert ctx.quota.inflight == 0 and ctx.quota.queued == 0
            reg.resolve("globex").unpin()
            # acme survived: its journal/batcher were not closed under
            # the request that is still holding the context
            assert reg.context_if_resident("acme") is ctx
            assert _events(ctx.engine.analyze(_data(TRAFFIC[0])))
            ctx.unpin()  # transport finished: lease ends, eviction may run
            reg.resolve("globex").unpin()
            assert reg.context_if_resident("acme") is None
        finally:
            reg.shutdown()

    def test_stats_shape(self, root):
        reg = _registry(root)
        try:
            reg.resolve("acme")
            s = reg.stats()
            assert set(s) == {
                "residentTenants", "budgetMb", "residentBankMb", "resolved",
                "created", "evicted", "rebuilds", "unknown", "invalid",
                "forwarded", "forwards", "fenced", "fence", "perTenant",
            }
            assert set(s["perTenant"]) == {DEFAULT_TENANT, "acme"}
            per = s["perTenant"]["acme"]
            assert set(per) == {
                "bankBytes", "patterns", "reloadEpoch", "quota",
            }
            assert per["bankBytes"] > 0 and per["patterns"] == 2
        finally:
            reg.shutdown()


# ------------------------------------------------------- id resolution


class TestResolution:
    def test_default_and_none_map_to_default_tenant(self, root):
        reg = _registry(root)
        try:
            assert reg.resolve(None) is reg.default_context
            assert reg.resolve("") is reg.default_context
            assert reg.resolve(DEFAULT_TENANT) is reg.default_context
        finally:
            reg.shutdown()

    @pytest.mark.parametrize(
        "bad", ["../evil", "a/b", "", ".hidden", "x" * 65]
    )
    def test_traversal_ids_are_400(self, root, bad):
        reg = _registry(root)
        try:
            if bad == "":
                return  # empty maps to default, covered above
            with pytest.raises(TenantError) as exc:
                reg.resolve(bad)
            assert exc.value.status == 400
            assert reg.invalid >= 1
        finally:
            reg.shutdown()

    def test_unknown_tenant_is_404(self, root):
        reg = _registry(root)
        try:
            with pytest.raises(TenantError) as exc:
                reg.resolve("ghost")
            assert exc.value.status == 404
            assert reg.unknown == 1
        finally:
            reg.shutdown()

    def test_no_root_means_single_tenant_404(self):
        reg = TenantRegistry(_default_engine())
        try:
            with pytest.raises(TenantError) as exc:
                reg.resolve("acme")
            assert exc.value.status == 404
            assert "tenant-root" in str(exc.value)
        finally:
            reg.shutdown()

    def test_concurrent_first_touch_builds_once(self, root):
        reg = _registry(root)
        try:
            got: list = []
            lock = threading.Lock()

            def one():
                ctx = reg.resolve("acme")
                with lock:
                    got.append(ctx)

            threads = [threading.Thread(target=one) for _ in range(6)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert len(got) == 6
            assert all(c is got[0] for c in got)
            assert reg.created == 1  # coalesced: ONE build
        finally:
            reg.shutdown()
