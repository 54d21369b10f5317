"""The simulated fleet: router + backends + warm standby in one process.

Every node is the same production stack the live servers run — a
``TenantRegistry`` (per-tenant engines, journaled frequency state), a
``Migrator`` (live moves) and, on the replication pair, a ``Replicator``
(WAL shipping / fenced failover) — wired over per-node state dirs and the
shared :class:`~log_parser_tpu.sim.transport.SimNet`.  ``kill()`` is the
journal layer's own ``abandon()`` (byte-for-byte what ``kill -9`` leaves);
``revive()`` rebuilds the same objects over the same dirs and runs the
production ``recover()`` paths, exactly like the PR 16/17 crash-matrix
tests — just composed across planes instead of one boundary at a time.

Bookkeeping the invariants need (never visible to production code):

* ``controls`` — one fault-free engine per tenant on the same virtual
  clock, fed every request the owner accepted (the PR 16 parity control).
* ``durable`` — per (node, tenant), the control's raw state at the last
  instant the tenant's journal was fsync-durable; a lossy crash forks the
  control back to this checkpoint, because that is what the disk holds.
* ``acked`` — per replicated tenant, the control's raw state at the last
  zero-lag ship; a promotion forks the control here (the unshipped tail
  is the documented failover loss, not a bug).  A standby crash clears
  the checkpoints — after a lossy standby restart the shipped prefix is
  unknown, so the next promotion re-anchors instead of guessing.
"""

from __future__ import annotations

import os

from log_parser_tpu.config import ScoringConfig
from log_parser_tpu.fleet.ring import HashRing
from log_parser_tpu.models.pattern import (
    Pattern,
    PatternSet,
    PatternSetMetadata,
    PrimaryPattern,
)
from log_parser_tpu.models.pod import PodFailureData
from log_parser_tpu.patterns import load_pattern_directory
from log_parser_tpu.runtime import AnalysisEngine
from log_parser_tpu.runtime.migrate import (
    LocalTarget,
    MigrationCrash,
    MigrationError,
    Migrator,
    SOURCE_RECORDS,
)
from log_parser_tpu.runtime.replicate import (
    LocalReplicaTarget,
    Replicator,
)
from log_parser_tpu.runtime.tenancy import (
    TenantError,
    TenantForwarded,
    TenantRegistry,
)
from log_parser_tpu.sim.transport import SimMigrationTarget, SimNet, SimReplicaTarget

MAX_FORWARD_HOPS = 4

# the traffic corpus: deterministic blobs exercising multi-pattern matches
TRAFFIC = (
    "INFO boot\njava.lang.OutOfMemoryError: heap\nan ERROR here",
    "Connection refused by peer\nINFO ok",
    "ERROR twice\nERROR again\nOutOfMemoryError",
    "nothing to see",
    "Connection refused\njava.lang.OutOfMemoryError: metaspace\nERROR",
    "INFO a\nINFO b\nan ERROR here",
)

TENANT_LIBS = {
    "acme": """
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
""",
    "globex": """
metadata:
  library_id: globex-lib
patterns:
  - id: conn
    name: Connection refused
    severity: HIGH
    primary_pattern:
      regex: "Connection refused"
      confidence: 0.7
""",
}


def write_tenant_root(root: str) -> str:
    """Materialize the fixed tenant libraries under ``root``."""
    for tid, text in TENANT_LIBS.items():
        d = os.path.join(root, tid)
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, "lib.yaml"), "w", encoding="utf-8") as f:
            f.write(text)
    return root


def _base_pattern_set() -> PatternSet:
    return PatternSet(
        metadata=PatternSetMetadata(library_id="base-lib", name="base-lib"),
        patterns=[
            Pattern(
                id="base", name="base", severity="LOW",
                primary_pattern=PrimaryPattern(regex="BASE", confidence=0.5),
            )
        ],
    )


def events_of(result) -> list:
    """The parity projection (the PR 16 technique): per event the line,
    pattern id and score, plus the summary verdict."""
    d = result.to_dict(drop_none=True)
    return [
        (e["lineNumber"], e["matchedPattern"]["id"], e["score"])
        for e in d.get("events", [])
    ] + [
        (d["summary"]["significantEvents"], d["summary"]["highestSeverity"])
    ]


def _data(blob: str) -> PodFailureData:
    return PodFailureData(pod={"metadata": {"name": "sim"}}, logs=blob)


# One fully-compiled template engine per (fixed) library, shared across
# every fleet/run in the process via the ``_install_library`` transplant
# seam the fleet router's shared-pack path uses. Without it each of the
# dozens of engines a seed sweep builds would re-trace the fused device
# program — seconds per run instead of tens of milliseconds.
_TEMPLATES: dict[str, object] = {}


def _share_compiled(eng, key: str, sets_factory):
    tmpl = _TEMPLATES.get(key)
    if tmpl is None:
        tmpl = AnalysisEngine(sets_factory(), ScoringConfig())
        for blob in TRAFFIC:  # trace every shape the corpus dispatches
            tmpl.analyze(_data(blob))
        _TEMPLATES[key] = tmpl
    with eng.state_lock:
        eng._install_library(tmpl)
    return eng


class SimNode:
    """One simulated process: registry + migrator (+ replicator)."""

    def __init__(self, fleet: "SimFleet", name: str, *,
                 standby_of: str | None = None, standby: str | None = None):
        self.fleet = fleet
        self.name = name
        self.standby_of = standby_of   # set on the standby: its primary
        self.standby = standby         # set on the primary: its standby
        self.state_dir = os.path.join(fleet.state_root, name)
        os.makedirs(self.state_dir, exist_ok=True)
        self.registry: TenantRegistry | None = None
        self.migrator: Migrator | None = None
        self.replicator: Replicator | None = None
        self.alive = False

    # ------------------------------------------------------------ build

    def build(self) -> None:
        fleet = self.fleet
        clk = fleet.wall_clock
        state = self.state_dir

        def setup(eng, tid):
            _share_compiled(
                eng, tid,
                lambda: load_pattern_directory(
                    os.path.join(fleet.tenant_root, tid)
                ),
            )
            eng.attach_journal(os.path.join(state, "wal", tid), wall=clk)

        default_engine = _share_compiled(
            AnalysisEngine([_base_pattern_set()], ScoringConfig(), clock=clk),
            "__base__", lambda: [_base_pattern_set()],
        )
        self.registry = TenantRegistry(
            default_engine, root=fleet.tenant_root, clock=clk,
            engine_setup=setup,
        )
        if self.standby_of is None:
            self.migrator = Migrator(
                self.registry, state_root=state,
                node_url=f"local://{self.name}",
            )
        target = None
        peer = None
        if self.standby is not None:
            target = SimReplicaTarget(
                fleet.net, self.name, self.standby,
                fleet._replica_inner(self.standby),
            )
        if self.standby_of is not None:
            peer = f"local://{self.standby_of}"
        if target is not None or peer is not None:
            self.replicator = Replicator(
                self.registry, state_root=state,
                node_url=f"local://{self.name}",
                peer_url=peer, target=target, clock=clk, wall=clk,
            )
        self.alive = True

    def recover(self) -> dict:
        """The boot-time convergence sweep each production process runs —
        migrator first, replicator last, the serve/__main__ boot order
        (the replication role's fences/forwards must win arbitration),
        then the cross-plane hooks wired and the migration ownership
        verdicts replayed through them, exactly as serve/__main__ does."""
        out = {}
        if self.migrator is not None:
            out["migrate"] = self.migrator.recover(
                self.fleet.migration_targets(self.name)
            )
        if self.replicator is not None:
            out["replica"] = self.replicator.recover()
            if self.migrator is not None:
                self.migrator.on_release = self.replicator.release_tenant
                self.migrator.on_adopt = self.replicator.adopt_tenant
                self.migrator.on_primacy_check = \
                    self.replicator.verify_primacy
                for tid in out["migrate"].get("forwards", ()):
                    fwd = self.registry.forward_for(tid)
                    if fwd:
                        self.replicator.release_tenant(
                            tid, fwd[0], ship=False
                        )
                for tid in out["migrate"].get("owned", ()):
                    self.replicator.adopt_tenant(tid, ship=False)
        return out

    # ------------------------------------------------------------- kill

    def _journaled_engines(self):
        reg = self.registry
        if reg is None:
            return
        with reg._lock:
            ctxs = list(reg._contexts.values())
        for ctx in ctxs:
            j = getattr(ctx.engine, "journal", None)
            if j is not None:
                yield j
        j = getattr(reg.default_engine, "journal", None)
        if j is not None:
            yield j

    def kill(self) -> None:
        """``kill -9``: drop every handle without the clean-shutdown
        fsync/snapshot. Per-append flush means the on-disk bytes are
        exactly the durable prefix."""
        for j in self._journaled_engines():
            j.abandon()
        if self.replicator is not None:
            try:
                self.replicator._journal.close()
            except OSError:  # pragma: no cover
                pass
        self.registry = None
        self.migrator = None
        self.replicator = None
        self.alive = False

    def shutdown(self) -> None:
        if not self.alive:
            return
        self.kill()  # journals are append-durable; abandon loses nothing here

    # ------------------------------------------------------ owner probes

    def resident(self, tenant: str) -> bool:
        if not self.alive or self.registry is None:
            return False
        with self.registry._lock:
            return tenant in self.registry._contexts

    def accepts(self, tenant: str) -> bool:
        """Would a request for *tenant* be served locally (no fence, no
        forward)? Pure probe — never builds an engine."""
        if not self.alive or self.registry is None:
            return False
        if self.registry.fence_for() is not None:
            return False
        return self.registry.forward_for(tenant) is None


class SimFleet:
    def __init__(self, state_root: str, tenant_root: str, clock,
                 *, backends=("a", "b"), standby=("s", "a"),
                 tenants=("acme", "globex")):
        self.state_root = state_root
        self.tenant_root = tenant_root
        self.clock = clock
        self.wall_clock = clock.wall  # bound method: the shared callable
        self.net = SimNet()
        self.backends = list(backends)
        self.standby_name, self.primary_name = standby
        self.tenants = list(tenants)
        self.ring = HashRing(self.backends)
        self.nodes: dict[str, SimNode] = {}
        # invariant bookkeeping
        self.controls: dict[str, AnalysisEngine] = {}
        self.durable: dict[tuple[str, str], dict] = {}
        self.acked: dict[str, dict] = {}
        self.last_owner: dict[str, str] = {}
        self.overrides: dict[str, str] = {}
        self.fencing_pending: set[str] = set()
        self.pending_reanchor: dict[str, str] = {}
        # tenants that migrated off the replication pair while the release
        # notice could not reach the standby (partition / standby down):
        # until the pump delivers it, a promotion resurrects a stale warm
        # copy there — the documented release-in-flight loss window,
        # tolerated by SIM-I1 the way fencing_pending tolerates a
        # rebooted stale primary
        self.release_unshipped: set[str] = set()
        self.parity_exact = True
        self.degraded = False
        self.serves = 0
        self.serve_failures = 0

        # standby first (the _pair idiom): its boot fence must exist
        # before the primary's first ship
        sb = SimNode(self, self.standby_name, standby_of=self.primary_name)
        self.nodes[self.standby_name] = sb
        sb.build()
        sb.recover()
        for b in self.backends:
            n = SimNode(
                self, b,
                standby=self.standby_name if b == self.primary_name else None,
            )
            self.nodes[b] = n
            n.build()
            n.recover()

    # ------------------------------------------------------- wiring help

    def _replica_inner(self, dst: str):
        def get_inner():
            node = self.nodes.get(dst)
            if node is None or not node.alive or node.replicator is None:
                return None
            return LocalReplicaTarget(node.replicator, url=f"local://{dst}")
        return get_inner

    def _migration_target(self, src: str, dst: str) -> SimMigrationTarget:
        def get_inner():
            node = self.nodes.get(dst)
            if node is None or not node.alive or node.migrator is None:
                return None
            return LocalTarget(node.migrator, url=f"local://{dst}")
        return SimMigrationTarget(self.net, src, dst, get_inner)

    def migration_targets(self, src: str) -> dict:
        return {
            f"local://{dst}": self._migration_target(src, dst)
            for dst in self.backends if dst != src
        }

    def control(self, tenant: str) -> AnalysisEngine:
        eng = self.controls.get(tenant)
        if eng is None:
            eng = _share_compiled(
                AnalysisEngine(
                    load_pattern_directory(
                        os.path.join(self.tenant_root, tenant)
                    ),
                    ScoringConfig(), clock=self.wall_clock,
                ),
                tenant,
                lambda: load_pattern_directory(
                    os.path.join(self.tenant_root, tenant)
                ),
            )
            self.controls[tenant] = eng
        return eng

    # ------------------------------------------------------------ lifecycle

    def kill(self, name: str) -> bool:
        node = self.nodes[name]
        if not node.alive:
            return False
        node.kill()
        self.fencing_pending.discard(name)
        if name == self.standby_name:
            # after a lossy standby restart the shipped prefix on its disk
            # is unknowable from out here: drop the expectation, the next
            # promotion re-anchors
            self.acked.clear()
        return True

    def revive(self, name: str) -> dict | None:
        node = self.nodes[name]
        if node.alive:
            return None
        node.build()
        summary = node.recover()
        rep = node.replicator
        if node.standby is not None:
            sb = self.nodes.get(node.standby)
            if sb is not None and sb.alive and sb.replicator is not None \
                    and sb.replicator.role == "primary" \
                    and rep is not None and rep.role == "primary":
                # a rebooted old primary whose standby promoted meanwhile:
                # a stale owner until its first ship is rejected by the
                # higher epoch — the documented convergence window
                # invariant SIM-I1 tolerates exactly until that pump
                self.fencing_pending.add(name)
        if node.standby_of is not None and rep is not None \
                and rep.role == "primary":
            # the standby crashed mid/after-promote and recovered as the
            # owner: surface the placement signal and re-anchor controls
            primary = self.nodes.get(node.standby_of)
            if primary is not None and primary.alive \
                    and primary.replicator is not None \
                    and primary.replicator.role == "primary":
                self.fencing_pending.add(node.standby_of)
            self._note_promoted(node)
        # the disk now holds exactly the durable prefix: fork each control
        # this node owns back to its durable checkpoint
        for tenant in self.tenants:
            if self.last_owner.get(tenant) == name:
                ckpt = self.durable.get((name, tenant))
                if ckpt is not None:
                    self.control(tenant).frequency._load_state(ckpt)
        return summary

    def shutdown(self) -> None:
        for node in self.nodes.values():
            node.shutdown()

    # ------------------------------------------------------------- routing

    def route_chain(self, tenant: str) -> list[str]:
        """The nodes a request would visit: override/ring owner, then
        the forward chain, capped at MAX_FORWARD_HOPS."""
        chain = []
        cur = self.overrides.get(tenant) or self.ring.owner(tenant)
        for _ in range(MAX_FORWARD_HOPS):
            chain.append(cur)
            node = self.nodes.get(cur)
            if node is None or not node.alive or node.registry is None:
                return chain
            reg = node.registry
            fwd = reg.fence_for() or reg.forward_for(tenant)
            if fwd is None:
                return chain
            nxt = fwd[0].rsplit("://", 1)[-1]
            if nxt == cur:
                return chain
            cur = nxt
        chain.append(cur)
        return chain

    def serve(self, tenant: str, blob_idx: int) -> dict:
        """Route one request through the fleet; on success feed the
        fault-free control the same blob at the same instant and compare
        the event projections (realtime half of invariant SIM-I2)."""
        blob = TRAFFIC[blob_idx % len(TRAFFIC)]
        self.serves += 1
        chain = self.route_chain(tenant)
        end = chain[-1]
        node = self.nodes.get(end)
        out = {"tenant": tenant, "chain": chain}
        if node is None or not node.alive or len(chain) > MAX_FORWARD_HOPS:
            self.serve_failures += 1
            out.update(ok=False, reason=self._explain_failure(tenant, chain))
            return out
        try:
            ctx = node.registry.resolve(tenant)
        except (TenantForwarded, TenantError) as exc:
            self.serve_failures += 1
            out.update(
                ok=False, status=getattr(exc, "status", 500),
                reason=self._explain_failure(tenant, chain),
            )
            return out
        try:
            if self.pending_reanchor.get(tenant) == end:
                # first serve on a promoted owner that never received this
                # tenant's state: the pre-failover history is documented
                # loss, so the expectation restarts from what recovered
                with ctx.engine.state_lock:
                    self.control(tenant).frequency._load_state(
                        ctx.engine.frequency._save_state()
                    )
                del self.pending_reanchor[tenant]
            got = events_of(ctx.engine.analyze(_data(blob)))
            journal = getattr(ctx.engine, "journal", None)
            durable = journal is not None and not journal.degraded
            if node.replicator is not None and node.replicator.target is not None:
                node.replicator.attach_sender(tenant, ctx.engine)
        finally:
            ctx.unpin()
        want = events_of(self.control(tenant).analyze(_data(blob)))
        self.last_owner[tenant] = end
        if end != chain[0]:
            self.overrides[tenant] = end  # the router learns the 307
        if durable:
            self.durable[(end, tenant)] = \
                self.control(tenant).frequency._save_state()
        out.update(ok=True, node=end, blob=blob_idx,
                   parity=(got == want))
        return out

    def _explain_failure(self, tenant: str, chain: list[str]) -> str | None:
        """Attribute a failed serve to an active fault, or None —
        an unexplained 5xx (invariant SIM-I3 fires on None)."""
        end = self.nodes.get(chain[-1])
        if end is None or not end.alive:
            return f"node {chain[-1]} is down"
        if len(chain) > MAX_FORWARD_HOPS:
            # a forward loop is never explained — it IS the historical
            # A->B->A resurrection bug; report it for SIM-I4 to catch
            return None
        reg = end.registry
        if reg is not None and reg.fence_for() is not None:
            return f"node {chain[-1]} is a fenced standby"
        if reg is not None and reg.forward_for(tenant) is not None:
            return f"forward chain truncated at {chain[-1]}"
        return None

    # ---------------------------------------------------------- pump hooks

    def pump(self, name: str) -> dict:
        node = self.nodes.get(name)
        if node is None or not node.alive or node.replicator is None:
            return {}
        outcomes = node.replicator.pump_all()
        rep = node.replicator
        if self.release_unshipped:
            # the window closes when the release has nowhere left to
            # come from: no live replicator holds it pending AND no dead
            # node's journal could still produce it at revive
            any_dead = any(not n.alive for n in self.nodes.values())
            self.release_unshipped = {
                t for t in self.release_unshipped
                if any_dead or any(
                    n.alive and n.replicator is not None
                    and t in n.replicator._release_pending
                    for n in self.nodes.values()
                )
            }
        if rep.role != "primary":
            # the stale primary's ship was rejected by the standby's
            # higher epoch and it demoted (re-fencing itself): the
            # split-brain grace window is over
            self.fencing_pending.discard(name)
        if rep.role == "primary" and rep.target is not None:
            with rep._lock:
                senders = dict(rep._senders)
            for tenant, sender in senders.items():
                # zero WAL lag only proves the standby is caught up when
                # the WAL is actually receiving appends: under hard disk
                # pressure served events divert to the in-memory ring, so
                # the checkpoint must not advance past what shipped
                if sender.seeded and sender.lag_bytes == 0 \
                        and not self.degraded \
                        and tenant in self.controls:
                    self.acked[tenant] = \
                        self.control(tenant).frequency._save_state()
        return outcomes

    def _note_promoted(self, node: SimNode) -> None:
        """Placement bookkeeping after the standby became the owner: the
        replication pair's placement flips wholesale (every tenant the old
        primary effectively owned now routes to the standby), and each
        control forks to the acked prefix — the unshipped tail is the
        documented failover loss.  A tenant the standby never received
        (or whose checkpoint a lossy standby restart invalidated) has no
        trustworthy expectation: re-anchor on the recovered state, at
        promote time if resident, else lazily on its first serve."""
        old = node.standby_of or self.primary_name
        for tenant in self.tenants:
            owner = self.last_owner.get(tenant) or self.ring.owner(tenant)
            if owner != old and owner != node.name:
                continue  # a tenant migrated off the pair keeps its owner
            self.overrides[tenant] = node.name
            self.last_owner[tenant] = node.name
            ctl = self.control(tenant)
            if node.resident(tenant):
                ckpt = self.acked.get(tenant)
                if ckpt is not None:
                    ctl.frequency._load_state(ckpt)
                else:
                    reg = node.registry
                    ctx = reg.resolve(tenant, ignore_forward=True)
                    try:
                        with ctx.engine.state_lock:
                            ctl.frequency._load_state(
                                ctx.engine.frequency._save_state()
                            )
                    finally:
                        ctx.unpin()
                self.durable[(node.name, tenant)] = \
                    ctl.frequency._save_state()
            else:
                self.pending_reanchor[tenant] = node.name

    def promote(self, reason: str = "admin") -> dict | None:
        """Admin-path promotion of the standby. ``ReplicationError`` /
        ``ReplicaCrash`` propagate — the harness classifies them."""
        node = self.nodes[self.standby_name]
        if not node.alive or node.replicator is None:
            return None
        if node.replicator.role == "primary":
            return {"status": "primary"}
        out = node.replicator.promote(reason=reason)
        primary = self.nodes.get(self.primary_name)
        if primary is not None and primary.alive \
                and primary.replicator is not None \
                and primary.replicator.role == "primary":
            self.fencing_pending.add(self.primary_name)
        self._note_promoted(node)
        return out

    def migrate(self, tenant: str, dst: str,
                crash_after: str | None = None) -> dict:
        """Run a live move from the current owner to ``dst``. A
        ``crash_after`` record kind turns this into a crash-matrix op:
        the crashed side is killed at the fsync'd record boundary."""
        src = self.last_owner.get(tenant) or self.ring.owner(tenant)
        node = self.nodes.get(src)
        if src == dst or node is None or not node.alive \
                or node.migrator is None:
            return {"outcome": "noop", "src": src}
        dst_node = self.nodes.get(dst)
        if dst_node is None or not dst_node.alive \
                or dst_node.migrator is None:
            return {"outcome": "noop", "src": src}
        mig = node.migrator
        target = self._migration_target(src, dst)
        kinds = frozenset({crash_after} if crash_after else ())
        pre_epoch = self._journal_epoch(node, tenant)
        try:
            mig.crash_after = kinds
            dst_node.migrator.crash_after = kinds
            res = mig.migrate(tenant, target)
            outcome = {"outcome": res["outcome"], "src": src, "dst": dst}
        except MigrationCrash:
            # the crashed process dies at the record boundary; which side
            # depends on whose journal carries the record kind
            crashed = src if crash_after in SOURCE_RECORDS else dst
            if crashed == src and crash_after == "complete":
                # died after COMPLETE: the handoff fully landed — the
                # target activated, the forward was set and the release
                # notified — so ownership bookkeeping mirrors the
                # completed path (the release may still be pending if
                # the standby was unreachable when it was notified)
                rep = node.replicator
                released = rep is None \
                    or tenant not in rep._release_pending
                self.kill(crashed)
                self.last_owner[tenant] = dst
                self.overrides[tenant] = dst
                self.durable[(dst, tenant)] = \
                    self.control(tenant).frequency._save_state()
                if dst != self.primary_name:
                    self.acked.pop(tenant, None)
                    if src == self.primary_name and not released:
                        self.release_unshipped.add(tenant)
            elif crashed == src and crash_after == "cutover":
                # died at the commit record: ownership is committed in
                # the source's journal but the import is NOT live (the
                # target activates after cutover) and the release never
                # left the process. The tenant is unavailable until the
                # source revives and recover() resumes the handoff; the
                # standby cannot learn of the cutover until then — the
                # release-in-flight loss window SIM-I1 tolerates
                self.kill(crashed)
                if src == self.primary_name:
                    self.release_unshipped.add(tenant)
                # when the revived source resumes the handoff, the
                # target restores the bundle's age-relative frequency
                # snapshot rebased to apply time: re-anchor the raw-
                # timestamp control on the first serve at the target
                self.pending_reanchor[tenant] = dst
            elif crashed == src and crash_after in ("export", "import_ack"):
                # pre-cutover source crash, but the export fold already
                # sealed the full live state into the snapshot: the
                # source's durable prefix advanced past the last durable
                # serve, so the revive expectation must not regress
                self.kill(crashed)
                self.durable[(src, tenant)] = \
                    self.control(tenant).frequency._save_state()
            elif crashed == dst and crash_after in ("activate", "applied"):
                # post-cutover target crash: ownership committed (the
                # live source holds the forward and notified the
                # release) and the target's boot replay re-applies the
                # bundle — whose age-relative frequency snapshot rebases
                # to revive time, so the raw-timestamp control is no
                # longer owed byte-exactly: re-anchor it on the state
                # the target recovers, at its first serve there
                self.kill(crashed)
                self.pending_reanchor[tenant] = dst
                if dst != self.primary_name:
                    self.acked.pop(tenant, None)
                    rep = getattr(self.nodes.get(src), "replicator", None)
                    if rep is not None and tenant in rep._release_pending:
                        self.release_unshipped.add(tenant)
            else:
                self.kill(crashed)
            outcome = {"outcome": "crash", "src": src, "dst": dst,
                       "crashed": crashed, "at": crash_after}
        except MigrationError as exc:
            outcome = {"outcome": "refused", "src": src, "dst": dst,
                       "status": exc.status}
        finally:
            for n in (self.nodes[src], dst_node):
                if n.alive and n.migrator is not None:
                    n.migrator.crash_after = frozenset()
        if outcome["outcome"] == "completed":
            self.last_owner[tenant] = dst
            self.overrides[tenant] = dst
            self.durable[(dst, tenant)] = \
                self.control(tenant).frequency._save_state()
            if dst != self.primary_name:
                # the tenant left the replication pair: the shipped-prefix
                # checkpoint no longer predicts anything a promotion
                # could recover
                self.acked.pop(tenant, None)
                rep = getattr(self.nodes.get(src), "replicator", None)
                if rep is not None and tenant in rep._release_pending:
                    self.release_unshipped.add(tenant)
        elif self.nodes[src].alive \
                and self._journal_epoch(self.nodes[src], tenant) != pre_epoch:
            # a refusal or target-side crash after the export fold: the
            # tenant stays at the source, but the fold sealed the full
            # live state into its snapshot — the durable prefix advanced
            # past the last durable serve checkpoint
            self.durable[(src, tenant)] = \
                self.control(tenant).frequency._save_state()
        return outcome

    def _journal_epoch(self, node: SimNode, tenant: str) -> int | None:
        """The tenant engine's journal epoch on *node*, or None when the
        tenant is not resident there — snapshot_now() bumps it, so a
        changed epoch across a migration attempt means the export fold
        ran (and durably sealed the live state)."""
        if not node.alive or node.registry is None:
            return None
        ctx = node.registry.context_if_resident(tenant)
        if ctx is None:
            return None
        j = getattr(ctx.engine, "journal", None)
        return None if j is None else j.epoch

    # ------------------------------------------------------------ disk ops

    def enter_disk_hard(self) -> int:
        """Shared-disk ENOSPC: every journal diverts to its in-memory
        ring (the pressure ladder's hard response)."""
        n = 0
        self.degraded = True
        for node in self.nodes.values():
            if node.alive:
                for j in node._journaled_engines():
                    j.degrade()
                    n += 1
        return n

    def recover_disk(self) -> int:
        """Pressure cleared: re-arm every journal (snapshot + truncate),
        which makes the CURRENT live state the durable baseline."""
        n = 0
        self.degraded = False
        for node in self.nodes.values():
            if node.alive:
                for j in node._journaled_engines():
                    if j.rearm():
                        n += 1
        for tenant, owner in self.last_owner.items():
            node = self.nodes.get(owner)
            if node is not None and node.alive and node.resident(tenant):
                self.durable[(owner, tenant)] = \
                    self.control(tenant).frequency._save_state()
        return n

    def rotate_wals(self, name: str) -> int:
        node = self.nodes.get(name)
        if node is None or not node.alive:
            return 0
        if self.degraded:
            # under hard disk pressure the production snapshot writer
            # skips atomically (pressure.writes_paused()); the sim sets
            # journal-level degrade without the process-wide controller,
            # so the gate is modeled here — a forced rotate must not
            # durably seal ring-diverted state
            return 0
        return sum(1 for j in node._journaled_engines() if j.snapshot_now())

    def ack_skew(self, tenant: str, delta: int = 3) -> bool:
        """Corrupt a sender's resume offset (the misaligned-resume
        hazard): the production fix reseeds on the next pump."""
        primary = self.nodes.get(self.primary_name)
        if primary is None or not primary.alive \
                or primary.replicator is None:
            return False
        with primary.replicator._lock:
            sender = primary.replicator._senders.get(tenant)
        if sender is None or not sender.seeded or sender.acked_offset <= 0:
            return False
        sender.acked_offset = max(1, sender.acked_offset - delta)
        return True

    def supervise(self) -> str | None:
        """One standby-side failover probe (FailoverSupervisor source of
        truth: consecutive-downtime promotion)."""
        node = self.nodes[self.standby_name]
        if not node.alive or node.replicator is None \
                or node.replicator.role == "primary":
            return None
        rep = node.replicator
        if rep.supervisor is None:
            def probe():
                return (
                    self.nodes[self.primary_name].alive
                    and not self.net.partitioned(
                        self.standby_name, self.primary_name
                    )
                )

            rep.arm_failover(
                f"local://{self.primary_name}", after_s=5.0, poll_s=1.0,
            )
            rep.supervisor.probe = probe
        verdict = rep.supervisor.check_once()
        if verdict == "promoted":
            primary = self.nodes.get(self.primary_name)
            if primary is not None and primary.alive \
                    and primary.replicator is not None \
                    and primary.replicator.role == "primary":
                self.fencing_pending.add(self.primary_name)
            self._note_promoted(node)
        return verdict
