"""shard_map pipeline: fused match + integer-factor extraction on a
line-sharded batch.

One jitted SPMD program per library: every shard scans its own lines
through the DFA bank (zero communication — lines are independent for
matching, AnalysisService.java:89-113), then extracts the integer factor
components of ops/fused.py with the narrowest collective each one needs:

==================  =========================================================
factor component    communication
==================  =========================================================
chronological       none (global line index is shard offset + local index)
secondary dists     ``ppermute`` halo of the secondary-match columns
                    (window ≤ halo), or ``all_gather`` when shards are
                    smaller than the halo
context counts      same halo machinery over the four context-flag columns
sequence flags      ``all_gather`` of the (few) sequence-event columns —
                    the backward scan is unbounded (ScoringService.java:
                    296-305), so each shard keeps the full column and the
                    chain runs as local gathers
frequency           NONE — line-sharding is contiguous, so concatenating
                    per-shard record blocks in shard order reproduces global
                    discovery order, and the host finalizer recovers every
                    read-before-record prior from the stream itself
==================  =========================================================

Each shard compacts its matches into a local K-capped record buffer;
outputs are per-shard record blocks that the host concatenates (shard-major
= line-major = discovery order) and feeds to the same exact-f64 finalizer
as the single-device engine. No float64 — and no floating point at all —
ever runs on the devices.

Halo rows are masked-valid *before* exchange, so shard edges and batch
padding contribute nothing.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from log_parser_tpu import _clock as pclock
from log_parser_tpu.config import ScoringConfig
from log_parser_tpu.ops.fused import (
    K_LADDER,
    NO_HIT,
    FusedStaticTables,
    MatchRecords,
    _prefix,
    _prev_next_dist,
    compact_records,
    sequence_flags_from_events,
)
from log_parser_tpu.parallel.mesh import DATA_AXIS
from log_parser_tpu.patterns.bank import (
    CTX_ERROR,
    CTX_EXCEPTION,
    CTX_STACK,
    CTX_WARN,
    PatternBank,
)
from log_parser_tpu.runtime.engine import AnalysisEngine
from log_parser_tpu.utils.trace import NO_TRACE


def _ring_halo(x: jax.Array, h: int, d: int) -> jax.Array:
    """[Bl, K] -> [h + Bl + h, K]: h rows from each ring neighbor via
    ppermute; edge shards receive zeros (ppermute's missing-source fill).
    ``d`` is the mesh axis size — the permutation list must be static, so
    the caller passes it rather than querying the traced axis."""
    from_left = jax.lax.ppermute(
        x[-h:], DATA_AXIS, [(i, i + 1) for i in range(d - 1)]
    )
    from_right = jax.lax.ppermute(
        x[:h], DATA_AXIS, [(i + 1, i) for i in range(d - 1)]
    )
    return jnp.concatenate([from_left, x, from_right], axis=0)


class ShardedRun(NamedTuple):
    """One request's pass through :class:`ShardedFusedStep`: its records,
    the SPMD launches the K ladder took (1 when the first rung held every
    shard's matches) and the per-shard record capacity that held them."""

    records: MatchRecords
    launches: int
    k_local: int


class ShardedFusedStep:
    """The full per-batch SPMD program, shard_mapped over the mesh."""

    def __init__(
        self,
        bank: PatternBank,
        config: ScoringConfig,
        mesh,
        matchers,
        multiprocess: bool | None = None,
    ):
        self.bank = bank
        self.config = config
        self.mesh = mesh
        self.matchers = matchers  # MatcherBanks: tiered Shift-Or + DFA cube
        self.t = FusedStaticTables(bank, config)
        self.n_shards = mesh.devices.size

        # static halo requirement per factor family
        self.h_prox = int(self.t.sec_window.max()) if len(self.t.sec_window) else 0
        has_rules = bank.has_context_rules
        self.h_ctx = int(
            max(
                bank.ctx_before[has_rules].max(initial=0),
                bank.ctx_after[has_rules].max(initial=0),
            )
        ) if bank.n_patterns else 0

        self._jit = jax.jit(
            lambda kl, lines, lens, om, ov, n: self._sharded(kl)(lines, lens, om, ov, n),
            static_argnums=(0,),
        )
        # one mesh may span multiple processes (parallel/distributed.py);
        # then inputs must be assembled as global arrays (each process
        # donating its addressable shards) and outputs gathered across
        # processes before host assembly. A process-local mesh inside a
        # multi-process runtime (the degrade-to-local step) passes an
        # explicit False: its collectives must never leave this process.
        self.multiprocess = (
            jax.process_count() > 1 if multiprocess is None else multiprocess
        )

    # ------------------------------------------------- host<->device helpers

    def _put(self, x, spec) -> jax.Array:
        """Device-put respecting the multi-process mesh: every process holds
        the full host value (requests are replicated by broadcast), so each
        donates the shards it addresses."""
        if not self.multiprocess:
            return jnp.asarray(x)
        from jax.sharding import NamedSharding

        arr = np.asarray(x)
        return jax.make_array_from_callback(
            arr.shape, NamedSharding(self.mesh, spec), lambda idx: arr[idx]
        )

    def _host(self, x) -> np.ndarray:
        """Fetch a (possibly process-spanning) device array to every host."""
        if not self.multiprocess:
            return np.asarray(x)
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(x, tiled=True))

    def _read_back(self, outs, trace) -> list[np.ndarray]:
        """Wait for ``outs`` (``device.wait``), then copy them to the host
        (``device.readback``), as ``ops/fused._read_back`` does: each
        copy is queued before the wait, so it starts when the program
        ends. A process-spanning array is gathered after the wait."""
        if not self.multiprocess:
            for x in outs:
                x.copy_to_host_async()
        with trace.stage("device.wait"):
            jax.block_until_ready(outs)
        with trace.stage("device.readback"):
            return [self._host(x) for x in outs]

    def _sharded(self, k_local: int):
        return jax.shard_map(
            lambda lines, lens, om, ov, n: self._step(k_local, lines, lens, om, ov, n),
            mesh=self.mesh,
            in_specs=(
                P(DATA_AXIS, None),  # lines [B, T] (transposed on device)
                P(DATA_AXIS),  # lengths [B]
                P(DATA_AXIS, None),  # override_mask [B, C]
                P(DATA_AXIS, None),  # override_val [B, C]
                P(),  # n_lines
            ),
            out_specs=(
                P(DATA_AXIS),  # n_matches per shard [D]
                P(DATA_AXIS),  # rec line (global) [D*K_l]
                P(DATA_AXIS),  # rec pattern [D*K_l]
                P(DATA_AXIS, None),  # rec sec dists [D*K_l, S_max]
                P(DATA_AXIS, None),  # rec seq flags [D*K_l, Q_max]
                P(DATA_AXIS, None),  # rec ctx counts [D*K_l, 5]
            ),
            check_vma=False,
        )

    # ------------------------------------------------------------- host API

    def __call__(
        self,
        lines_u8: np.ndarray,
        lengths: np.ndarray,
        override_mask: np.ndarray,
        override_val: np.ndarray,
        n_lines: int,
        k_hint: int = 0,
        trace=NO_TRACE,
    ) -> ShardedRun:
        """Runs the SPMD step, growing per-shard record buffers until every
        shard's matches fit; returns globally-ordered match records.
        ``trace`` (a PhaseTrace) times the ``device.upload``,
        ``device.launch``, ``device.wait`` and ``device.readback`` stages,
        as the one-chip step does."""
        B = lines_u8.shape[0]
        D = self.n_shards
        cap_local = (B // D) * max(1, self.bank.n_patterns)
        # contiguous [B, T] upload; the step transposes on device (a host
        # .T copy measured ~9x the contiguous upload — ops/fused.py)
        with trace.stage("device.upload"):
            lines_bt = self._put(lines_u8, P(DATA_AXIS, None))
            lens = self._put(lengths, P(DATA_AXIS))
            om = self._put(override_mask, P(DATA_AXIS, None))
            ov = self._put(override_val, P(DATA_AXIS, None))
            n = self._put(np.asarray(n_lines, dtype=np.int32), P())

        start = 0
        per_shard_hint = -(-max(1, k_hint) // D)
        while start < len(K_LADDER) - 1 and K_LADDER[start] < per_shard_hint:
            start += 1
        for launches, k_bucket in enumerate((*K_LADDER[start:], cap_local), 1):
            k_l = min(k_bucket, cap_local)
            with trace.stage("device.launch"):
                out = self._jit(k_l, lines_bt, lens, om, ov, n)
            (n_per_shard,) = self._read_back(out[:1], trace)
            if n_per_shard.max(initial=0) <= k_l or k_l >= cap_local:
                return ShardedRun(
                    self._assemble(k_l, n_per_shard, out, trace), launches, k_l
                )
        raise AssertionError("unreachable: ladder capped at per-shard B*P")

    def _assemble(self, k_l: int, n_per_shard: np.ndarray, out,
                  trace) -> MatchRecords:
        """Concatenate each shard's live records; shard-major order is
        line-major order because line sharding is contiguous."""
        D = self.n_shards
        line, pat, dist, seq, ctx = self._read_back(out[1:], trace)
        line = line.reshape(D, k_l)
        pat = pat.reshape(D, k_l)
        dist = dist.reshape(D, k_l, -1)
        seq = seq.reshape(D, k_l, -1)
        ctx = ctx.reshape(D, k_l, -1)
        keep = [np.arange(min(int(n), k_l)) for n in n_per_shard]
        return MatchRecords(
            n_matches=int(sum(len(k) for k in keep)),
            line=np.concatenate([line[d, k] for d, k in enumerate(keep)] or [line[0, :0]]),
            pattern=np.concatenate([pat[d, k] for d, k in enumerate(keep)] or [pat[0, :0]]),
            sec_dist=np.concatenate([dist[d, k] for d, k in enumerate(keep)] or [dist[0, :0]]),
            seq_ok=np.concatenate([seq[d, k] for d, k in enumerate(keep)] or [seq[0, :0]]),
            ctx_counts=np.concatenate([ctx[d, k] for d, k in enumerate(keep)] or [ctx[0, :0]]),
        )

    def exchange_bytes(self, B: int) -> int:
        """Bytes one launch's collectives deliver between chips for a
        ``B``-row batch, from the static shapes :meth:`_step` exchanges:
        each halo family's two ``ppermute`` directions, ``D - 1`` sends
        of ``h`` rows each (edge shards receive zeros), or, where the
        halo reaches past a neighbour, the ``D - 1`` shards each one
        receives from an ``all_gather``; and the sequence events'
        ``all_gather``. Mirrors the choices of :meth:`_extend`,
        :meth:`_secondary_distances`, :meth:`_sequence_flags` and
        :meth:`_context_counts`."""
        D = self.n_shards
        Bl = B // D
        if self.bank.n_patterns == 0:
            return 0

        def neighbourhood(h: int, row_bytes: int) -> int:
            if h < Bl:
                return 2 * (D - 1) * h * row_bytes
            return D * (D - 1) * Bl * row_bytes

        total = 0
        if len(self.t.sec_cols):  # bool secondary columns
            total += neighbourhood(max(1, self.h_prox), len(self.t.sec_cols))
        total += neighbourhood(max(1, self.h_ctx), 4 * 4)  # 4 int32 flags
        if self.bank.sequences:  # bool event columns, all_gathered
            total += D * (D - 1) * Bl * len(self.t.seq_event_cols)
        return total

    # ------------------------------------------------------------ the step

    def _step(self, K, lines_bt, lengths, override_mask, override_val, n_lines):
        lines_tb = lines_bt.T  # device-side layout change (see run())
        bank, t = self.bank, self.t
        Bl = lengths.shape[0]
        P_ = bank.n_patterns
        d = jax.lax.axis_index(DATA_AXIS)
        lidx = jnp.arange(Bl, dtype=jnp.int32)
        gidx = (d * Bl + lidx).astype(jnp.int32)
        valid = gidx < n_lines

        # ---- local match (no communication; tiered Shift-Or + DFA) --------
        # barrier as in ops/fused.py: keep XLA from fusing factor
        # extraction back into the scan loops
        cube = jax.lax.optimization_barrier(
            self.matchers.cube(lines_tb, lengths)
        )
        cube = jnp.where(override_mask, override_val, cube)
        cube = cube & valid[:, None]

        if P_ == 0:
            z32 = jnp.zeros((K,), jnp.int32)
            return (
                jnp.zeros((1,), jnp.int32),
                z32,
                z32,
                jnp.full((K, max(1, t.s_max)), NO_HIT, jnp.int32),
                jnp.zeros((K, max(1, t.q_max)), bool),
                jnp.zeros((K, 5), jnp.int32),
            )

        pm = cube[:, jnp.asarray(bank.primary_columns)]  # [Bl, P]

        sec_dist = self._secondary_distances(cube, lidx, Bl)
        seq_ok = self._sequence_flags(cube, gidx, Bl, n_lines)
        ctx_counts = self._context_counts(cube, gidx, lidx, Bl, n_lines)

        # per-shard compaction: emit global line indexes, gather local rows
        n_matches, rec_gline, rec_pat, rec_dist, rec_seq, rec_ctx = compact_records(
            K, pm, t, gidx, lidx, sec_dist, seq_ok, ctx_counts
        )
        return n_matches[None], rec_gline, rec_pat, rec_dist, rec_seq, rec_ctx

    # ---------------------------------------------------------- factor parts

    def _extend(self, cols: jax.Array, h: int, Bl: int):
        """Neighborhood view of sharded columns: (extended array, offset of
        local row 0). ppermute halo when shards are big enough; all_gather
        when the halo would span multiple shards."""
        if h < Bl:
            return _ring_halo(cols, h, self.n_shards), h  # offset is static
        gathered = jax.lax.all_gather(cols, DATA_AXIS, axis=0, tiled=True)
        d = jax.lax.axis_index(DATA_AXIS)
        return gathered, d * Bl  # offset is traced

    def _secondary_distances(self, cube, lidx, Bl):
        """[Bl, n_sec_entries] int32 nearest-hit distance per local line.
        Exact for every in-window hit: any hit within window ≤ h is inside
        the extended view; farther hits report NO_HIT, which the finalizer
        treats identically to out-of-window (ScoringService.java:315-347)."""
        t = self.t
        if len(t.sec_cols) == 0:
            return jnp.full((Bl, 1), NO_HIT, jnp.int32)
        sm = cube[:, jnp.asarray(t.sec_cols)]  # [Bl, S]
        h = max(1, self.h_prox)
        ext, off = self._extend(sm, h, Bl)
        mine = off + lidx  # my rows in ext coordinates
        return _prev_next_dist(ext, jnp.arange(ext.shape[0], dtype=jnp.int32))[mine]

    def _sequence_flags(self, cube, gidx, Bl, n_lines):
        """[Bl, n_sequences] — the backward chain reads arbitrarily far back
        (ScoringService.java:296-305), so the event columns are all_gathered
        and the shared chain logic runs in global coordinates for local rows."""
        t = self.t
        if not self.bank.sequences:
            return jnp.zeros((Bl, 1), dtype=bool)
        em_local = cube[:, jnp.asarray(t.seq_event_cols, dtype=np.int32)]  # [Bl, E]
        em = jax.lax.all_gather(em_local, DATA_AXIS, axis=0, tiled=True)  # [B, E]
        return sequence_flags_from_events(self.bank.sequences, t, em, gidx, n_lines)

    def _context_counts(self, cube, gidx, lidx, Bl, n_lines):
        """[Bl, U, 5] int32 per unique context shape, window sums via
        halo-extended prefix sums with the global clamps of
        AnalysisService.java:142,148 expressed on the global index."""
        t = self.t
        err = cube[:, CTX_ERROR]
        warn = cube[:, CTX_WARN] & ~err
        stack = cube[:, CTX_STACK]
        exc = cube[:, CTX_EXCEPTION]
        flags = jnp.stack([err, warn, stack, exc], axis=1).astype(jnp.int32)  # [Bl, 4]

        h = max(1, self.h_ctx)
        ext, off = self._extend(flags, h, Bl)
        ps = _prefix(ext)  # [ext+1, 4]
        ext_len = ext.shape[0]
        mine = off + lidx

        per_shape = []
        for has_rules, before, after in t.ctx_shapes:
            if not has_rules:
                counts = flags
                total = jnp.ones((Bl,), jnp.int32)
            else:
                lo_g = jnp.maximum(gidx - before, 0)
                hi_g = jnp.minimum(gidx + 1 + after, n_lines).astype(jnp.int32)
                hi_g = jnp.maximum(hi_g, lo_g)
                total = hi_g - lo_g
                lo_e = jnp.clip(mine - (gidx - lo_g), 0, ext_len)
                hi_e = jnp.clip(mine + (hi_g - gidx), 0, ext_len)
                counts = ps[hi_e] - ps[lo_e]  # [Bl, 4]
            per_shape.append(jnp.concatenate([counts, total[:, None]], axis=1))
        return jnp.stack(per_shape, axis=1)  # [Bl, U, 5]


class ShardedEngine(AnalysisEngine):
    """AnalysisEngine whose device step is the shard_map program: the line
    batch is sharded over the mesh, and every other responsibility (ingest,
    host verification, frequency tracking, exact-f64 finalization, result
    assembly, observability) is the inherited shared pipeline."""

    def __init__(self, pattern_sets, config=None, mesh=None, clock=None):
        import time as _time

        super().__init__(pattern_sets, config, clock=clock or pclock.mono)
        if mesh is None:
            from log_parser_tpu.parallel.mesh import make_mesh

            mesh = make_mesh()
        self.mesh = mesh
        self.step = ShardedFusedStep(self.bank, self.config, mesh, self.matchers)
        self.tables = self.step.t

    def _install_library(self, source) -> None:
        # the SPMD program and its static tables are compiled against the
        # bank — rebuild both on the swapped library (hot reload)
        super()._install_library(source)
        self.step = ShardedFusedStep(
            self.bank, self.config, self.mesh, self.matchers
        )
        self.tables = self.step.t

    def _corpus_min_rows(self) -> int:
        # row padding must be divisible by the mesh size for shard_map
        return max(8, self.mesh.devices.size)

    def _run_device(self, enc, n_lines: int, om, ov, trace=NO_TRACE):
        return self._run_step(self.step, enc, n_lines, om, ov, trace)

    def _run_step(self, step: ShardedFusedStep, enc, n_lines: int, om, ov,
                  trace=NO_TRACE) -> MatchRecords:
        """One request through ``step``, its launches, exchanged bytes and
        record slots counted (``logparser_shard_*``)."""
        B = enc.u8.shape[0]
        C = self.bank.n_columns
        if om is None:  # the SPMD program's in_specs always take overrides
            om = np.zeros((B, C), dtype=bool)
            ov = np.zeros((B, C), dtype=bool)
        run = step(
            enc.u8, enc.lengths, om, ov, n_lines, k_hint=self._k_hint,
            trace=trace,
        )
        self.obs.note_shard_step(
            self.obs_tenant,
            relaunches=run.launches - 1,
            exchange_bytes=run.launches * step.exchange_bytes(B),
            record_slots=step.n_shards * run.k_local,
            records=run.records.n_matches,
        )
        return run.records
