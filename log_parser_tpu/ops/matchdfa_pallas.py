"""Pallas TPU kernel for the union multi-DFA reported-flag scan.

The lax.scan implementation (ops/match.py ``MultiDfaBank`` /
``MultiDfaCluster``) pays one ``[B]`` (or ``[B, G]``) flat-table gather
per byte — and TPU gathers run on the scalar unit at ~9 ns/element
(PERF.md §1/§4), which is the measured binding constraint of the multi
tier. This kernel keeps the transition planes resident in VMEM and
replaces the per-step gather with MXU one-hot matmuls vectorized across
the batch tile:

- the table is re-encoded densely as ``v' = next_state * 2 + reported``
  (``next_state < 8192`` under the union state budget, so ``v' <= 16383``
  fits two exact 8-bit matmul planes — TPU matmuls run at bfloat16
  precision, 8-bit mantissa, the same plane split as bitglush_pallas.py);
- the byte axis is BYTE-CLASS COMPRESSED (PERF.md §16): planes are
  ``[n_classes_pad, S_pad]`` over the group's ~dozens of distinct byte
  classes, not ``[256, S_pad]`` over raw bytes — a tiny per-group
  ``[1, 256]`` class-map row contracts against the transposed byte
  one-hot (``[256, TILE]``, iota-over-sublanes compared against the byte
  row — never materialized in HBM) to yield each lane's class, a second
  one-hot over classes then contracts with the planes. Both the VMEM
  footprint and the MXU contraction shrink by 256/n_classes (~4–10×);
- the state select is a lane-iota compare against the carried state
  column (``[TILE, 1]``) summed over lanes — a vector select, not a
  gather;
- scan state (state, reported) stays in VMEM across a ``fori_loop`` over
  the byte steps (the unrolled form blew the Mosaic compile past 9
  minutes on the bitglush kernel at T=64; the loop form compiles in
  seconds), with single-stride and pair-stride variants (the pair
  variant mirrors the fused scan's byte-pair steps; both orders visit
  every byte and are bit-identical);
- groups ride the grid: ``grid = (G, B // TILE)`` with each group's
  class map + plane pair streamed per grid step, so one ``pallas_call``
  advances the whole union cluster.

Padding is gate-free exactly like the scan tier: the class map routes
byte 0 to a per-group IDENTITY class whose plane row self-loops carrying
the state's own report flag (content NULs never reach the device), so no
length gating is needed and the reported OR past end-of-line is an
idempotent re-OR. The exact flagged-row accept recovery
(``_multi_contribution`` — out-word re-scan of flagged rows with the
``lax.cond`` dense fallback) deliberately stays on the XLA tier: it
touches only the rare flagged rows, so the gather there is not on the
hot path.

Admission: ``build_dfa_plan`` packs each group's minimized automaton
(patterns/regex/minimize.py runs at compile time) into class-compressed
planes and, when the padded geometry still blows the scoped-VMEM budget
(Mosaic scopes ~16 MB; we budget 12 MB and leave the rest for the byte
tile, the one-hots, and the ``[TILE, S_pad]`` temporaries), RE-SPLITS
the offending union group into the cheapest admissible k-way partition
(``entries`` supplies the group's regexes) instead of refusing outright
— refusal (``table_too_large``) remains only for callers that cannot
recompile (no entries) or groups inadmissible even alone. The admitted
plan carries the (possibly re-partitioned) groups and a ``geometry``
report (states before/after minimization, byte classes, plane bytes,
chosen split) surfaced on ``/trace/last`` and tools/probe_kernels.py.
``dfa_tile`` re-checks at call time against the actual T and shrinks the
batch tile before giving up — callers fall back to the XLA scan tier on
``None``. Mosaic-friendly dialect throughout: int32 only, logical
shifts via ``jax.lax.shift_right_logical``, no bool vectors (compare
results are cast immediately), 128-aligned lane slices (``S_pad``) and
8-aligned sublane counts (``nc_pad``). Per-group operands (class map,
starts) and the output carry a squeezed leading group axis, so every
block's last two dims meet Mosaic's rule: divisible by (8, 128) or
equal to the array's (tests/test_tpu_compile.py compiles it for a
described v5e).

Semantics are IDENTICAL to the scan tier's reported-flag carry —
verified bit-exactly by tests/test_matchdfa_pallas.py (interpreter
mode) and adjudicated on live TPU by tools/probe_kernels.py.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from log_parser_tpu.ops.bitglush_pallas import _SRL, _dotT, pick_tile

# Smaller than bitglush's 512: the [TILE, S_pad] transition-row
# temporaries scale with the tile, and the planes already claim most of
# the budget at large S.
DFA_TILE_B = 128
DFA_VMEM_BUDGET = 12 * 1024 * 1024
# T used for admission when the batch's padded length is not yet known
# (host-side tier predicates); dfa_tile re-checks with the real T.
_NOMINAL_T = 512

_REPORT_BIT = 1 << 30  # MultiDfaBank._REPORT_BIT
_STATE_MASK = _REPORT_BIT - 1

# Tier reason codes surfaced in /trace/last (kernel block) and pinned to
# docs/OPS.md rows by tools/hygiene.py. Keep keys snake_case words.
REASONS = {
    "byte_classed": "kernel admitted as packed: minimized byte-class "
    "planes fit the VMEM budget without re-partitioning",
    "split": "kernel admitted after re-partitioning: the cheapest "
    "admissible union-group split replaced the packed groups",
    "off": "LOG_PARSER_TPU_PALLAS_DFA unset (default) — XLA scan tier",
    "no_union_groups": "bank packed no union multi-DFA groups",
    "table_too_large": "dense planes exceed the VMEM budget — XLA scan",
    "no_tile": "no usable batch tile for this batch size — XLA scan",
}

#: reason codes meaning "an admissible plan exists" (provenance split)
ADMITTED = frozenset({"byte_classed", "split"})


@dataclass
class DfaKernelPlan:
    """Host-packed kernel operands for one bank's union groups."""

    cmap: np.ndarray  # [G, 256] float32 byte→class, byte 0 → identity class
    p0: np.ndarray  # [nc_pad, G * s_pad] float32: (state*2 + rep) & 0xFF
    p1: np.ndarray  # [nc_pad, G * s_pad] float32: (state*2 + rep) >> 8
    starts: np.ndarray  # [G, 2] int32: (start state, start reported)
    s_pad: int
    nc_pad: int
    n_groups: int
    # the (possibly re-partitioned) MultiDfaBank groups this plan serves,
    # in plane order — callers adopt these so scan-tier fallbacks and the
    # kernel agree on group membership
    groups: list = field(default_factory=list)
    # admission report: states before/after minimization, byte classes,
    # plane bytes, chosen split (see build_dfa_plan)
    geometry: dict = field(default_factory=dict)


def _pad_states(n: int) -> int:
    return max(128, -(-n // 128) * 128)  # 128-aligned lane slices


def _pad_classes(n: int) -> int:
    # +1 for the identity padding class; 8-aligned f32 sublanes
    return max(8, -(-(n + 1) // 8) * 8)


def _group_planes(
    group, s_pad: int, nc_pad: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Class-compressed 8-bit plane pair [nc_pad, s_pad] of one group's
    minimized table, re-encoded v' = next_state * 2 + reported and
    transposed class-major, plus the [256] float class map. Class C (the
    group's identity padding class) self-loops carrying each state's own
    report flag and byte 0 maps to it; padding classes past C and padding
    states past S carry v' = 0 — unreachable (the class map only emits
    [0, C] and the carried state never leaves [0, S))."""
    trans = np.asarray(group._trans_np, dtype=np.int64)  # [S, C]
    reports = np.asarray(group._reports_np, dtype=np.int64)  # [S] 0/1
    S, C = trans.shape
    vp = np.zeros((nc_pad, s_pad), np.int32)
    vp[:C, :S] = (trans * 2 + reports[trans]).T
    vp[C, :S] = np.arange(S, dtype=np.int64) * 2 + reports
    cmap = np.asarray(group._byte_class_np, dtype=np.float32).copy()
    cmap[0] = C
    return (vp & 0xFF).astype(np.float32), (vp >> 8).astype(np.float32), cmap


def _vmem_estimate(s_pad: int, nc_pad: int, tile: int, T: int) -> int:
    """Bytes of VMEM one grid step needs: byte tile + both class planes +
    the class map + the byte and class one-hots + ~5 [tile, s_pad]
    f32/i32 temporaries (two plane results, reassembled next, select
    mask, product) + carries/out."""
    return 4 * (
        T * tile
        + 2 * nc_pad * s_pad
        + 256
        + 256 * tile
        + nc_pad * tile
        + tile
        + 5 * tile * s_pad
        + 2 * tile
    )


def _group_cost(group) -> int:
    return _vmem_estimate(
        _pad_states(group.n_states),
        _pad_classes(group.n_classes),
        DFA_TILE_B,
        _NOMINAL_T,
    )


def _plane_bytes(groups) -> int:
    return sum(
        2 * 4 * _pad_classes(g.n_classes) * _pad_states(g.n_states)
        for g in groups
    )


def _chunks(seq: list, k: int) -> list[list]:
    base, rem = divmod(len(seq), k)
    out, i = [], 0
    for j in range(k):
        size = base + (1 if j < rem else 0)
        if size:
            out.append(seq[i : i + size])
            i += size
    return out


def _compile_parts(group_entries: list, k: int, max_states: int):
    """Compile a k-way contiguous split of one group's (key, regex, ci)
    entries into minimized MultiDfaBank parts; None when any chunk blows
    the state budget (caller tries a finer split)."""
    from log_parser_tpu.ops.match import MultiDfaBank
    from log_parser_tpu.patterns.regex.multidfa import (
        MultiDfaLimitError,
        compile_union_regexes,
    )

    parts = []
    for chunk in _chunks(group_entries, k):
        try:
            md = compile_union_regexes(
                [(rx, ci) for _, rx, ci in chunk],
                max_states=max_states,
                minimize=True,
            )
        except MultiDfaLimitError:
            return None
        parts.append(MultiDfaBank(md, [key for key, _, _ in chunk]))
    return parts


def _split_group(group_entries: list, budget: int, max_states: int):
    """Cheapest admissible re-partition of one union group: the first
    k-way contiguous balanced split whose parts each fit the budget at
    the nominal tile, priced against the (k+1)-way alternative by total
    plane bytes. None when even singletons are inadmissible."""
    n = len(group_entries)
    chosen = None
    for k in range(2, n + 1):
        parts = _compile_parts(group_entries, k, max_states)
        if parts is None:
            continue
        if all(_group_cost(p) <= budget for p in parts):
            chosen = (k, parts)
            break
    if chosen is None:
        return None
    k, parts = chosen
    if k < n:
        alt = _compile_parts(group_entries, k + 1, max_states)
        if (
            alt is not None
            and all(_group_cost(p) <= budget for p in alt)
            and _plane_bytes(alt) < _plane_bytes(parts)
        ):
            k, parts = k + 1, alt
    return parts, _chunks(group_entries, k)


def build_dfa_plan(
    groups,
    budget: int | None = None,
    entries: list | None = None,
    max_states: int = 8192,
) -> tuple[DfaKernelPlan | None, str]:
    """Pack a bank's union groups into kernel operands, or refuse with a
    REASONS code.

    ``entries``: per-group ``(key, regex, case_insensitive)`` lists in
    bit order (MatcherBanks keeps them beside ``multi_groups``). When the
    padded geometry exceeds ``budget``, the costliest group is re-split
    via ``entries`` (cheapest admissible k-way partition) until the plan
    admits — callers must then adopt ``plan.groups``. Without entries
    the old refuse-outright behaviour stands. Admission here is
    table-geometry only (state/class counts are static); the batch tile
    is re-admitted per call by dfa_tile. Returns reason ``byte_classed``
    (admitted as packed) or ``split`` (admitted after re-partitioning)."""
    if budget is None:
        budget = DFA_VMEM_BUDGET
    if not groups:
        return None, "no_union_groups"
    groups = list(groups)
    entries = [list(e) for e in entries] if entries is not None else None
    split_desc: list[str] = []
    while True:
        s_pad = _pad_states(max(g.n_states for g in groups))
        nc_pad = _pad_classes(max(g.n_classes for g in groups))
        if _vmem_estimate(s_pad, nc_pad, DFA_TILE_B, _NOMINAL_T) <= budget:
            break
        gi = max(range(len(groups)), key=lambda i: _group_cost(groups[i]))
        if entries is None or len(entries[gi]) < 2:
            return None, "table_too_large"
        split = _split_group(entries[gi], budget, max_states)
        if split is None:
            return None, "table_too_large"
        parts, part_entries = split
        split_desc.append(f"{len(entries[gi])}p->{len(parts)}")
        groups[gi : gi + 1] = parts
        entries[gi : gi + 1] = part_entries
    G = len(groups)
    cmap = np.zeros((G, 256), np.float32)
    p0 = np.zeros((nc_pad, G * s_pad), np.float32)
    p1 = np.zeros((nc_pad, G * s_pad), np.float32)
    starts = np.zeros((G, 2), np.int32)
    for gi, g in enumerate(groups):
        a, b, cm = _group_planes(g, s_pad, nc_pad)
        p0[:, gi * s_pad : (gi + 1) * s_pad] = a
        p1[:, gi * s_pad : (gi + 1) * s_pad] = b
        cmap[gi] = cm
        starts[gi] = (g.start, int(g.start_reports))
    geometry = {
        "nGroups": G,
        "sPad": s_pad,
        "ncPad": nc_pad,
        "planeBytes": 2 * 4 * nc_pad * G * s_pad,
        "vmemPerStep": _vmem_estimate(s_pad, nc_pad, DFA_TILE_B, _NOMINAL_T),
        "statesUnmin": sum(g.n_states_unmin for g in groups),
        "states": sum(g.n_states for g in groups),
        "groupPatterns": [g.n_cols for g in groups],
        "groupStatesUnmin": [g.n_states_unmin for g in groups],
        "groupStates": [g.n_states for g in groups],
        "groupByteClasses": [g.n_classes for g in groups],
        "split": ",".join(split_desc) if split_desc else None,
    }
    plan = DfaKernelPlan(
        cmap, p0, p1, starts, s_pad, nc_pad, G, groups, geometry
    )
    return plan, ("split" if split_desc else "byte_classed")


def dfa_tile(
    plan: DfaKernelPlan,
    B: int,
    T: int | None = None,
    budget: int | None = None,
) -> int | None:
    """Largest admissible batch tile for a B-row batch, shrinking until
    the VMEM estimate fits; None when no tile works (caller falls back
    to the XLA scan)."""
    if budget is None:
        budget = DFA_VMEM_BUDGET
    T = _NOMINAL_T if T is None else T
    limit = DFA_TILE_B
    while True:
        tile = pick_tile(B, limit)
        if tile is None:
            return None
        if _vmem_estimate(plan.s_pad, plan.nc_pad, tile, T) <= budget:
            return tile
        limit = tile - 1


def _kernel(
    bytes_ref, cmap_ref, p0_ref, p1_ref, start_ref, out_ref, *, T, stride
):
    tile = out_ref.shape[0]
    nc_pad, s_pad = p0_ref.shape
    row256 = jax.lax.broadcasted_iota(jnp.int32, (256, tile), 0)
    rowC = jax.lax.broadcasted_iota(jnp.int32, (nc_pad, tile), 0)
    lane_s = jax.lax.broadcasted_iota(jnp.int32, (tile, s_pad), 1)
    one = jnp.int32(1)

    def step(t, s, rep):
        b_row = bytes_ref[pl.ds(t, 1), :]  # [1, TILE]
        ohT = (row256 == b_row).astype(jnp.float32)  # [256, TILE]
        # per-lane byte class: the [1, 256] map row contracted against
        # the byte one-hot — class ids <= 256 are exact at bf16's 8-bit
        # mantissa, same argument as the planes
        cls = jnp.dot(
            cmap_ref[:], ohT, preferred_element_type=jnp.float32
        ).astype(jnp.int32)  # [1, TILE]
        ohC = (rowC == cls).astype(jnp.float32)  # [nc_pad, TILE]
        n0 = _dotT(ohC, p0_ref[:])  # [TILE, s_pad]
        n1 = _dotT(ohC, p1_ref[:])
        nxt = n0.astype(jnp.int32) | (n1.astype(jnp.int32) << 8)
        sel = (lane_s == s).astype(jnp.int32)  # state one-hot per lane
        v = jnp.sum(nxt * sel, axis=1, keepdims=True)  # [TILE, 1]
        return _SRL(v, one), rep | (v & one)

    if stride == 2:
        n_steps = T // 2

        def body(i, carry):
            s, rep = step(2 * i, *carry)
            return step(2 * i + 1, s, rep)

    else:
        n_steps = T

        def body(i, carry):
            return step(i, *carry)

    init = (
        jnp.full((tile, 1), start_ref[0, 0], jnp.int32),
        jnp.full((tile, 1), start_ref[0, 1], jnp.int32),
    )
    s, rep = jax.lax.fori_loop(0, n_steps, body, init)
    if stride == 2 and T % 2:
        s, rep = step(T - 1, s, rep)
    out_ref[:] = rep


def multidfa_reported_pallas(
    plan: DfaKernelPlan,
    lines_tb: jax.Array,
    stride: int = 2,
    interpret: bool | None = None,
    tile_b: int | None = None,
    budget: int | None = None,
) -> jax.Array:
    """Run every union group's reported-flag scan in one Pallas call.

    ``lines_tb``: uint8 [T, B]; returns int32 [B, G] 0/1 reported flags
    in group order, bit-equal to finishing the scan tier's pair_stepper
    carry. ``stride`` 2 mirrors the fused scan's byte-pair steps; 1 is
    the single-stride variant (identical results, A/B'd by
    tools/probe_kernels.py)."""
    assert stride in (1, 2)
    T, B = lines_tb.shape
    if interpret is None:
        # the interpreter runs only on the CPU test backend; any other
        # backend lowers through Mosaic, so a refusal raises
        interpret = jax.default_backend() == "cpu"
    tile = dfa_tile(plan, B, T, budget=budget) if tile_b is None else tile_b
    assert tile is not None, f"no usable tile for batch rows {B}"
    G, s_pad, nc_pad = plan.n_groups, plan.s_pad, plan.nc_pad
    kernel = functools.partial(_kernel, T=T, stride=stride)
    return pl.pallas_call(
        kernel,
        grid=(G, B // tile),
        in_specs=[
            pl.BlockSpec(
                (T, tile), lambda g, i: (0, i), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (None, 1, 256), lambda g, i: (g, 0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (nc_pad, s_pad), lambda g, i: (0, g), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (nc_pad, s_pad), lambda g, i: (0, g), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (None, 1, 2), lambda g, i: (g, 0, 0), memory_space=pltpu.SMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (None, tile, 1), lambda g, i: (g, i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((G, B, 1), jnp.int32),
        interpret=interpret,
    )(
        lines_tb.astype(jnp.int32),
        jnp.asarray(plan.cmap).reshape(G, 1, 256),
        jnp.asarray(plan.p0),
        jnp.asarray(plan.p1),
        jnp.asarray(plan.starts).reshape(G, 1, 2),
    )[:, :, 0].T
