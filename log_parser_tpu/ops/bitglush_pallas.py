"""Pallas TPU kernel for the bit-parallel extended Shift-And scan.

The lax.scan implementation (ops/bitglush.py) pays one contiguous
``[256, W]`` mask-row take per byte plus ~15 elementwise ``[B, W]`` ops,
all streaming through HBM — measured ~200 ms for the 74-word builtin
program over the 229k-row config-2 batch. This kernel moves the whole
scan into VMEM:

- the mask-row select becomes MXU one-hot matmuls. The one-hot is built
  TRANSPOSED (``[256, TILE]`` — comparing an iota over sublanes against
  the byte row slice directly, no per-step relayout) and contracted over
  dim 0: ``ohT^T @ M[256, W]``. Mask words ride in four 8-bit planes —
  TPU matmuls run at bfloat16 precision (8-bit mantissa), so 16-bit
  plane values measurably drop bits (0x0101 → 0x0100) while ≤255 values
  are exact. Per-row byte word-ness comes from the same one-hot against
  a ``[256, 1]`` table. The one-hot never exists in HBM — precisely why
  the pre-Pallas one-hot variant was deleted (VERDICT r2 #6: a [B, 256]
  f32 one-hot per scan step is ~235 MB of HBM traffic at this batch);
- the scan state (``D``, ``hits``, previous word-ness) stays in VMEM
  across a ``fori_loop`` over the T byte steps (an unrolled variant
  pushed the Mosaic compile past 9 minutes at T=64; the loop form
  compiles in seconds), so per-tile HBM traffic is the byte tile in and
  the hit words out.

Mosaic-friendly dialect: everything is int32 — no uint32, no bool
vectors, no dynamic lane slicing (each hits an unsupported lowering) —
conditions are 0/1 int32 carried to 0/-1 masks, logical right shifts via
``jax.lax.shift_right_logical``, cross-word shift carry via
``pltpu.roll`` with the lane-0 wraparound masked off.

Semantics are IDENTICAL to BitGlushBank's per-byte *hits* pipeline
(``_hits_pair_stepper``) — same candidate / ε-closure / assertion-gating
/ accept path, verified bit-exactly by tests/test_bitglush.py
(interpreter mode) and the TPU-side parity sweep in
tools/probe_tiers.py. The scan path's default stepper is now the sink
stepper (no hits carry), but on sink-packed banks the hits machinery
(``f_plain``/``f_dollar``/``fin_*``, the ``pos < length`` gates)
remains VALID — sinks only add always-admitting positions that no hit
term reads — and this kernel is its remaining consumer: do not strip
those constants from ``use_sinks`` banks while this path exists.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_B = 512

_SRL = jax.lax.shift_right_logical

# word bytes: [0-9A-Za-z_]
_WORD_TAB = np.zeros((256, 1), dtype=np.float32)
for _b in range(256):
    _WORD_TAB[_b, 0] = float(
        48 <= _b <= 57 or 65 <= _b <= 90 or 97 <= _b <= 122 or _b == 95
    )


def _build_matmul_masks(bank) -> list[np.ndarray]:
    """Four [256, W] float32 matrices: the mask words split into 8-bit
    planes (exact under the MXU's bf16 mantissa; see module docstring)."""
    bmask = np.asarray(bank.bmask, dtype=np.uint32)  # [256, W]
    return [
        ((bmask >> (8 * p)) & 0xFF).astype(np.float32) for p in range(4)
    ]


def _i32(a) -> np.ndarray:
    return np.asarray(a, dtype=np.uint32).astype(np.int64).astype(np.int32)


def pick_tile(B: int, limit: int | None = None) -> int | None:
    """Largest batch tile ≤ ``limit`` (default TILE_B) that Mosaic can
    block; None when no usable tile exists. The tile is the LANE axis of
    the ``[T, tile]`` byte block, so it must be a multiple of 128 or the
    whole batch (a multiple of 8: it is also the sublane axis of the
    ``[tile, ·]`` blocks). The encoder's rows are powers of two up to
    128 and multiples of 128 beyond, so every rung has a tile."""
    limit = min(limit or TILE_B, B)
    if B == limit and B % 8 == 0:
        return B
    tile = limit // 128 * 128
    while tile >= 128:
        if B % tile == 0:
            return tile
        tile -= 128
    return None


def _dotT(ohT: jax.Array, m: jax.Array) -> jax.Array:
    """[256, TILE]^T @ [256, N] -> [TILE, N] on the MXU."""
    return jax.lax.dot_general(
        ohT,
        m,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _kernel(
    bytes_ref,  # [T, TILE] int32 (bytes widened on host)
    lens_ref,  # [TILE, 1] int32
    m0_ref,  # [256, W] float32 mask byte-plane 0 (bits 0-7)
    m1_ref,  # [256, W] float32 mask byte-plane 1
    m2_ref,  # [256, W] float32 mask byte-plane 2
    m3_ref,  # [256, W] float32 mask byte-plane 3
    word_ref,  # [256, 1] float32 word-ness table
    consts_ref,  # [8, W] int32: s_static, k_skip, start, caret_start,
    #              f_plain, f_dollar, f_tb, f_tB
    allow_ref,  # [4, W] int32
    out_ref,  # [TILE, W] int32 hit words
    *,
    T: int,
    W: int,
    skip_run: int,
    has_tb: bool,
    has_dollar: bool,
):
    tile = out_ref.shape[0]
    lens = lens_ref[:]  # [TILE, 1]

    s_static = consts_ref[0, :].reshape(1, W)
    k_skip = consts_ref[1, :].reshape(1, W)
    start = consts_ref[2, :].reshape(1, W)
    caret_start = consts_ref[3, :].reshape(1, W)
    f_plain = consts_ref[4, :].reshape(1, W)
    f_dollar = consts_ref[5, :].reshape(1, W)
    f_tb = consts_ref[6, :].reshape(1, W)
    f_tB = consts_ref[7, :].reshape(1, W)
    a0 = allow_ref[0, :].reshape(1, W)
    a1 = allow_ref[1, :].reshape(1, W)
    a2 = allow_ref[2, :].reshape(1, W)
    a3 = allow_ref[3, :].reshape(1, W)

    row256 = jax.lax.broadcasted_iota(jnp.int32, (256, tile), 0)
    ones31 = jnp.int32(31)
    # -1 everywhere except lane 0: kills pltpu.roll's wraparound so the
    # cross-word shift carry is zero into word 0
    not_lane0 = -jnp.minimum(
        jax.lax.broadcasted_iota(jnp.int32, (tile, W), 1), 1
    )

    def full_mask(cond_i32):
        """0/1 int32 -> 0 / -1 (all-ones) mask."""
        return -cond_i32

    def ge(a, b):  # a >= b as 0/1 int32 (small-magnitude operands)
        return 1 - _SRL(a - b, ones31)

    def shift1(d):
        sh = d << 1
        if W > 1:
            carry = pltpu.roll(_SRL(d, ones31), shift=1, axis=1) & not_lane0
            sh = sh | carry
        return sh

    def body(t, carry):
        d, hits, pw = carry
        b_row = bytes_ref[pl.ds(t, 1), :]  # [1, TILE]
        ohT = (row256 == b_row).astype(jnp.float32)  # [256, TILE]
        cw = _dotT(ohT, word_ref[:]).astype(jnp.int32)  # [TILE, 1] 0/1
        ok = ge(lens, t + 1)  # t < len
        okm = full_mask(ok)

        if has_tb:
            bc = pw ^ cw  # 0/1 boundary
            hits = hits | (d & f_tb & okm & full_mask(bc))
            hits = hits | (d & f_tB & okm & full_mask(1 - bc))

        brow = jnp.zeros((tile, W), jnp.int32)
        for p, mp in enumerate((m0_ref, m1_ref, m2_ref, m3_ref)):
            plane = _dotT(ohT, mp[:])
            brow = brow | (plane.astype(jnp.int32) << (8 * p))

        c = shift1(d) | start
        # ^-anchored starts inject only at the line's first byte; the
        # caret guard bit (bitglush.py _alt_allocs) absorbs shift/skip
        # leaks, so no ``& not_caret`` is needed here either
        c = c | (caret_start & full_mask(ge(jnp.int32(0), t)))
        for _ in range(skip_run):
            c = c | shift1(c & k_skip)

        pwm = full_mask(pw)
        cwm = full_mask(cw)
        allow = (pwm & ((cwm & a3) | (~cwm & a2))) | (
            ~pwm & ((cwm & a1) | (~cwm & a0))
        )
        d_new = (c & allow & brow) | (d & brow & s_static)
        d = (okm & d_new) | (~okm & d)

        hits = hits | (okm & d & f_plain)
        eolm = full_mask(ok * ge(t + 1, lens))  # t == len-1
        if has_dollar:
            hits = hits | (eolm & d & f_dollar)
        if has_tb:
            hits = hits | (eolm & cwm & d & f_tb)
            hits = hits | (eolm & ~cwm & d & f_tB)
        pw = (ok * cw) | ((1 - ok) * pw)
        return d, hits, pw

    carry0 = (
        jnp.zeros((tile, W), jnp.int32),
        jnp.zeros((tile, W), jnp.int32),
        jnp.zeros((tile, 1), jnp.int32),
    )
    _, hits, _ = jax.lax.fori_loop(0, T, body, carry0)
    out_ref[:] = hits


def bitglush_hits_pallas(
    bank,
    lines_tb: jax.Array,
    lengths: jax.Array,
    interpret: bool | None = None,
    tile_b: int | None = None,
) -> jax.Array:
    """Run the bank's whole scan in one Pallas call.

    ``lines_tb``: uint8 [T, B] with B a multiple of TILE_B (the encoder's
    row rungs are); returns uint32 [B, W] accumulated hit words, bit-equal
    to running the pair_stepper scan and keeping its hits carry."""
    T, B = lines_tb.shape
    W = bank.n_words
    if interpret is None:
        # the interpreter runs only on the CPU test backend; any other
        # backend lowers through Mosaic, so a refusal raises
        interpret = jax.default_backend() == "cpu"
    consts = jnp.asarray(
        np.stack(
            [
                _i32(bank.s_static),
                _i32(bank.k_skip),
                _i32(bank.start),
                _i32(bank.caret_start),
                _i32(bank.f_plain),
                _i32(bank.f_dollar),
                _i32(bank.f_tb),
                _i32(bank.f_tB),
            ]
        )
    )
    planes = [jnp.asarray(p) for p in _build_matmul_masks(bank)]
    wordtab = jnp.asarray(_WORD_TAB)
    allow = jnp.asarray(_i32(bank.allow4))
    lens2d = lengths.astype(jnp.int32).reshape(B, 1)

    tile = pick_tile(B, tile_b)
    assert tile is not None, f"no usable tile for batch rows {B}"
    kernel = functools.partial(
        _kernel,
        T=T,
        W=W,
        skip_run=bank.max_skip_run,
        has_tb=bank.has_tb,
        has_dollar=bank.has_dollar,
    )
    lines_i32 = lines_tb.astype(jnp.int32)
    out = pl.pallas_call(
        kernel,
        grid=(B // tile,),
        in_specs=[
            pl.BlockSpec((T, tile), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((256, W), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((256, W), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((256, W), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((256, W), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((256, 1), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((8, W), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((4, W), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (tile, W), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((B, W), jnp.int32),
        interpret=interpret,
    )(lines_i32, lens2d, *planes, wordtab, consts, allow)
    return jax.lax.bitcast_convert_type(out, jnp.uint32)
