"""Fused serving-score Pallas-TPU kernel — the factored model's hot path.

Serving a shared-representation model (paper §2: W = U A) means every
request batch computes

    score_b = <x_b @ U, c_{id_b}>        (U: (p, r), C: (m, r))

``repro.serve.mtl._score_batch`` runs this as three XLA ops — gemm,
gather, reduce — so the (B, r) intermediate round-trips HBM. Fused
here: one pass streams X row blocks through VMEM, computes the block's
(bb, r) projection on the MXU, gathers the per-task codes for the
block's ids from a VMEM-resident code table and reduces to the (bb,)
predictions — X and C are each read from HBM exactly once.

The gather needs the task ids at scalar positions, so they ride in as
a scalar-prefetch operand (SMEM) via ``PrefetchScalarGridSpec``; a
``fori_loop`` of dynamic slices copies the selected codes into a
(bb, r) VMEM scratch — one row for an f32 table, the row's aligned
sublane tile for a packed int8 / fp8 table. The code table is kept whole in VMEM:
at r=4 even m=10**6 int8 codes are 4 MB, which is exactly the
quantization bandwidth argument (DESIGN.md §14).

Quantized tables enter as the raw int8 / float8 array plus a per-code
scale column S (m, 1); the kernel dequantizes the gathered row with
one multiply. The f32 path passes S = 1.0 exactly, so the multiply is
bitwise neutral and a single kernel serves every ``code_dtype``.

Out-of-range ids clamp (``jnp.take`` semantics); validity is flagged
by the wrapper, mirroring ``_score_batch``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _code_row(c_ref, idx, tile: int):
    """Row ``idx`` of the code table as (1, r) f32.  A packed dtype
    (int8, fp8) is sliced a whole sublane tile at a time — the chip
    addresses packed rows only at tile-aligned offsets — and the row is
    selected from the tile with a mask."""
    if tile == 1:
        return c_ref[pl.ds(idx, 1), :].astype(jnp.float32)
    base = pl.multiple_of(idx // tile * tile, tile)
    rows = c_ref[pl.ds(base, tile), :].astype(jnp.float32)     # (tile, r)
    hit = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0) == idx - base
    return jnp.sum(jnp.where(hit, rows, 0.0), axis=0, keepdims=True)


def _kernel(ids_ref, x_ref, u_ref, c_ref, s_ref, out_ref, codes_scr, *,
            bb: int, m: int, tile: int):
    bi = pl.program_id(0)
    z = x_ref[...].astype(jnp.float32) @ u_ref[...].astype(jnp.float32)

    def gather(i, _):
        idx = ids_ref[bi * bb + i]
        idx = jnp.clip(idx, 0, m - 1)                  # jnp.take semantics
        row = _code_row(c_ref, idx, tile)
        codes_scr[pl.ds(i, 1), :] = row * s_ref[pl.ds(idx, 1), :]
        return 0

    jax.lax.fori_loop(0, bb, gather, 0)
    out_ref[...] = jnp.sum(z * codes_scr[...], axis=1)


@functools.partial(jax.jit, static_argnames=("bb", "interpret"))
def mtl_score_fused(U, C, S, ids, X, *, bb: int = 128,
                    interpret: bool = False):
    """U: (p, r); C: (m, r) any dtype; S: (m, 1) f32 per-code scales;
    ids: (B,) int; X: (B, p) -> scores (B,) f32.

    B is padded to a multiple of ``bb`` with id 0 / zero rows (their
    projection is exactly 0.0) and the pad is sliced off.
    """
    B, p = X.shape
    m, r = C.shape
    nb = -(-B // bb)
    pad = nb * bb - B
    if pad:
        ids = jnp.concatenate([ids, jnp.zeros((pad,), ids.dtype)])
        X = jnp.concatenate([X, jnp.zeros((pad, p), X.dtype)])
    # packed tables gather whole sublane tiles: pad the rows to a whole
    # number of tiles (zero codes no clamped id reaches)
    tile = 1 if C.dtype.itemsize == 4 else 32 // C.dtype.itemsize
    m_pad = -(-m // tile) * tile
    if m_pad != m:
        C = jnp.concatenate([C, jnp.zeros((m_pad - m, r), C.dtype)])
        S = jnp.concatenate([S, jnp.ones((m_pad - m, 1), S.dtype)])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((bb, p), lambda i, ids: (i, 0)),
            pl.BlockSpec((p, r), lambda i, ids: (0, 0)),
            pl.BlockSpec((m_pad, r), lambda i, ids: (0, 0)),
            pl.BlockSpec((m_pad, 1), lambda i, ids: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bb,), lambda i, ids: (i,)),
        scratch_shapes=[pltpu.VMEM((bb, r), jnp.float32)],
    )
    kern = functools.partial(_kernel, bb=bb, m=m, tile=tile)
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nb * bb,), jnp.float32),
        interpret=interpret,
        name="mtl_score",
    )(ids, X, U, C, S)
    return out[:B]
