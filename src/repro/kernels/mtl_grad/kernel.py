"""Per-task gradient Pallas-TPU kernel — the paper's worker hot spot.

Every round of ProxGD / AccProxGD / DFW / DGSP has each worker compute

    g_j = (1/n) X_j^T  l'(X_j w_j, y_j)          (X_j: (n, p))

before sending it to the master. Fused here: one pass over X streams
row blocks through VMEM, computes predictions, applies the loss
derivative and accumulates X_blk^T r in a VMEM scratch — X is read
from HBM exactly once and the (n,) prediction/residual vectors never
round-trip to HBM.

Grid: (task blocks, row blocks); the row-block axis is sequential, the
accumulator carried in scratch.  A grid step holds ``tb`` tasks so the
(tb, p) blocks of W and G obey the chip's (8, 128) tiling rule; y rides
in as an (m, n, 1) column so its (br, 1) block does too.  Each task is
a batched mat-vec, so the products run on the vector unit (multiply,
then a lane or sublane sum) — an MXU pass would be one column wide.
Loss derivative is a static switch:
  squared:   l' = (pred - y)
  logistic:  l' = -y * sigmoid(-y * pred),  y in {-1, +1}
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_TASKS_PER_BLOCK = 8          # the f32 sublane tile of the (tb, p) blocks
_X_BLOCK_BYTES = 2 << 20      # VMEM bytes of one (tb, br, p) X block


def block_plan(m: int, n: int, p: int, itemsize: int, br=None):
    """(tb, br): tasks and rows per grid step for an (m, n, p) design.

    ``tb`` is 8, or all of m when m <= 8 (a block dim equal to the
    array's is always legal).  ``br`` keeps one X block near
    ``_X_BLOCK_BYTES`` (lanes padded to 128), is a multiple of the
    dtype's sublane tile, and is balanced so the last row block is as
    full as the others; ``br >= n`` collapses to one block of all n.
    """
    tb = m if m <= _TASKS_PER_BLOCK else _TASKS_PER_BLOCK
    sub = 8 * max(1, 4 // itemsize)
    if br is None:
        lanes = -(-p // 128) * 128
        br = _X_BLOCK_BYTES // (tb * lanes * itemsize) // sub * sub
    br = max(sub, int(br))
    if br >= n:
        return tb, n
    rows = -(-n // -(-n // br))          # n over as many blocks as br needs
    return tb, -(-rows // sub) * sub


def loss_derivative(loss: str, pred, y):
    if loss == "squared":
        return pred - y
    if loss == "logistic":
        return -y * jax.nn.sigmoid(-y * pred)
    raise ValueError(loss)


def accumulate_gradients(x_ref, y_ref, w_ref, acc_scr, *, loss: str,
                         tb: int, br: int, n_rows: int) -> None:
    """acc[t] += X_blk[t]^T l'(X_blk[t] w[t], y_blk[t]) for the tb tasks
    of this grid step (rows past ``n_rows`` of a ragged last block are
    zeroed: their contents are undefined)."""
    ragged = n_rows % br != 0
    if ragged:
        row = (pl.program_id(1) * br
               + jax.lax.broadcasted_iota(jnp.int32, (br, 1), 0))
        valid = row < n_rows
    for t in range(tb):
        x = x_ref[t].astype(jnp.float32)                     # (br, p)
        if ragged:
            x = jnp.where(valid, x, 0.0)
        w = w_ref[pl.ds(t, 1), :].astype(jnp.float32)       # (1, p)
        pred = jnp.sum(x * w, axis=1, keepdims=True)         # (br, 1)
        r = loss_derivative(loss, pred, y_ref[t].astype(jnp.float32))
        if ragged:
            r = jnp.where(valid, r, 0.0)
        acc_scr[pl.ds(t, 1), :] += jnp.sum(r * x, axis=0, keepdims=True)


def _kernel(x_ref, y_ref, w_ref, g_ref, acc_scr, *, loss: str, tb: int,
            br: int, n_blocks: int, n_rows: int):
    bi = pl.program_id(1)

    @pl.when(bi == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    accumulate_gradients(x_ref, y_ref, w_ref, acc_scr, loss=loss, tb=tb,
                         br=br, n_rows=n_rows)

    @pl.when(bi == n_blocks - 1)
    def _fin():
        g_ref[...] = (acc_scr[...] / n_rows).astype(g_ref.dtype)


@functools.partial(jax.jit, static_argnames=("loss", "br", "interpret"))
def task_gradients_mnp(X, y, W, *, loss: str = "squared", br=None,
                       interpret: bool = False):
    """X: (m, n, p); y: (m, n); W: (m, p) -> G (m, p) f32."""
    m, n, p = X.shape
    tb, br = block_plan(m, n, p, X.dtype.itemsize, br)
    nb = -(-n // br)
    kern = functools.partial(_kernel, loss=loss, tb=tb, br=br, n_blocks=nb,
                             n_rows=n)
    return pl.pallas_call(
        kern,
        grid=(-(-m // tb), nb),
        in_specs=[
            pl.BlockSpec((tb, br, p), lambda t, b: (t, b, 0)),
            pl.BlockSpec((tb, br, 1), lambda t, b: (t, b, 0)),
            pl.BlockSpec((tb, p), lambda t, b: (t, 0)),
        ],
        out_specs=pl.BlockSpec((tb, p), lambda t, b: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((m, p), jnp.float32),
        scratch_shapes=[pltpu.VMEM((tb, p), jnp.float32)],
        interpret=interpret,
        name="mtl_grad",
    )(X, y[..., None], W)
