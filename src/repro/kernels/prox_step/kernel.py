"""Fused prox-family worker update — gradient + step in one kernel.

Every stochastic round of ProxGD / AccProxGD / ADMM has each worker
compute a minibatch gradient over its local task columns and then take
the proximal / augmented-Lagrangian step

    g_j  = (1/n) X_j^T l'(X_j w_j, y_j) + l2 w_j
    w_j <- w_j - eta (g_j / m + q_j + rho (w_j - z_j))

as two separate dispatches, round-tripping the (L, p) gradient through
HBM between them.  Fused here: the ``mtl_grad`` streaming accumulator
(X row blocks through VMEM, residual-weighted row sums into a (tb, p)
scratch) finishes by applying the step in-register — the gradient
never leaves VMEM.  ProxGD/AccProxGD are the q = 0, rho = 0 special
case (the solver passes ``eta * m`` so the 1/m cancels, matching the
unfused update bit-for-bit in exact arithmetic).

eta / rho / 1/m / l2 arrive as a (1, 4) f32 SMEM operand — they are
traced scalars inside the solver round body, so they cannot be baked
into the kernel as Python statics.

Grid and block shapes are ``mtl_grad``'s: (task blocks of tb, row
blocks of br), with the same static loss-derivative switch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..mtl_grad.kernel import accumulate_gradients, block_plan


def _kernel(x_ref, y_ref, w_ref, z_ref, q_ref, par_ref, out_ref, acc_scr,
            *, loss: str, tb: int, br: int, n_blocks: int, n_rows: int):
    bi = pl.program_id(1)

    @pl.when(bi == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    accumulate_gradients(x_ref, y_ref, w_ref, acc_scr, loss=loss, tb=tb,
                         br=br, n_rows=n_rows)

    @pl.when(bi == n_blocks - 1)
    def _fin():
        eta, rho, inv_m, l2 = (par_ref[0, 0], par_ref[0, 1],
                               par_ref[0, 2], par_ref[0, 3])
        w = w_ref[...].astype(jnp.float32)
        g = acc_scr[...] / n_rows + l2 * w
        z = z_ref[...].astype(jnp.float32)
        q = q_ref[...].astype(jnp.float32)
        step = g * inv_m + q + rho * (w - z)
        out_ref[...] = (w - eta * step).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("loss", "br", "interpret"))
def prox_step_lnp(X, y, W, Z, Q, params, *, loss: str = "squared",
                  br=None, interpret: bool = False):
    """X: (L, n, p); y: (L, n); W/Z/Q: (L, p); params: (1, 4) f32
    [eta, rho, 1/m, l2] -> updated W (L, p) f32."""
    L, n, p = X.shape
    tb, br = block_plan(L, n, p, X.dtype.itemsize, br)
    nb = -(-n // br)
    kern = functools.partial(_kernel, loss=loss, tb=tb, br=br, n_blocks=nb,
                             n_rows=n)
    task_block = pl.BlockSpec((tb, p), lambda t, b: (t, 0))
    return pl.pallas_call(
        kern,
        grid=(-(-L // tb), nb),
        in_specs=[
            pl.BlockSpec((tb, br, p), lambda t, b: (t, b, 0)),
            pl.BlockSpec((tb, br, 1), lambda t, b: (t, b, 0)),
            task_block, task_block, task_block,
            pl.BlockSpec((1, 4), lambda t, b: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=task_block,
        out_shape=jax.ShapeDtypeStruct((L, p), jnp.float32),
        scratch_shapes=[pltpu.VMEM((tb, p), jnp.float32)],
        interpret=interpret,
        name="prox_step",
    )(X, y[..., None], W, Z, Q, params)
