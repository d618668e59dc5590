"""Plain reference of a served score.

score(x, task j) = x . U c_j with c_j = s * V[j], in NumPy float64 from the
seed's factors, two ways: from the float32 operands as they are, and with
x and U first rounded to bfloat16, as the TPU's default float32 matmul
takes them (its accumulation is float32).  A served score is held to the
nearer of the two, so either arithmetic is sound and nothing coarser is.

``gaps`` normalises each score's error by max(||z||, median ||z||) ||c_j||
with z = x U: the bound of |score| by Cauchy-Schwarz in the rank-r space,
the pool's median norm keeping a request whose z is near nought from
reading large.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np


def codes(s, V) -> np.ndarray:
    return (np.asarray(V, np.float64) * np.asarray(s, np.float64)[None, :])


def projections(U, pool, bf16: bool = False) -> np.ndarray:
    """z = x U for every pool row, (pool_rows, r) float64."""
    X, U = np.asarray(pool, np.float32), np.asarray(U, np.float32)
    if bf16:
        X, U = X.astype(ml_dtypes.bfloat16), U.astype(ml_dtypes.bfloat16)
    return X.astype(np.float64) @ U.astype(np.float64)


def scores(U, s, V, pool, ids, rows, bf16: bool = False) -> np.ndarray:
    """Reference scores of the requests (ids[i], pool[rows[i]])."""
    Z = projections(U, pool, bf16)
    return np.einsum("nr,nr->n", Z[rows], codes(s, V)[ids])


def gaps(pred, U, s, V, pool, ids, rows) -> np.ndarray:
    """Per request: |pred - reference| over max(||z||, median ||z||) ||c_j||,
    the reference being the nearer of the two arithmetics."""
    pred = np.asarray(pred, np.float64)
    err = np.minimum(*(np.abs(pred - scores(U, s, V, pool, ids, rows, bf))
                       for bf in (False, True)))
    zn = np.linalg.norm(projections(U, pool), axis=1)
    cn = np.linalg.norm(codes(s, V), axis=1)
    den = np.maximum(zn, np.median(zn))[rows] * cn[ids]
    return err / np.maximum(den, 1e-30)
