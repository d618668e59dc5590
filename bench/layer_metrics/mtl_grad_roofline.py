"""Share of its roofline that the worker's gradient kernel reaches (%).

The kernel (``repro.kernels.mtl_grad``) computes, for the m_c tasks of one
chip, g_j = (1/n) X_j^T l'(X_j w_j, y_j).  Per call it needs 4 m_c n p
operations and reads X, y and W and writes G once, in float32.  Its least
time is max(ops / peak FLOP/s, bytes / peak HBM bytes/s); the share is that
bound times the calls, over the kernel's device time in the trace.
"""
import re

# The kernel's events in a TPU trace (see PERF.md, "Trace names").
KERNEL = re.compile(r"^(mtl_grad|task_gradients)")


def ops_bytes(cfg, chips: int):
    pr = cfg["problem"]
    m, n, p = pr["m"] // chips, pr["n"], pr["p"]
    return 4.0 * m * n * p, 4.0 * (m * n * p + m * n + 2 * m * p)


def read(rec):
    from bench.trace_reduce import op_seconds
    if rec["trace"] is None:
        return None
    secs, calls = op_seconds(rec["trace"], KERNEL.search)
    if not calls or secs <= 0:
        return None
    ops, nbytes = ops_bytes(rec["config"], rec["chips"])
    pk = rec["peaks"]
    bound = max(ops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * calls * bound / secs
