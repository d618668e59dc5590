"""Sharding & donation lints over a captured solver program.

Three bug classes this repo has actually hit (or dodged narrowly):

* SHRD001 — a large leaf (the raw data, a Gram cache) entering a
  ``shard_map`` body fully REPLICATED.  The program still computes the
  right answer, but every chip holds — and every round reads — the
  whole array, silently erasing the memory/bandwidth win the mesh
  exists for (the PR 3 2-D Gram regression, the PR 4 no-mapped-leaf
  vmap trap).  Heuristic: a replicated global invar at least as large
  as the LARGEST sharded invar is almost certainly a mistake — in a
  healthy program the biggest operands are exactly the ones that get
  sharded, while the intentionally replicated master state (the (p, m)
  iterate, basis carries) is orders of magnitude smaller.
* SHRD002 — a buffer donated to a jitted call and then read again by a
  later equation of the same enclosing program (undefined contents),
  or donated with no output of matching shape/dtype (XLA cannot reuse
  it, the donation is dead weight).  The round program donates its
  state carry; this proves the shield-copy discipline
  (``_shield_donated``) actually protects every later read.
* SHRD003 — round-body state whose output avals drift from its input
  avals (dtype / weak-type promotion, shape change).  The round
  program is a ``lax.scan`` under every driver, and jax rejects a
  drifting carry there outright; the lint names the leaf.  The D=1
  weak-type bug fixed in ``ProtocolRuntime._norm_collective`` is
  exactly this class.
"""
from __future__ import annotations

from typing import List

from jax._src import core as jcore

from .jaxpr_walk import WalkResult, _inner_jaxpr, _sub_jaxprs
from .report import Finding


# ---------------------------------------------------------------------------
# SHRD001: replicated large leaves inside shard_map bodies
# ---------------------------------------------------------------------------
def replication_lint(walked: WalkResult, where: str) -> List[Finding]:
    findings: List[Finding] = []
    for site in walked.shard_maps:
        sharded = [int(aval.size) for aval, names in site.invars if names]
        if not sharded:
            continue
        threshold = max(sharded)
        for aval, names in site.invars:
            if names or int(aval.size) < threshold:
                continue
            findings.append(Finding(
                "SHRD001",
                f"replicated invar {aval.str_short()} entering shard_map "
                f"at {site.path} is as large as the largest sharded "
                f"operand ({threshold} floats) — every chip holds the "
                f"full array; shard it or prune it from the round data",
                where))
    return findings


# ---------------------------------------------------------------------------
# SHRD002: donated buffers read after donation / donations XLA can't use
# ---------------------------------------------------------------------------
def _donation_walk(jaxpr, path: str, findings: List[Finding], where: str
                   ) -> None:
    eqns = jaxpr.eqns
    for i, eqn in enumerate(eqns):
        if eqn.primitive.name == "jit":
            donated = eqn.params.get("donated_invars", ())
            donated_vars = [v for v, d in zip(eqn.invars, donated)
                            if d and isinstance(v, jcore.Var)]
            if donated_vars:
                out_avals = [(tuple(v.aval.shape), v.aval.dtype)
                             for v in eqn.outvars]
                later_uses = {v for e in eqns[i + 1:] for v in e.invars
                              if isinstance(v, jcore.Var)}
                later_uses |= {v for v in jaxpr.outvars
                               if isinstance(v, jcore.Var)}
                for v in donated_vars:
                    if v in later_uses:
                        findings.append(Finding(
                            "SHRD002",
                            f"buffer {v} ({v.aval.str_short()}) donated to "
                            f"jit at {path}/jit is read again afterwards "
                            f"— its contents are undefined after the call "
                            f"(copy it first: _shield_donated)", where))
                    elif (tuple(v.aval.shape), v.aval.dtype) not in out_avals:
                        findings.append(Finding(
                            "SHRD002",
                            f"buffer {v} ({v.aval.str_short()}) donated to "
                            f"jit at {path}/jit matches no output aval — "
                            f"XLA cannot reuse it; the donation is dead",
                            where))
        for sub in _sub_jaxprs(eqn):
            _donation_walk(_inner_jaxpr(sub), f"{path}/{eqn.primitive.name}",
                           findings, where)


def donation_lint(closed, where: str) -> List[Finding]:
    findings: List[Finding] = []
    _donation_walk(_inner_jaxpr(closed), "", findings, where)
    return findings


# ---------------------------------------------------------------------------
# SHRD003: round-body state aval drift
# ---------------------------------------------------------------------------
def _leaf_sig(leaf):
    return (tuple(leaf.shape), str(leaf.dtype),
            bool(getattr(leaf, "weak_type", False)))


def drift_lint(in_shapes, out_shapes, where: str) -> List[Finding]:
    import jax

    findings: List[Finding] = []
    in_leaves = jax.tree_util.tree_flatten_with_path(in_shapes)[0]
    out_leaves = jax.tree_util.tree_flatten_with_path(out_shapes)[0]
    if len(in_leaves) != len(out_leaves):
        findings.append(Finding(
            "SHRD003",
            f"round body returns {len(out_leaves)} state leaves for "
            f"{len(in_leaves)} inputs — state structure changes across "
            f"rounds", where))
        return findings
    for (path_i, leaf_i), (_, leaf_o) in zip(in_leaves, out_leaves):
        sig_i, sig_o = _leaf_sig(leaf_i), _leaf_sig(leaf_o)
        if sig_i != sig_o:
            name = jax.tree_util.keystr(path_i)
            findings.append(Finding(
                "SHRD003",
                f"state leaf {name} drifts across one round: "
                f"in shape/dtype/weak_type {sig_i} -> out {sig_o} — the "
                f"round program's scan carry rejects it "
                f"(normalize the aval, cf. _norm_collective)", where))
    return findings


def lint_program(trace, walked: WalkResult) -> List[Finding]:
    """All program-level lints for one captured solver trace."""
    where = f"{trace.method}/{trace.layout}/{trace.driver}"
    findings = replication_lint(walked, where)
    findings += donation_lint(trace.jaxpr, where)
    findings += drift_lint(trace.in_shapes, trace.out_shapes, where)
    return findings
