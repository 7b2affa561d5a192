"""Pass 2 — jaxpr pathology analyzer.

Traces every registered kernel with ABSTRACT inputs (no compile, no
device) and computes the graph-shape metrics that predict the XLA
compile-time pathologies this repo has actually hit (the algebraic
simplifier's circular-simplification loop on the fused
`verify_praos_core` graph — VERDICT r5 weak #3/#4, the round-5
eager-only composed smoke):

  mul_chain_depth   longest path of multiply-class primitives
                    (mul / dot_general) through any SINGLE XLA
                    computation. Control-flow bodies (while / scan /
                    cond / pallas_call) are separate computations — the
                    simplifier rewrites one computation at a time, so a
                    `fori_loop` FENCES a chain: only the unrolled
                    segment feeds the rewrite loop. This is the metric
                    the squaring-chain family trips.
  op_fanout         max number of consumer equations of one value —
                    wide fan-out multiplies the simplifier's rewrite
                    candidates per pass.
  remat_width       peak number of simultaneously live values over the
                    jaxpr's own schedule — a proxy for the
                    rematerialization pressure XLA's scheduler faces.
  eqns              recursive primitive count (graph size).
  mul_count         recursive multiply-class primitive count.

`budgets.json` pins a ceiling per registered graph; `check_budgets`
fails any graph over its ceiling, fencing regressions of the
simplifier-circular pattern family in CI (tests/test_analysis.py).
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Callable

# multiply-class primitives: the algebraic simplifier's worst rewrite
# families (reassociation/distribution) chew on these
_MUL_PRIMS = {"mul", "dot_general"}
# call-like primitives whose subjaxprs are separate XLA computations
# (a jitted call is `pjit` up to JAX 0.6 and `jit` since)
_FENCE_PRIMS = {
    "while", "scan", "cond", "jit", "pjit", "closed_call", "core_call",
    "custom_jvp_call", "custom_vjp_call", "remat", "checkpoint",
    "pallas_call", "shard_map", "custom_partitioning",
}


@dataclasses.dataclass
class GraphReport:
    name: str
    eqns: int
    mul_count: int
    mul_chain_depth: int
    op_fanout: int
    remat_width: int
    computations: int

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        vs = v if isinstance(v, (list, tuple)) else [v]
        for x in vs:
            while hasattr(x, "jaxpr"):  # ClosedJaxpr (possibly nested)
                x = x.jaxpr
            if hasattr(x, "eqns"):
                yield x


def _analyze(jaxpr, acc: dict) -> int:
    """One computation: returns its internal max mul-chain depth and
    folds every metric into `acc`. Recurses into subcomputations, which
    contribute to the global max but NOT to this computation's chain
    (they are fences)."""
    depth: dict[int, int] = {}  # id(var) -> mul-chain depth at that value
    uses: dict[int, int] = {}
    last_use: dict[int, int] = {}
    acc["computations"] += 1

    for i, eqn in enumerate(jaxpr.eqns):
        acc["eqns"] += 1
        prim = eqn.primitive.name
        is_mul = prim in _MUL_PRIMS
        if is_mul:
            acc["mul_count"] += 1
        in_depth = 0
        for v in eqn.invars:
            if hasattr(v, "val"):  # Literal
                continue
            uses[id(v)] = uses.get(id(v), 0) + 1
            last_use[id(v)] = i
            in_depth = max(in_depth, depth.get(id(v), 0))
        if prim in _FENCE_PRIMS:
            for sub in _sub_jaxprs(eqn):
                _analyze(sub, acc)
            out_depth = 0  # separate computation: the chain is fenced
        else:
            out_depth = in_depth + (1 if is_mul else 0)
        for v in eqn.outvars:
            depth[id(v)] = out_depth
        acc["chain"] = max(acc["chain"], out_depth)
    for v in jaxpr.outvars:
        if not hasattr(v, "val"):
            uses[id(v)] = uses.get(id(v), 0) + 1
            last_use[id(v)] = len(jaxpr.eqns)
    if uses:
        acc["fanout"] = max(acc["fanout"], max(uses.values()))

    # remat_width: live-interval sweep over the jaxpr's own order
    born: dict[int, int] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.outvars:
            born[id(v)] = i
    events: list[tuple[int, int]] = []
    for vid, b in born.items():
        d = last_use.get(vid, b)
        events.append((b, 1))
        events.append((d + 1, -1))
    live = peak = 0
    for _, delta in sorted(events):
        live += delta
        peak = max(peak, live)
    acc["width"] = max(acc["width"], peak)
    return acc["chain"]


def analyze_jaxpr(closed_jaxpr, name: str = "graph") -> GraphReport:
    """Compute the pathology metrics of one traced jaxpr."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    acc = {"eqns": 0, "mul_count": 0, "chain": 0, "fanout": 0,
           "width": 0, "computations": 0}
    _analyze(jaxpr, acc)
    return GraphReport(
        name=name,
        eqns=acc["eqns"],
        mul_count=acc["mul_count"],
        mul_chain_depth=acc["chain"],
        op_fanout=acc["fanout"],
        remat_width=acc["width"],
        computations=acc["computations"],
    )


# ---------------------------------------------------------------------------
# Kernel registry: every graph the repo dispatches, with the abstract
# input shapes it is traced at. T (the batch tile) only scales array
# widths, never graph structure, so a tiny T keeps tracing fast while
# the metrics match production shapes exactly. Every builder takes an
# optional lane-count override `t`: the octrange interval certification
# (analysis/absint.py) re-traces the lane-SENSITIVE graphs (msm,
# aggregate, verdict_reduce — anything that reduces over the lane axis)
# at production lane counts, while budgets and the lane-INVARIANT
# certificates share the default small-tile trace through trace_graph's
# cache.
# ---------------------------------------------------------------------------

_T = 2
_NB = 2
_DEPTH = 2


def _s(*shape):
    import jax
    from jax import numpy as jnp

    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _pk_core_args(t):
    return (
        _s(32, t), _s(32, t), _s(32, t), _s(_NB, 128, t), _s(t),
        _s(32, t), _s(t), _s(32, t), _s(32, t), _s(32, t),
        _s(_DEPTH, 32, t), _s(_NB, 128, t), _s(t),
        _s(32, t), _s(32, t), _s(16, t), _s(32, t), _s(32, t),
        _s(64, t), _s(32, t), _s(32, t),
    )


def _graph_ed_core(t=None):
    from ..ops.pk import verify as pv

    t = t or _T
    return pv.ed_core, (_s(32, t), _s(32, t), _s(_NB, 128, t), _s(t))


def _graph_kes_core(t=None):
    import functools

    from ..ops.pk import verify as pv

    t = t or _T
    fn = functools.partial(pv.kes_core, depth=_DEPTH)
    return fn, (
        _s(32, t), _s(t), _s(32, t), _s(32, t), _s(_DEPTH, 32, t),
        _s(_NB, 128, t), _s(t),
    )


def _graph_vrf_core(t=None):
    from ..ops.pk import verify as pv

    t = t or _T
    return pv.vrf_core, (
        _s(32, t), _s(32, t), _s(16, t), _s(32, t), _s(32, t)
    )


def _graph_finish_core(t=None):
    from ..ops.pk import verify as pv

    t = t or _T

    def fn(ed_ok, ed_pt, ed_r, kes_ok, kes_pt, kes_r, vrf_ok, vrf_flat,
           c, beta, tlo, thi):
        from ..ops.pk import curve as pc

        def pt(flat):
            return pc.Point(flat[0:20], flat[20:40], flat[40:60], flat[60:80])

        pts = [pt(vrf_flat[80 * i: 80 * (i + 1)]) for i in range(5)]
        return pv.finish_core(
            ed_ok != 0, pt(ed_pt), ed_r, kes_ok != 0, pt(kes_pt), kes_r,
            vrf_ok != 0, pts, c, beta, tlo, thi,
        )

    return fn, (
        _s(t), _s(80, t), _s(32, t), _s(t), _s(80, t), _s(32, t),
        _s(t), _s(400, t), _s(16, t), _s(64, t), _s(32, t), _s(32, t),
    )


def _graph_verify_praos_core(t=None):
    import functools

    from ..ops.pk import verify as pv

    fn = functools.partial(pv.verify_praos_core, kes_depth=_DEPTH)
    return fn, _pk_core_args(t or _T)


def _pk_core_args_bc(t):
    # batch-compatible composed shapes: vrf_c [16, T] is replaced by the
    # announced u, v [32, T] columns
    return (
        _s(32, t), _s(32, t), _s(32, t), _s(_NB, 128, t), _s(t),
        _s(32, t), _s(t), _s(32, t), _s(32, t), _s(32, t),
        _s(_DEPTH, 32, t), _s(_NB, 128, t), _s(t),
        _s(32, t), _s(32, t), _s(32, t), _s(32, t), _s(32, t),
        _s(32, t),
        _s(64, t), _s(32, t), _s(32, t),
    )


def _graph_vrf_bc_core(t=None):
    from ..ops.pk import verify as pv

    t = t or _T
    return pv.vrf_core_bc, (
        _s(32, t), _s(32, t), _s(32, t), _s(32, t), _s(32, t),
        _s(32, t),
    )


def _graph_verify_praos_core_bc(t=None):
    import functools

    from ..ops.pk import verify as pv

    fn = functools.partial(pv.verify_praos_core_bc, kes_depth=_DEPTH)
    return fn, _pk_core_args_bc(t or _T)


def _graph_msm(t=None):
    """One Pippenger MSM (ops/pk/msm.py) at a tiny lane count: the
    fori-fenced scans keep the chain depth flat in N, so tiny shapes pin
    the same structure the bench-scale aggregate dispatches. (The
    interval certification re-traces at production N — the bucket-count
    accumulators are the lane-sensitive part.)"""
    from ..ops.pk import curve as pc
    from ..ops.pk import msm as pk_msm

    n = t or 4

    def fn(scalars, x, y, z, t):
        return pk_msm.msm(scalars, pc.Point(x, y, z, t), 256)

    return fn, (_s(20, n), _s(20, n), _s(20, n), _s(20, n), _s(20, n))


def _graph_aggregate_core(t=None):
    """The full aggregated window program (ops/pk/aggregate.py): cheap
    per-lane work + Fiat–Shamir coefficients + the two-group MSM."""
    import functools

    from ..ops.pk import aggregate as pk_aggregate

    t = t or _T
    fn = functools.partial(pk_aggregate.aggregate_window, kes_depth=_DEPTH)
    return fn, (
        _s(32, t), _s(32, t), _s(32, t), _s(_NB, 128, t), _s(1, t),
        _s(32, t), _s(1, t), _s(32, t), _s(32, t), _s(32, t),
        _s(_DEPTH, 32, t), _s(_NB, 128, t), _s(1, t),
        _s(32, t), _s(32, t), _s(32, t), _s(32, t), _s(32, t),
        _s(32, t),
        _s(64, t), _s(32, t), _s(32, t),
    )


def _graph_aggregate_vrf_core(t=None):
    """The kill-switch (OCT_RLC_ALL=0) aggregated window program
    (ops/pk/aggregate.aggregate_window_vrf): exact per-lane ed/KES
    checks + the vrf-only RLC on the unsigned per-group MSM engine.
    Same 22-column signature as the unified program."""
    import functools

    from ..ops.pk import aggregate as pk_aggregate

    t = t or _T
    fn = functools.partial(pk_aggregate.aggregate_window_vrf,
                           kes_depth=_DEPTH)
    return fn, (
        _s(32, t), _s(32, t), _s(32, t), _s(_NB, 128, t), _s(1, t),
        _s(32, t), _s(1, t), _s(32, t), _s(32, t), _s(32, t),
        _s(_DEPTH, 32, t), _s(_NB, 128, t), _s(1, t),
        _s(32, t), _s(32, t), _s(32, t), _s(32, t), _s(32, t),
        _s(32, t),
        _s(64, t), _s(32, t), _s(32, t),
    )


def _graph_spmd_local(t=None):
    """The per-shard body of parallel/spmd._sharded_verify: the XLA-twin
    `protocol.batch.verify_praos` plus the verdict collectives, traced
    under a single-device mesh (collective structure is device-count
    independent)."""
    import jax
    import numpy as np
    from jax import numpy as jnp
    from jax.sharding import Mesh

    from ..parallel import spmd

    b = t or 8

    def u8(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.uint8)

    def u32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.uint32)

    # flatten_batch order, staged dtypes (protocol/batch.PraosBatch)
    cols = (
        u8(b, 32), u8(b, 32), u8(b, 32), u32(b, _NB, 16, 2), _s(b),
        u8(b, 32), _s(b), u8(b, 32), u8(b, 32), u8(b, 32),
        u8(b, _DEPTH, 32), u32(b, _NB, 16, 2), _s(b),
        u8(b, 32), u8(b, 32), u8(b, 16), u8(b, 32), u8(b, 32),
        u8(b, 64), u8(b, 32), u8(b, 32),
    )
    mesh = Mesh(np.asarray(jax.devices("cpu")[:1]), (spmd.BATCH_AXIS,))

    def fn(*cs):
        return spmd._sharded_verify(mesh, jnp.int32(b), *cs)

    return fn, cols


def _graph_packed_unpack(t=None):
    """The PRODUCTION packed `unpack` stage
    (ops/pk/kernels._mk_packed_unpack): protocol/batch.unpack_packed —
    body-sourced u8 columns -> the 21 staged columns, including the
    on-device SHA-512 padding, VRF alpha hash and table gathers —
    CHAINED into staged_to_limb_first, exactly the graph the per-stage
    jit/AOT executable compiles and dispatches. The body layouts are an
    operand (a table and each lane's row of it), so no offset changes
    the graph."""
    import jax
    from jax import numpy as jnp

    from ..ops.pk import kernels as pk_kernels
    from ..protocol import batch as pbatch

    b = t or 4
    layout = pbatch.PraosPackedLayout(
        body_len=304, kes_depth=_DEPTH, slots_per_kes=100, has_nonce=True,
    )

    def u8(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.uint8)

    args = (
        u8(b, 304), u8(b, 64), _s(b), u8(8, 32 + 32 * _DEPTH),
        _s(b), _s(b), _s(b), _s(b), u8(8, 64), u8(32), _s(b),
        _s(pbatch._MAX_BODY_LAYOUTS, len(pbatch.BODY_TAB_COLS)),
    )
    return pk_kernels._mk_packed_unpack(layout), args


def _graph_verdict_reduce(t=None):
    """The round-6 packed D2H reduction (protocol/batch.verdict_reduce):
    verdict-bit packing + the sequential Blake2b nonce scan
    (ops/blake2b.nonce_fold_scan). The scan body is a separate
    computation (lax.scan fences the chain). No dispatch path runs it
    since PR 29 (the host folds); it stays registered until its goldens
    go with it (ROADMAP)."""
    import jax
    from jax import numpy as jnp

    from ..protocol import batch as pbatch

    b = t or 8

    def bl(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bool_)

    args = (
        _s(5, b), _s(b, 32), _s(b), _s(),
        _s(32), bl(), _s(32), bl(),
    )
    return pbatch.verdict_reduce, args


def _graph_forge_sweep(t=None):
    """The leader-election sweep (protocol/forge.forge_sweep): device
    alpha derivation, the full VRF prove (both proof serializations),
    the Blake2b leader-value tail and the threshold bracket — exactly
    the program the batched synthesizer dispatches per election window.
    Lane-invariant (everything is per-(slot, pool) pair), so the tiny
    registry tile pins the production FORGE_BUCKET structure."""
    import jax
    from jax import numpy as jnp

    from ..protocol import forge as pforge

    b = t or _T

    def u8(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.uint8)

    args = (
        u8(b, 32), u8(b, 32), u8(b, 32), _s(b), u8(32),
        u8(b, 32), u8(b, 32),
    )
    return pforge.forge_sweep, args


def _graph_forge_sign(t=None):
    """The packed OCert-issue signer (protocol/forge.forge_sign — the
    certified ed25519 sign kernel under its forge-lane registry name):
    the sign direction of the forging pipeline carries its own pins at
    the shape the synthesizer dispatches (deduped OCert signables)."""
    import jax
    from jax import numpy as jnp

    from ..protocol import forge as pforge

    b = t or 4

    def u8(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.uint8)

    def u32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.uint32)

    args = (
        u8(b, 32), u8(b, 32), u32(b, _NB, 16, 2), _s(b),
        u32(b, _NB, 16, 2), _s(b),
    )
    return pforge.forge_sign, args


REGISTRY: dict[str, Callable] = {
    "ed_core": _graph_ed_core,
    "kes_core": _graph_kes_core,
    "vrf_core": _graph_vrf_core,
    "vrf_bc_core": _graph_vrf_bc_core,
    "finish_core": _graph_finish_core,
    "verify_praos_core": _graph_verify_praos_core,
    "verify_praos_core_bc": _graph_verify_praos_core_bc,
    "msm": _graph_msm,
    "aggregate_core": _graph_aggregate_core,
    "aggregate_vrf_core": _graph_aggregate_vrf_core,
    "spmd_sharded_verify": _graph_spmd_local,
    "packed_unpack": _graph_packed_unpack,
    "verdict_reduce": _graph_verdict_reduce,
    "forge_sweep": _graph_forge_sweep,
    "forge_sign": _graph_forge_sign,
}


# Source modules (repo-relative) each graph's trace actually executes —
# the `scripts/lint.py --changed` fast path re-analyzes only graphs
# whose module set intersects the git diff. Shared leaves (limbs, curve,
# hashes, field) appear in every pk graph by construction.
_PK_COMMON = [
    "ouroboros_consensus_tpu/ops/pk/limbs.py",
    "ouroboros_consensus_tpu/ops/pk/curve.py",
    "ouroboros_consensus_tpu/ops/pk/hashes.py",
    "ouroboros_consensus_tpu/ops/pk/verify.py",
    "ouroboros_consensus_tpu/ops/field.py",
    "ouroboros_consensus_tpu/ops/bigint.py",
    "ouroboros_consensus_tpu/ops/sha512.py",
    "ouroboros_consensus_tpu/ops/blake2b.py",
    "ouroboros_consensus_tpu/ops/u64.py",
]
_XLA_TWIN = [
    "ouroboros_consensus_tpu/ops/curve.py",
    "ouroboros_consensus_tpu/ops/scalar.py",
    "ouroboros_consensus_tpu/ops/ed25519_batch.py",
    "ouroboros_consensus_tpu/ops/kes_batch.py",
    "ouroboros_consensus_tpu/ops/ecvrf_batch.py",
    "ouroboros_consensus_tpu/protocol/batch.py",
]
GRAPH_SOURCES: dict[str, list[str]] = {
    "ed_core": _PK_COMMON,
    "kes_core": _PK_COMMON,
    "vrf_core": _PK_COMMON,
    "vrf_bc_core": _PK_COMMON,
    "finish_core": _PK_COMMON,
    "verify_praos_core": _PK_COMMON,
    "verify_praos_core_bc": _PK_COMMON,
    "msm": _PK_COMMON + ["ouroboros_consensus_tpu/ops/pk/msm.py"],
    "aggregate_core": _PK_COMMON + [
        "ouroboros_consensus_tpu/ops/pk/msm.py",
        "ouroboros_consensus_tpu/ops/pk/aggregate.py",
    ],
    "aggregate_vrf_core": _PK_COMMON + [
        "ouroboros_consensus_tpu/ops/pk/msm.py",
        "ouroboros_consensus_tpu/ops/pk/aggregate.py",
    ],
    "spmd_sharded_verify": _XLA_TWIN + [
        "ouroboros_consensus_tpu/parallel/spmd.py",
        "ouroboros_consensus_tpu/ops/field.py",
        "ouroboros_consensus_tpu/ops/bigint.py",
        "ouroboros_consensus_tpu/ops/sha512.py",
        "ouroboros_consensus_tpu/ops/blake2b.py",
        "ouroboros_consensus_tpu/ops/u64.py",
    ],
    "packed_unpack": _PK_COMMON + [
        "ouroboros_consensus_tpu/ops/pk/kernels.py",
        "ouroboros_consensus_tpu/protocol/batch.py",
    ],
    "verdict_reduce": [
        "ouroboros_consensus_tpu/protocol/batch.py",
        "ouroboros_consensus_tpu/ops/blake2b.py",
        "ouroboros_consensus_tpu/ops/u64.py",
    ],
    # the forge graphs trace through the XLA-twin ops (ecvrf_batch /
    # ed25519_batch), not the ops/pk ladder cores
    "forge_sweep": _XLA_TWIN + [
        "ouroboros_consensus_tpu/protocol/forge.py",
        "ouroboros_consensus_tpu/ops/field.py",
        "ouroboros_consensus_tpu/ops/bigint.py",
        "ouroboros_consensus_tpu/ops/sha512.py",
        "ouroboros_consensus_tpu/ops/blake2b.py",
        "ouroboros_consensus_tpu/ops/u64.py",
    ],
    "forge_sign": [
        "ouroboros_consensus_tpu/protocol/forge.py",
        "ouroboros_consensus_tpu/ops/ed25519_batch.py",
        "ouroboros_consensus_tpu/ops/curve.py",
        "ouroboros_consensus_tpu/ops/scalar.py",
        "ouroboros_consensus_tpu/ops/bigint.py",
        "ouroboros_consensus_tpu/ops/field.py",
        "ouroboros_consensus_tpu/ops/sha512.py",
        "ouroboros_consensus_tpu/ops/u64.py",
    ],
}


# the tile each builder bakes when called with t=None — trace_graph
# normalizes an explicit t equal to the builder default onto the (name,
# None) cache key so the budget, point-op and certification passes share
# one trace per graph
DEFAULT_TILES: dict[str, int] = {
    "ed_core": _T, "kes_core": _T, "vrf_core": _T, "vrf_bc_core": _T,
    "finish_core": _T, "verify_praos_core": _T, "verify_praos_core_bc": _T,
    "aggregate_core": _T, "aggregate_vrf_core": _T, "msm": 4,
    "spmd_sharded_verify": 8,
    "packed_unpack": 4, "verdict_reduce": 8,
    "forge_sweep": _T, "forge_sign": 4,
}


def registered_graphs() -> list[str]:
    return sorted(REGISTRY)


# trace cache: (name, t) -> ClosedJaxpr. One tier-1 pytest process
# traces each composed graph ONCE no matter how many passes (budgets,
# golden pin, interval, taint, point-ops) consume it — the traces are
# the expensive part (30-60 s each for the composed cores). Capped LRU:
# a composed jaxpr holds ~200k eqn objects, so an unbounded cache would
# pin gigabytes across a full slow-tier sweep; consumers that want
# sharing run their passes per graph before moving on.
_TRACE_CACHE_MAX = 3
_TRACE_CACHE: dict[tuple[str, int | None], object] = {}
# trace-time point-op capture (ops/pk/curve.py op_counter), recorded as
# a free by-product of every cached trace: (name, t) -> dict (kept for
# all keys — counts are tiny)
_POINT_OPS: dict[tuple[str, int | None], dict] = {}


def trace_graph(name: str, t: int | None = None):
    import jax

    if t is not None and t == DEFAULT_TILES.get(name):
        t = None
    key = (name, t)
    if key in _TRACE_CACHE:
        _TRACE_CACHE[key] = _TRACE_CACHE.pop(key)  # LRU touch
        return _TRACE_CACHE[key]
    from ..ops.pk import curve as pc

    fn, args = REGISTRY[name](t)
    with pc.op_counter() as stats:
        traced = jax.make_jaxpr(fn)(*args)
    _POINT_OPS[key] = {"ops": stats["ops"], "lane_ops": stats["lane_ops"]}
    _TRACE_CACHE[key] = traced
    while len(_TRACE_CACHE) > _TRACE_CACHE_MAX:
        _TRACE_CACHE.pop(next(iter(_TRACE_CACHE)))
    return traced


def point_ops(name: str, t: int | None = None) -> dict:
    """Point-op counts captured while tracing (name, t); traces on
    first use. Only the ops/pk graphs route through the counted
    add/double helpers — other graphs report zeros."""
    if t is not None and t == DEFAULT_TILES.get(name):
        t = None
    trace_graph(name, t)
    return dict(_POINT_OPS[(name, t)])


def measure_graph(name: str, lanes: int | None = None,
                  compile: bool = True) -> dict:
    """Lower (and optionally compile) one registered graph at `lanes`
    and extract its device resources (obs/resources.py's vocabulary).
    With compile=True the numbers come from the OPTIMIZED executable
    plus its memory stats — the pin source for scripts/lint.py
    --update-resources; compile=False stops at the HLO cost analysis
    (no peak HBM) for a quick look."""
    import jax

    from ..obs import resources

    fn, args = REGISTRY[name](lanes)
    lowered = jax.jit(fn).lower(*args)
    res = resources.from_lowered(lowered) or {}
    res["source"] = "lowered"
    if compile:
        res.update(resources.from_compiled(lowered.compile()) or {})
        res["source"] = "compiled"
    res["at_lanes"] = lanes if lanes is not None else (
        DEFAULT_TILES.get(name)
    )
    return res


def analyze_registered(names: list[str] | None = None) -> list[GraphReport]:
    reports = []
    for name in names or registered_graphs():
        reports.append(analyze_jaxpr(trace_graph(name), name))
    return reports


# ---------------------------------------------------------------------------
# Budgets
# ---------------------------------------------------------------------------

_BUDGET_PATH = os.path.join(os.path.dirname(__file__), "budgets.json")


def load_budgets(path: str | None = None) -> dict:
    with open(path or _BUDGET_PATH, encoding="utf-8") as f:
        return json.load(f)


def check_point_ops(budgets: dict | None = None,
                    names: list[str] | None = None) -> list[str]:
    """Third ratcheted metric (promoted from scripts/count_point_ops.py):
    per-lane point-op ceilings per graph, pinned in budgets.json under
    "point_ops" as {"at_lanes": T, "lane_ops_per_lane": ceiling}.
    Counts come free with the (name, at_lanes) trace (the op_counter
    capture in trace_graph), so a gate that already traced the graph for
    budgets/certification pays nothing extra. A perf regression in the
    MSM/aggregate path — more adds per bucket pass, a lost shared
    doubling chain — fails here statically, without a device."""
    budgets = budgets if budgets is not None else load_budgets()
    sec = budgets.get("point_ops", {})
    violations = []
    for name in sorted(sec):
        cfg = sec[name]
        if name == "all_stage_total":
            # Composite pin (round 15): the SUM of per-lane point ops
            # across every stage executable the unified dispatch path
            # runs per window (cfg["graphs"]). This is the number the
            # one-RLC fold is accountable for — before the fold the
            # per-window total was agg(vrf) + ed + kes ladders
            # (~1018/lane); folding all four equations into one
            # shared-bucket MSM takes the whole pipeline under 100.
            members = list(cfg["graphs"])
            if names is not None and not set(members) & set(names):
                continue
            lanes = int(cfg["at_lanes"])
            ceiling = float(cfg["lane_ops_per_lane"])
            total = sum(point_ops(g, lanes)["lane_ops"] / lanes
                        for g in members)
            if total > ceiling:
                violations.append(
                    f"all_stage_total: {total:.1f} point lane-ops/lane "
                    f"summed over {'+'.join(members)} at {lanes} lanes "
                    f"exceeds budget {ceiling:g}"
                )
            continue
        if names is not None and name not in names:
            continue
        lanes = int(cfg["at_lanes"])
        ceiling = float(cfg["lane_ops_per_lane"])
        stats = point_ops(name, lanes)
        per_lane = stats["lane_ops"] / lanes
        if per_lane > ceiling:
            violations.append(
                f"{name}: {per_lane:.1f} point lane-ops/lane at "
                f"{lanes} lanes exceeds budget {ceiling:g}"
            )
    return violations


def check_instrumentation_purity(budgets: dict | None = None,
                                 names: list[str] | None = None) -> list[str]:
    """Observability is HOST-side only: re-trace each graph listed under
    budgets.json "instrumentation_purity" with the obs flight recorder
    installed and OCT_TRACE forced on, and fail on ANY equation-count
    delta against the baseline trace. Telemetry that leaks into a traced
    program (an io_callback, a debug print, a traced counter) would grow
    the jaxpr — this differential pins the growth at exactly zero.

    The configured set is the graphs built FROM the instrumented host
    modules (protocol/batch.py, ops/pk/kernels.py): those are the only
    programs whose trace even executes telemetry-adjacent code, so the
    differential is cheap (small tiles) while fencing the real hazard."""
    budgets = budgets if budgets is not None else load_budgets()
    cfg = budgets.get("instrumentation_purity", {})
    todo = [n for n in cfg.get("graphs", [])
            if names is None or n in names]
    if not todo:
        return []
    import jax

    from .. import obs

    violations = []
    for name in todo:
        if name not in REGISTRY:
            violations.append(
                f"{name}: instrumentation_purity names an unregistered graph"
            )
            continue
        base = analyze_jaxpr(trace_graph(name), name).eqns
        old = os.environ.get("OCT_TRACE")
        os.environ["OCT_TRACE"] = "1"
        obs.install()
        try:
            fn, args = REGISTRY[name](None)
            with_obs = analyze_jaxpr(jax.make_jaxpr(fn)(*args), name).eqns
        finally:
            obs.uninstall()
            if old is None:
                os.environ.pop("OCT_TRACE", None)
            else:
                os.environ["OCT_TRACE"] = old
        if with_obs != base:
            violations.append(
                f"{name}: {with_obs - base:+d} equation(s) from telemetry "
                f"({base} -> {with_obs}); observability must stay host-side"
            )
    return violations


def check_budgets(reports: list[GraphReport],
                  budgets: dict | None = None) -> list[str]:
    """-> list of violation strings (empty = all graphs under budget).
    A graph missing from the budget file is itself a violation: every
    registered kernel must carry a pinned ceiling."""
    budgets = budgets if budgets is not None else load_budgets()
    per_graph = budgets.get("graphs", {})
    violations = []
    for r in reports:
        limits = per_graph.get(r.name)
        if limits is None:
            violations.append(
                f"{r.name}: no budget entry in budgets.json "
                "(add one to pin this graph)"
            )
            continue
        for metric, ceiling in limits.items():
            actual = getattr(r, metric, None)
            if actual is None:
                violations.append(f"{r.name}: unknown metric {metric!r}")
            elif actual > ceiling:
                violations.append(
                    f"{r.name}: {metric} = {actual} exceeds budget "
                    f"{ceiling}"
                )
    return violations
