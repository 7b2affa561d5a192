"""octrange — abstract interpretation over the registered crypto jaxprs.

Pass 3 of the analysis subsystem: a jaxpr interpreter (no XLA compile,
no device — pure Python over the traced graph, cheap on the 1-core box)
in the classical Cousot & Cousot (POPL'77) style, instantiated with the
two domains in analysis/domains.py:

  range  — interval/overflow certification at PER-ROW granularity
           along the limb axis (axis 0 for the limb-first ops/pk
           kernels, the MINOR axis for the XLA-twin ops/field.py
           [..., 20] layout — domains.Rows / domains.LastRows). Input
           bounds are seeded from analysis/shapes.json (wire bytes
           0..255, nearly normalized limbs <= B_MAX, ...); transfer
           functions cover the op vocabulary the registered graphs
           actually use; scan/fori bodies run to a fixpoint with
           threshold widening (affine induction counters are pinned to
           their exact closed form instead). Any SIGNED-int eqn whose
           inferred bound leaves its dtype range, and any
           convert_element_type that truncates a non-proven-narrow
           value, is a finding. Unsigned wrap is DEFINED XLA semantics
           (the SHA-512/Blake2b lanes rely on it) and clamps to the
           full dtype range silently.

           Per-row is the load-bearing design point: the limb kernels'
           carry headroom is a PER-ROW invariant. `limbs.mul` folds its
           row 40 with weight FOLD^2 = 369664, which is only safe
           because rows 39-40 receive nothing but second-order carry
           residues (<= 1 after two passes); `limbs.sub` adds the SUBC
           column whose TOP limb is 12287 while the others reach
           2^15.5, so the FOLD-weighted top-row carry is <= 2 only
           per-row. A whole-tensor interval provably cannot certify
           either (it reports top*FOLD^2 as ~3.0e9 > 2^31) — measured
           before this rewrite as ~4k false overflow findings on
           ed_core alone. The LastRows mirror buys the same proof for
           the batch-major twin: field.mul's `.at[..., 0].add(top *
           FOLD^2)` is exactly the axis-transposed fold.

  taint  — secret-independence in the ct-verif spirit (Almeida et al.,
           USENIX Security'16), with two levels: `wire` (untrusted but
           PUBLIC header data — everything a verifier sees) and
           `secret` (sign-path scalars/nonces). ANY taint reaching a
           cond/while predicate is a finding (data-dependent control
           flow is also the TPU batch-uniformity hazard); SECRET taint
           reaching a gather/scatter/dynamic-slice index or a sort key
           is a finding (secret-dependent access pattern). Wire taint
           may steer access patterns: the MSM's per-window argsort runs
           over Fiat–Shamir coefficients, which are deterministic
           functions of PUBLIC wire bytes — public data cannot leak
           through timing, so the sort is clean by policy and the
           certificate records the wire marks that reached it
           (Report.wire_steered).

Lane-count soundness: bounds are certified either at explicit
production lane counts (the lane-SENSITIVE graphs — msm bucket counts,
sum_mod_l lane sums, verdict popcounts — re-traced at the shapes.json
sweep sizes; tracing cost is lane-count independent) or as
LANE-UNIVERSAL certificates: the interpreter records every axis size
that ever scales a bound (reduce/cumsum/dot contractions, iota
extents, collective axes), and if the traced lane-tile size never
appears in that set, no transfer ever consulted it, so the inferred
bounds hold verbatim at every lane count. (Trace-time Python
arithmetic on the lane count — baked literals — would evade the check;
exactly the graphs whose builders do that, msm/aggregate/verdict/spmd,
are the ones certified by explicit sweep instead.)

Certification results are pinned in analysis/certified.json (a ratchet
like baseline.json): scripts/lint.py fails when a graph loses its
proof or grows a taint finding beyond its pinned set.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import re

import numpy as np

from . import domains as D
from . import graphs

_SHAPES_PATH = os.path.join(os.path.dirname(__file__), "shapes.json")
_CERTIFIED_PATH = os.path.join(os.path.dirname(__file__), "certified.json")

# call-like primitives whose subjaxpr runs once with the caller's values
# (a jitted call is `pjit` up to JAX 0.6 and `jit` since)
_CALL_PRIMS = {
    "jit", "pjit", "closed_call", "core_call", "remat", "checkpoint",
    "custom_jvp_call", "custom_vjp_call", "custom_partitioning",
}
# eqns whose (signed) result must fit the dtype — arithmetic that can
# actually overflow. Bitwise/select/shape ops always fit by construction.
_ARITH_CHECK = {
    "add", "sub", "mul", "neg", "abs", "dot_general", "reduce_sum",
    "cumsum", "scatter-add", "shift_left", "integer_pow", "psum",
    "reduce_prod", "cumprod", "pow",
}
# number of plain joins before widening kicks in, and the iteration cap
_FIX_JOINS = 2
_FIX_MAX = 24
# collective scale certified for psum/axis_index: bounds hold for any
# mesh up to this many devices along the batch axis (the traced mesh is
# a single CPU device; production meshes are orders of magnitude below
# this)
SPMD_AXIS_SCALE = 4096
# row-tracking cap: per-row intervals materialize only for axis-0
# extents up to this (the limb/byte axes are <= 41/400); anything
# larger collapses to a whole-tensor bound
ROW_CAP = 512


# `<file>:<line>[:<col>] (<qualified name>)`, as JAX summarizes a frame
_SRC_RE = re.compile(r"^(.*?):(\d+)(?::\d+)? \((.*)\)$")


def _normal_src(s: str) -> str:
    """A source location in the form finding keys are pinned in:
    repo-relative file, line, innermost function name. The installed
    JAX decides whether a summary carries a column and a
    `outer.<locals>.inner` chain; a pin must not."""
    m = _SRC_RE.match(s)
    if m:
        s = f"{m.group(1)}:{m.group(2)} ({m.group(3).rsplit('.', 1)[-1]})"
    # keep the path repo-relative and stable across checkouts
    for marker in ("ouroboros_consensus_tpu/", "tests/", "scripts/"):
        i = s.find(marker)
        if i > 0:
            return s[i:]
    return s


def _src_of(eqn) -> str:
    try:
        from jax._src import source_info_util

        return _normal_src(source_info_util.summarize(eqn.source_info))
    except Exception:
        return "<unknown>"


@dataclasses.dataclass(frozen=True)
class Finding:
    kind: str  # overflow | truncate | unknown-prim |
    #            taint-branch | taint-index | taint-sort | taint-output
    graph: str
    prim: str
    src: str
    message: str

    def key(self) -> str:
        return f"{self.kind}::{self.graph}::{self.prim}::{self.src}"

    def format(self) -> str:
        return (f"{self.graph}: {self.kind} at {self.src} "
                f"[{self.prim}] {self.message}")


@dataclasses.dataclass
class Report:
    graph: str
    domain: str  # "range" | "taint"
    lanes: int | None  # explicit lane count, or None = registry tile
    ok: bool
    findings: list
    eqns: int = 0
    scale_factors: tuple = ()
    lane_universal: bool = False
    output_taint: tuple = ()  # taint domain: union of output marks
    wire_steered: tuple = ()  # taint domain: wire marks at sort/index sites

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["findings"] = [f.format() for f in self.findings]
        d["scale_factors"] = sorted(self.scale_factors)
        d["output_taint"] = sorted(self.output_taint)
        d["wire_steered"] = sorted(self.wire_steered)
        return d


def _dedup(findings: list) -> list:
    """One finding per (kind, src, prim) key, first occurrence wins —
    a memo-missed subjaxpr can report the same source eqn thousands of
    times across call paths."""
    seen: set[str] = set()
    out = []
    for f in findings:
        k = f.key()
        if k not in seen:
            seen.add(k)
            out.append(f)
    return out


def _int_range(dtype) -> tuple[int, int] | None:
    import jax.numpy as jnp

    d = jnp.dtype(dtype)
    if d == jnp.dtype(bool):
        return (0, 1)
    if np.issubdtype(d, np.integer):
        info = np.iinfo(d)
        return (int(info.min), int(info.max))
    return None  # float — no range checks


def _is_signed(dtype) -> bool:
    return np.issubdtype(np.dtype(dtype), np.signedinteger)


def _is_literal(atom) -> bool:
    return hasattr(atom, "val")


def _sub_closed(eqn, key):
    """params[key] as (jaxpr, consts) whether it's closed or open."""
    v = eqn.params[key]
    if hasattr(v, "jaxpr"):  # ClosedJaxpr
        return v.jaxpr, v.consts
    return v, ()


# ---------------------------------------------------------------------------
# Shared driver
# ---------------------------------------------------------------------------


class _Interp:
    """Control-flow driver shared by both domains. Subclasses provide
    per-primitive transfer functions plus const/literal abstraction and
    the join/widen/eq lattice ops."""

    def __init__(self, graph_name: str):
        self.graph = graph_name
        self.findings: list[Finding] = []
        self.eqns = 0
        self.scale_factors: set[int] = set()
        self._memo: dict = {}
        self._const_memo: dict = {}
        self._recording = True
        self._defs: dict = {}
        # test hook (tests/test_absint.py soundness property): when set
        # to a list, collects (eqn, abstract_outs) for every TOP-level
        # eqn so a concrete eqn-by-eqn replay can check containment
        self.eqn_log: list | None = None
        self._level = 0

    # -- lattice hooks (subclass) -------------------------------------------

    def abs_const(self, c):
        raise NotImplementedError

    def abs_literal(self, lit):
        raise NotImplementedError

    def join(self, a, b):
        raise NotImplementedError

    def widen(self, old, new):
        return self.join(old, new)

    def per_step(self, x):
        """Abstraction of one scan step's slice of a stacked value (and
        of one step's output inside the stacked result): axis 0 is the
        SCAN axis there, so axis-0 row structure does not transfer."""
        return x

    def transfer(self, eqn, prim, ins, record):
        raise NotImplementedError

    # -- driver --------------------------------------------------------------

    def record(self, kind, eqn, message):
        self.findings.append(Finding(
            kind, self.graph, eqn.primitive.name, _src_of(eqn), message,
        ))

    def run_closed(self, closed_jaxpr, in_abs, record=True):
        jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
        consts = getattr(closed_jaxpr, "consts", ())
        return self.run_jaxpr(jaxpr, consts, in_abs, record)

    def run_jaxpr(self, jaxpr, consts, in_abs, record=True):
        env: dict = {}
        defs: dict = {}
        self._level += 1
        for v, c in zip(jaxpr.constvars, consts):
            env[v] = self.abs_const(c)
        assert len(jaxpr.invars) == len(in_abs), (
            len(jaxpr.invars), len(in_abs))
        for v, a in zip(jaxpr.invars, in_abs):
            env[v] = a

        def read(atom):
            if _is_literal(atom):
                return self.abs_literal(atom)
            return env[atom]

        try:
            for eqn in jaxpr.eqns:
                self.eqns += 1
                prim = eqn.primitive.name
                ins = [read(a) for a in eqn.invars]
                if prim in _CALL_PRIMS:
                    outs = self._call(eqn, ins, record)
                elif prim == "scan":
                    outs = self._scan(eqn, ins, record)
                elif prim == "while":
                    outs = self._while(eqn, ins, record)
                elif prim == "cond":
                    outs = self._cond(eqn, ins, record)
                elif prim == "shard_map":
                    outs = self._shard_map(eqn, ins, record)
                else:
                    self._defs = defs
                    outs = self.transfer(eqn, prim, ins, record)
                if len(outs) != len(eqn.outvars):
                    raise AssertionError(
                        f"{prim}: {len(outs)} abstract outputs for "
                        f"{len(eqn.outvars)} outvars"
                    )
                if self.eqn_log is not None and self._level == 1 and record:
                    self.eqn_log.append((eqn, list(outs)))
                for v, o in zip(eqn.outvars, outs):
                    env[v] = o
                    defs[v] = eqn
            return [read(v) for v in jaxpr.outvars]
        finally:
            self._level -= 1

    def _call(self, eqn, ins, record):
        key_name = "jaxpr" if "jaxpr" in eqn.params else "call_jaxpr"
        sub, consts = _sub_closed(eqn, key_name)
        if eqn.primitive.name in ("custom_jvp_call", "custom_vjp_call"):
            # the call_jaxpr takes exactly the primal inputs
            ins = ins[: len(sub.invars)]
        return self._memoized(sub, consts, ins, record)

    def _memoized(self, sub, consts, ins, record):
        try:
            # keyed by the recording flag too: a non-recording
            # (fixpoint) hit must never mask the findings a recording
            # pass would have produced
            key = (id(sub), record, tuple(ins))
            hit = self._memo.get(key)
        except TypeError:  # unhashable abstract value (never expected)
            key = hit = None
        if hit is not None:
            outs, sub_findings, sub_eqns, sub_scales = hit
            self.eqns += sub_eqns
            self.scale_factors |= sub_scales
            self.findings.extend(sub_findings)
            return outs
        f0, e0, s0 = len(self.findings), self.eqns, set(self.scale_factors)
        outs = self.run_jaxpr(sub, consts, ins, record)
        if key is not None:
            self._memo[key] = (
                outs,
                tuple(self.findings[f0:]),
                self.eqns - e0,
                self.scale_factors - s0,
            )
        return outs

    def _scan(self, eqn, ins, record):
        p = eqn.params
        sub, consts = _sub_closed(eqn, "jaxpr")
        nc, ncar = p["num_consts"], p["num_carry"]
        sc = ins[:nc]
        carry = list(ins[nc: nc + ncar])
        # affine induction variables (fori_loop counters lower to a
        # `carry_out = carry_in + 1` scan carry) have an EXACT closed
        # form over the known trip count — pin them instead of widening
        # (a widened counter reaches int32 max and its next `i + 1`
        # would report a false overflow)
        pinned = self.pin_scan_carries(
            sub, nc, ncar, p.get("length", 0), carry
        )
        for k, a in pinned.items():
            carry[k] = a
        # per-step slice of each xs: axis 0 is the scan axis, so any
        # axis-0 row structure collapses to a step-universal bound
        xs = [self.per_step(x) for x in ins[nc + ncar:]]

        def step(cur):
            for k, a in pinned.items():
                cur[k] = a
            return self._memoized(sub, consts, sc + cur + xs, False)

        carry = self._fixpoint(step, carry, ncar)
        for k, a in pinned.items():
            carry[k] = a
        outs = self._memoized(sub, consts, sc + carry + xs, record)
        final_carry = [
            self.join(i, o) for i, o in zip(ins[nc: nc + ncar], outs[:ncar])
        ]
        # stacked ys: the new leading axis is the step axis
        return final_carry + [self.per_step(y) for y in outs[ncar:]]

    def _while(self, eqn, ins, record):
        p = eqn.params
        cj, cc = _sub_closed(eqn, "cond_jaxpr")
        bj, bc = _sub_closed(eqn, "body_jaxpr")
        ncc, nbc = p["cond_nconsts"], p["body_nconsts"]
        cond_consts = ins[:ncc]
        body_consts = ins[ncc: ncc + nbc]
        init = list(ins[ncc + nbc:])
        carry = self._fixpoint(
            lambda cur: self._memoized(bj, bc, body_consts + cur, False),
            init, len(init),
        )
        pred = self._memoized(cj, cc, cond_consts + carry, record)
        self.on_while_pred(eqn, pred[0], record)
        outs = self._memoized(bj, bc, body_consts + carry, record)
        return [self.join(i, o) for i, o in zip(init, outs)]

    def _fixpoint(self, step, carry, ncar):
        for it in range(_FIX_MAX):
            outs = step(list(carry))
            new = [self.join(c, o) for c, o in zip(carry, outs[:ncar])]
            if new == carry:
                return carry
            if it >= _FIX_JOINS:
                new = [self.widen(c, n) for c, n in zip(carry, new)]
            carry = new
        return carry  # widening ladder guarantees we land here stable

    def _cond(self, eqn, ins, record):
        self.on_cond_pred(eqn, ins[0], record)
        outs = None
        for br in eqn.params["branches"]:
            sub, consts = _sub_closed_value(br)
            o = self._memoized(sub, consts, ins[1:], record)
            outs = o if outs is None else [
                self.join(a, b) for a, b in zip(outs, o)
            ]
        return outs

    def _shard_map(self, eqn, ins, record):
        sub, consts = _sub_closed(eqn, "jaxpr")
        return self.run_jaxpr(sub, consts, ins, record)

    # taint hooks; the interval domain ignores predicates
    def on_cond_pred(self, eqn, pred, record):
        pass

    def on_while_pred(self, eqn, pred, record):
        pass

    # interval hook; other domains have no notion of a counter
    def pin_scan_carries(self, sub, nc, ncar, length, carry):
        return {}


def _sub_closed_value(v):
    if hasattr(v, "jaxpr"):
        return v.jaxpr, v.consts
    return v, ()


# ---------------------------------------------------------------------------
# Interval domain (per-row)
# ---------------------------------------------------------------------------


class IntervalInterp(_Interp):
    def _minmax(self, a):
        if a.size == 0:
            return (0, 0)
        if a.dtype == np.bool_:
            return (int(a.min()), int(a.max()))
        if np.issubdtype(a.dtype, np.floating):
            return (float(a.min()), float(a.max()))
        return (int(a.min()), int(a.max()))

    def abs_const(self, c):
        key = id(c)
        hit = self._const_memo.get(key)
        if hit is None:
            a = np.asarray(c)
            if a.size == 0:
                hit = (0, 0)
            elif a.ndim and 1 < a.shape[0] <= ROW_CAP:
                # per-row constants carry the limb structure the proofs
                # need (SUBC's 12287 top limb vs 2^15.5 elsewhere). A
                # rank-1 Rows doubles as last-axis structure: the
                # broadcast_in_dim that consumes it decides which
                # convention the value enters under.
                hit = D.rows(self._minmax(a[i]) for i in range(a.shape[0]))
                if (
                    not isinstance(hit, D.Rows) and a.ndim >= 2
                    and 1 < a.shape[-1] <= ROW_CAP
                ):
                    # axis-0-uniform but minor-axis-structured: the
                    # XLA-twin [..., 20] limb convention
                    hit = D.last_rows(
                        self._minmax(a[..., j]) for j in range(a.shape[-1])
                    )
            elif a.ndim >= 2 and 1 < a.shape[-1] <= ROW_CAP:
                hit = D.last_rows(
                    self._minmax(a[..., j]) for j in range(a.shape[-1])
                )
            else:
                hit = self._minmax(a)
            self._const_memo[key] = hit
        return hit

    def abs_literal(self, lit):
        v = lit.val
        if np.ndim(v) > 0:
            return self.abs_const(v)
        a = np.asarray(v)  # 0-d ndarray literals are not scalar instances
        if a.dtype == np.bool_:
            return (int(a), int(a))
        if np.issubdtype(a.dtype, np.floating):
            return (float(a), float(a))  # may be ±inf: floats are unchecked
        return D.iv_const(a)

    def join(self, a, b):
        return D.iv_join_any(a, b)

    def widen(self, old, new):
        return D.iv_widen_any(old, new)

    def per_step(self, x):
        return D.collapse(x)

    def pin_scan_carries(self, sub, nc, ncar, length, carry):
        """Affine induction variables: a SCALAR carry k whose body
        output is `carry_in[k] + c` (c a scalar literal, either sign
        via add/sub) walks init, init+c, ..., init+c*(length-1) — the
        exact interval, no widening. fori_loop counters are the
        motivating instance."""
        if not length:
            return {}
        defs = {}
        for e in sub.eqns:
            for v in e.outvars:
                defs[v] = e
        pinned = {}
        for k in range(ncar):
            inv = sub.invars[nc + k]
            if inv.aval.shape != ():
                continue
            init = carry[k]
            if isinstance(init, (D.Rows, D.LastRows)) or not isinstance(
                init[0], int
            ):
                continue
            ov = sub.outvars[k]
            if _is_literal(ov):
                continue
            e = defs.get(ov)
            if e is None or e.primitive.name not in ("add", "sub"):
                continue
            a, b = e.invars
            step = None
            if a is inv and _is_literal(b) and np.ndim(b.val) == 0:
                step = int(b.val)
                if e.primitive.name == "sub":
                    step = -step
            elif (e.primitive.name == "add" and b is inv
                  and _is_literal(a) and np.ndim(a.val) == 0):
                step = int(a.val)
            if step is None:
                continue
            span = step * (length - 1)
            pinned[k] = (init[0] + min(0, span), init[1] + max(0, span))
        return pinned

    def _check(self, eqn, prim, out, aval, record):
        """Dtype-range policy: signed overflow is a finding, unsigned
        wraps to the full range, results are clamped either way so one
        miss doesn't cascade. Checks are per-row when rows are
        tracked; the finding reports the worst row."""
        rng = _int_range(aval.dtype)
        if rng is None:
            return out
        worst = D.collapse(out)
        if rng[0] <= worst[0] and worst[1] <= rng[1]:
            return out
        if _is_signed(aval.dtype) and prim in _ARITH_CHECK:
            if record:
                self.record(
                    "overflow", eqn,
                    f"inferred bound [{worst[0]}, {worst[1]}] exceeds "
                    f"{np.dtype(aval.dtype).name} range",
                )
        # clamp rowwise (unsigned wrap is defined; signed already
        # reported — clamping stops one miss from cascading)
        if isinstance(out, (D.Rows, D.LastRows)):
            return self._map_struct(
                out,
                lambda r: (max(r[0], rng[0]),
                           min(max(r[1], rng[0]), rng[1])),
            )
        return (max(worst[0], rng[0]), min(max(worst[1], rng[0]), rng[1]))

    def transfer(self, eqn, prim, ins, record):
        out_avals = [v.aval for v in eqn.outvars]
        fn = _IV_TABLE.get(prim)
        if fn is None:
            if record:
                self.record(
                    "unknown-prim", eqn,
                    f"no interval transfer for `{prim}`; assuming full "
                    "dtype range (certification stays unproven)",
                )
            return [
                _int_range(a.dtype) or (-math.inf, math.inf)
                for a in out_avals
            ]
        self._recording = record
        outs = fn(self, eqn, ins)
        return [
            self._check(eqn, prim, o, a, record)
            for o, a in zip(outs, out_avals)
        ]

    # -- helpers -------------------------------------------------------------

    def _scaled(self, n: int):
        if n > 1:
            self.scale_factors.add(int(n))
        return n

    def _dtype_range(self, eqn):
        return _int_range(eqn.outvars[0].aval.dtype) or (
            -math.inf, math.inf)

    def _rows_for(self, x, n):
        """Length-n axis-0 row tuple for one elementwise operand
        (uniform, broadcast and other-convention operands apply their
        collapsed bound to every row)."""
        if isinstance(x, D.Rows) and len(x) == n:
            return x
        return (D.collapse(x),) * n

    def _last_rows_for(self, x, n):
        if isinstance(x, D.LastRows) and len(x) == n:
            return x
        return (D.collapse(x),) * n

    @staticmethod
    def _map_struct(x, f):
        """Apply f per row, preserving whichever convention x carries."""
        if isinstance(x, D.Rows):
            return D.rows(f(r) for r in x)
        if isinstance(x, D.LastRows):
            return D.last_rows(f(r) for r in x)
        return f(x)

    def _onehot_along(self, var, contract_dims) -> bool:
        """True when `var` is an {0,1} indicator produced by comparing
        an iota against a broadcast value, with the iota's dimension
        inside `contract_dims`: along that axis the iota values are all
        distinct, so at most ONE element per contracted row is nonzero
        and a dot against it is a SELECTION, not a sum (the one-hot MXU
        table lookups of ops/pk/curve._onehot_lookup)."""
        defs = getattr(self, "_defs", {})

        def resolve(v, dims):
            for _ in range(6):
                e = defs.get(v)
                if e is None:
                    return False
                name = e.primitive.name
                if name == "convert_element_type":
                    v = e.invars[0]
                    continue
                if name == "eq":
                    for side in e.invars:
                        if _is_literal(side):
                            continue
                        if _iota_dim_in(defs, side, dims):
                            return True
                    return False
                if name == "broadcast_in_dim":
                    bd = e.params["broadcast_dimensions"]
                    inner = {
                        i for i, d in enumerate(bd) if d in dims
                    }
                    if not inner:
                        return False
                    v, dims = e.invars[0], inner
                    continue
                return False
            return False

        return resolve(var, set(contract_dims))


def _iota_dim_in(defs, v, dims) -> bool:
    for _ in range(6):
        e = defs.get(v)
        if e is None:
            return False
        name = e.primitive.name
        if name == "iota":
            return e.params["dimension"] in dims
        if name == "broadcast_in_dim":
            bd = e.params["broadcast_dimensions"]
            inner = {i for i, d in enumerate(bd) if d in dims}
            if not inner:
                return False
            v, dims = e.invars[0], inner
            continue
        if name == "convert_element_type":
            v = e.invars[0]
            continue
        return False
    return False


# -- elementwise wrapper ------------------------------------------------------


def _ew(kernel):
    """Lift a scalar-interval kernel `kernel(self, eqn, vals) -> iv`
    to a per-row transfer: rows materialize only when some operand
    already carries them (byte columns stay uniform and cheap)."""

    def t(self, eqn, ins):
        shape = eqn.outvars[0].aval.shape
        if (
            shape and 1 < shape[0] <= ROW_CAP
            and any(isinstance(x, D.Rows) for x in ins)
        ):
            n = shape[0]
            cols = [self._rows_for(x, n) for x in ins]
            return [D.rows(
                kernel(self, eqn, [c[i] for c in cols]) for i in range(n)
            )]
        if (
            shape and 1 < shape[-1] <= ROW_CAP
            and any(isinstance(x, D.LastRows) for x in ins)
        ):
            n = shape[-1]
            cols = [self._last_rows_for(x, n) for x in ins]
            return [D.last_rows(
                kernel(self, eqn, [c[i] for c in cols]) for i in range(n)
            )]
        return [kernel(self, eqn, [D.collapse(x) for x in ins])]

    return t


def _k_add(self, eqn, v):
    return D.iv_add(v[0], v[1])


def _k_sub(self, eqn, v):
    return D.iv_sub(v[0], v[1])


def _k_mul(self, eqn, v):
    return D.iv_mul(v[0], v[1])


def _k_div(self, eqn, v):
    return D.iv_div(v[0], v[1])


def _k_rem(self, eqn, v):
    return D.iv_rem(v[0], v[1])


def _k_max(self, eqn, v):
    return (max(v[0][0], v[1][0]), max(v[0][1], v[1][1]))


def _k_min(self, eqn, v):
    return (min(v[0][0], v[1][0]), min(v[0][1], v[1][1]))


def _k_neg(self, eqn, v):
    return (-v[0][1], -v[0][0])


def _k_abs(self, eqn, v):
    lo, hi = v[0]
    m = max(abs(lo), abs(hi))
    return (0 if lo <= 0 <= hi else min(abs(lo), abs(hi)), m)


def _k_sign(self, eqn, v):
    lo, hi = v[0]
    return (-1 if lo < 0 else 0 if lo == 0 else 1,
            1 if hi > 0 else 0 if hi == 0 else -1)


def _k_and(self, eqn, v):
    return D.iv_and(v[0], v[1], self._dtype_range(eqn))


def _k_or(self, eqn, v):
    return D.iv_or(v[0], v[1], self._dtype_range(eqn))


def _k_xor(self, eqn, v):
    return D.iv_xor(v[0], v[1], self._dtype_range(eqn))


def _k_not(self, eqn, v):
    lo, hi = v[0]
    rng = self._dtype_range(eqn)
    if rng == (0, 1):
        return (0, 1)
    if not _is_signed(eqn.outvars[0].aval.dtype):
        top = rng[1]
        return (top - hi, top - lo)
    return (-hi - 1, -lo - 1)


def _k_shl(self, eqn, v):
    return D.iv_shl(v[0], v[1])


def _k_shr_arith(self, eqn, v):
    return D.iv_shr(v[0], v[1])


def _k_shr_logical(self, eqn, v):
    if v[0][0] >= 0:
        return D.iv_shr(v[0], v[1])
    return self._dtype_range(eqn)  # negative reinterpretation: bitwise


def _k_select_n(self, eqn, v):
    out = v[1]
    for x in v[2:]:
        out = D.iv_join(out, x)
    return out


def _k_clamp(self, eqn, v):
    lo_b, x, hi_b = v
    lo = max(lo_b[0], min(x[0], hi_b[1]))
    hi = min(hi_b[1], max(x[1], lo_b[0]))
    return (min(lo, hi), max(lo, hi))


def _k_ipow(self, eqn, v):
    return _ipow(v[0], eqn.params["y"])


def _ipow(a, y):
    y = int(y)
    m = max(abs(a[0]), abs(a[1]))
    hi = m ** y
    if y % 2 == 0:
        return (0, hi)
    return (min(a[0] ** y, a[1] ** y), max(a[0] ** y, a[1] ** y))


# -- structural transfers -----------------------------------------------------


def _t_identity(self, eqn, ins):
    return [ins[0]]


def _t_bool(self, eqn, ins):
    return [(0, 1)]


def _t_slice(self, eqn, ins):
    x = ins[0]
    p = eqn.params
    if isinstance(x, D.Rows):
        start, limit = p["start_indices"][0], p["limit_indices"][0]
        stride = (p["strides"][0] if p["strides"] else 1) or 1
        return [D.rows(tuple(x)[start:limit:stride])]
    if isinstance(x, D.LastRows):
        start, limit = p["start_indices"][-1], p["limit_indices"][-1]
        stride = (p["strides"][-1] if p["strides"] else 1) or 1
        return [D.last_rows(tuple(x)[start:limit:stride])]
    return [x]


def _t_concat(self, eqn, ins):
    dim = eqn.params["dimension"]
    out_shape = eqn.outvars[0].aval.shape
    rank = len(out_shape)
    n0 = out_shape[0] if out_shape else 0
    nl = out_shape[-1] if out_shape else 0
    if dim == 0 and 1 < n0 <= ROW_CAP:
        rws = []
        for x, atom in zip(ins, eqn.invars):
            k = atom.aval.shape[0]
            if isinstance(x, D.Rows) and len(x) == k:
                rws.extend(x)
            else:
                rws.extend([D.collapse(x)] * k)
        return [D.rows(rws)]
    if dim == rank - 1 and dim != 0 and 1 < nl <= ROW_CAP:
        rws = []
        for x, atom in zip(ins, eqn.invars):
            k = atom.aval.shape[-1]
            if isinstance(x, D.LastRows) and len(x) == k:
                rws.extend(x)
            else:
                rws.extend([D.collapse(x)] * k)
        return [D.last_rows(rws)]
    if dim != 0 and 1 < n0 <= ROW_CAP and any(
        isinstance(x, D.Rows) for x in ins
    ):
        cols = [self._rows_for(x, n0) for x in ins]
        out = []
        for i in range(n0):
            j = cols[0][i]
            for c in cols[1:]:
                j = D.iv_join(j, c[i])
            out.append(j)
        return [D.rows(out)]
    if dim != rank - 1 and 1 < nl <= ROW_CAP and any(
        isinstance(x, D.LastRows) for x in ins
    ):
        cols = [self._last_rows_for(x, nl) for x in ins]
        out = []
        for i in range(nl):
            j = cols[0][i]
            for c in cols[1:]:
                j = D.iv_join(j, c[i])
            out.append(j)
        return [D.last_rows(out)]
    out = D.collapse(ins[0])
    for x in ins[1:]:
        out = D.iv_join(out, D.collapse(x))
    return [out]


def _t_broadcast(self, eqn, ins):
    x = ins[0]
    if not isinstance(x, (D.Rows, D.LastRows)):
        return [x]
    bd = eqn.params["broadcast_dimensions"]
    shape = eqn.params["shape"]
    in_shape = eqn.invars[0].aval.shape
    out_rank = len(shape)
    if isinstance(x, D.Rows):
        if bd and bd[0] == 0 and in_shape and in_shape[0] == shape[0]:
            return [x]
        # a rank-1 Rows broadcast into the MINOR axis enters the
        # XLA-twin convention: [20] limbs -> [..., 20]
        if (
            len(in_shape) == 1 and bd and bd[0] == out_rank - 1
            and shape[-1] == in_shape[0]
        ):
            return [D.LastRows(tuple(x))]
        return [D.collapse(x)]
    if (
        bd and bd[-1] == out_rank - 1 and in_shape
        and in_shape[-1] == shape[-1]
    ):
        return [x]
    return [D.collapse(x)]


def _t_reshape(self, eqn, ins):
    x = ins[0]
    if not isinstance(x, (D.Rows, D.LastRows)):
        return [x]
    new = eqn.params["new_sizes"]
    old = eqn.invars[0].aval.shape
    if isinstance(x, D.Rows):
        if new and old and new[0] == old[0]:
            return [x]
    elif new and old and new[-1] == old[-1]:
        return [x]
    return [D.collapse(x)]


def _t_transpose(self, eqn, ins):
    x = ins[0]
    if not isinstance(x, (D.Rows, D.LastRows)):
        return [x]
    perm = eqn.params["permutation"]
    if not perm:
        return [x]
    if isinstance(x, D.Rows):
        if perm[0] == 0:
            return [x]
        if perm[-1] == 0:  # leading axis moved minor: convention flips
            return [D.LastRows(tuple(x))]
        return [D.collapse(x)]
    if perm[-1] == len(perm) - 1:
        return [x]
    if perm[0] == len(perm) - 1:
        return [D.Rows(tuple(x))]
    return [D.collapse(x)]


def _t_squeeze(self, eqn, ins):
    x = ins[0]
    if not isinstance(x, (D.Rows, D.LastRows)):
        return [x]
    dims = eqn.params["dimensions"]
    in_rank = len(eqn.invars[0].aval.shape)
    if isinstance(x, D.Rows):
        return [D.collapse(x) if 0 in dims else x]
    return [D.collapse(x) if (in_rank - 1) in dims else x]


def _t_rev(self, eqn, ins):
    x = ins[0]
    dims = eqn.params["dimensions"]
    if isinstance(x, D.Rows) and 0 in dims:
        return [D.rows(tuple(x)[::-1])]
    if isinstance(x, D.LastRows) and (
        len(eqn.invars[0].aval.shape) - 1
    ) in dims:
        return [D.last_rows(tuple(x)[::-1])]
    return [x]


def _t_pad(self, eqn, ins):
    x, pv = ins[0], D.collapse(ins[1])
    cfg = eqn.params["padding_config"]
    if not isinstance(x, (D.Rows, D.LastRows)):
        if any(lo or hi or it for lo, hi, it in cfg):
            return [D.iv_join(x, pv)]
        return [x]
    if isinstance(x, D.Rows):
        own, rest, build = cfg[0], cfg[1:], D.rows
    else:
        own, rest, build = cfg[-1], cfg[:-1], D.last_rows
    pad_rest = any(lo or hi or it for lo, hi, it in rest)
    lo0, hi0, it0 = own if cfg else (0, 0, 0)
    if it0 or lo0 < 0 or hi0 < 0:
        return [D.iv_join(D.collapse(x), pv)]
    rws = [D.iv_join(r, pv) if pad_rest else r for r in x]
    rws = [pv] * lo0 + rws + [pv] * hi0
    if len(rws) > ROW_CAP:
        return [D.iv_join(D.collapse(x), pv)]
    return [build(rws)]


def _t_iota(self, eqn, ins):
    d = eqn.params["dimension"]
    shape = eqn.params["shape"]
    n = shape[d]
    self._scaled(n)
    if 1 < n <= ROW_CAP:
        # per-row iota values are EXACT along the iota axis — the index
        # comparisons the one-hot lookups and padding masks build on
        if d == 0:
            return [D.rows((k, k) for k in range(n))]
        if d == len(shape) - 1:
            return [D.last_rows((k, k) for k in range(n))]
    return [(0, max(0, n - 1))]


def _struct_axis(x, shape):
    """(tracked axis, expand, build) for whichever convention x uses."""
    if isinstance(x, D.Rows):
        return 0, D.rows_of, D.rows
    if isinstance(x, D.LastRows):
        return len(shape) - 1, D.last_rows_of, D.last_rows
    return None, None, None


def _t_reduce_sum(self, eqn, ins):
    shape = eqn.invars[0].aval.shape
    axes = eqn.params["axes"]
    x = ins[0]
    raxis, expand, build = _struct_axis(x, shape)
    n_other = 1
    for ax in axes:
        if ax != raxis:
            n_other *= shape[ax]
            self._scaled(shape[ax])
    if raxis is not None and raxis in axes:
        self._scaled(shape[raxis])
        rws = expand(x, shape[raxis])
        lo = sum(r[0] for r in rws)
        hi = sum(r[1] for r in rws)
        return [(lo * n_other, hi * n_other)]
    if raxis is None and axes:
        # uniform: n_other already covers every reduced axis
        return [D.iv_scale(D.collapse(x), n_other)]
    if n_other == 1:
        return [x]
    return [build(D.iv_scale(r, n_other) for r in expand(x, shape[raxis]))]


def _t_reduce_prod(self, eqn, ins):
    shape = eqn.invars[0].aval.shape
    n = 1
    for ax in eqn.params["axes"]:
        n *= shape[ax]
        self._scaled(shape[ax])
    a = D.collapse(ins[0])
    m = max(abs(a[0]), abs(a[1]))
    hi = m ** n
    lo = 0 if a[0] >= 0 else -hi
    return [(lo, hi)]


def _t_reduce_max(self, eqn, ins):
    x = ins[0]
    shape = eqn.invars[0].aval.shape
    raxis, expand, _ = _struct_axis(x, shape)
    if raxis is not None and raxis in eqn.params["axes"]:
        rws = expand(x, shape[raxis])
        return [(max(r[0] for r in rws), max(r[1] for r in rws))]
    return [x]


def _t_reduce_min(self, eqn, ins):
    x = ins[0]
    shape = eqn.invars[0].aval.shape
    raxis, expand, _ = _struct_axis(x, shape)
    if raxis is not None and raxis in eqn.params["axes"]:
        rws = expand(x, shape[raxis])
        return [(min(r[0] for r in rws), min(r[1] for r in rws))]
    return [x]


def _t_argminmax(self, eqn, ins):
    n = 1
    shape = eqn.invars[0].aval.shape
    for ax in eqn.params["axes"]:
        n *= shape[ax]
        self._scaled(shape[ax])
    return [(0, max(0, n - 1))]


def _t_cumsum(self, eqn, ins):
    ax = eqn.params["axis"]
    shape = eqn.invars[0].aval.shape
    n = shape[ax]
    x = ins[0]
    self._scaled(n)
    raxis, _, build = _struct_axis(x, shape)
    if raxis is not None and ax == raxis:
        rws = list(x)
        if eqn.params.get("reverse"):
            rws = rws[::-1]
        lo = hi = 0
        out = []
        for r in rws:
            lo += r[0]
            hi += r[1]
            out.append((lo, hi))
        if eqn.params.get("reverse"):
            out = out[::-1]
        return [build(out)]
    if raxis is not None:
        return [build(
            (min(r[0], n * r[0]), max(r[1], n * r[1])) for r in x
        )]
    a = D.collapse(x)
    return [(min(a[0], n * a[0]), max(a[1], n * a[1]))]


def _t_cumprod(self, eqn, ins):
    ax = eqn.params["axis"]
    n = eqn.invars[0].aval.shape[ax]
    self._scaled(n)
    a = D.collapse(ins[0])
    m = max(abs(a[0]), abs(a[1]), 1)
    hi = m ** n
    lo = min(a[0], 0 if a[0] >= 0 else -hi)
    return [(lo, max(a[1], hi))]


def _t_dot_general(self, eqn, ins):
    (lc, rc), _ = eqn.params["dimension_numbers"]
    k = 1
    for ax in lc:
        n = eqn.invars[0].aval.shape[ax]
        k *= n
        self._scaled(n)
    prod = D.iv_mul(D.collapse(ins[0]), D.collapse(ins[1]))
    for operand_idx, cdims in ((0, lc), (1, rc)):
        atom = eqn.invars[operand_idx]
        if not _is_literal(atom) and self._onehot_along(atom, cdims):
            # at most one nonzero term: a selection, not a k-term sum
            return [D.iv_join((0, 0), prod)]
    return [D.iv_scale(prod, k)]


def _t_scatter_add(self, eqn, ins):
    dn = eqn.params["dimension_numbers"]
    upd_aval = eqn.invars[2].aval
    window = set(dn.update_window_dims)
    n = 1
    for i, s in enumerate(upd_aval.shape):
        if i not in window:
            n *= s
            self._scaled(s)
    add = D.iv_scale(D.collapse(ins[2]), n)
    x, idx = ins[0], ins[1]
    op_shape = eqn.invars[0].aval.shape
    last = len(op_shape) - 1
    if (
        isinstance(x, D.LastRows) and n == 1
        and tuple(dn.scatter_dims_to_operand_dims) == (last,)
        and tuple(dn.inserted_window_dims) == (last,)
        and not isinstance(idx, (D.Rows, D.LastRows))
        and idx[0] == idx[1] and 0 <= idx[0] < len(x)
    ):
        # the `.at[..., k].add(v)` idiom with a static k (field.py's
        # FOLD^2 fold of limb 40 onto limb 0): only row k widens
        k = int(idx[0])
        rws = list(x)
        rws[k] = (rws[k][0] + min(0, add[0]), rws[k][1] + max(0, add[1]))
        return [D.last_rows(rws)]
    lo, hi = D.collapse(x)
    return [(lo + min(0, add[0]), hi + max(0, add[1]))]


def _t_scatter_set(self, eqn, ins):
    x, u = ins[0], D.collapse(ins[2])
    return [self._map_struct(x, lambda r: D.iv_join(r, u))
            if isinstance(x, (D.Rows, D.LastRows)) else D.iv_join(x, u)]


def _t_dus(self, eqn, ins):
    x, u = ins[0], D.collapse(ins[1])
    return [self._map_struct(x, lambda r: D.iv_join(r, u))
            if isinstance(x, (D.Rows, D.LastRows)) else D.iv_join(x, u)]


def _t_gather(self, eqn, ins):
    return [D.collapse(ins[0])]


def _t_sort(self, eqn, ins):
    dim = eqn.params.get("dimension", 0)
    out = []
    for x, atom in zip(ins, eqn.invars):
        rank = len(atom.aval.shape)
        if isinstance(x, D.Rows) and dim == 0:
            out.append(D.collapse(x))  # sorting mixes the tracked rows
        elif isinstance(x, D.LastRows) and dim == rank - 1:
            out.append(D.collapse(x))
        else:
            out.append(x)  # per-row multisets are permuted, not mixed
    return out


def _t_popcount(self, eqn, ins):
    bits = np.dtype(eqn.invars[0].aval.dtype).itemsize * 8
    return [(0, bits)]


def _t_convert(self, eqn, ins):
    x = ins[0]
    new = eqn.params["new_dtype"]
    rng = _int_range(new)

    def conv1(iv):
        lo, hi = iv
        if rng is None:  # -> float
            return (float(lo), float(hi)), False
        if isinstance(lo, float) or isinstance(hi, float):
            if not (math.isfinite(lo) and math.isfinite(hi)):
                lo, hi = rng[0] - 1, rng[1] + 1  # force the truncate path
            else:
                lo, hi = math.trunc(lo), math.trunc(hi)  # XLA truncates
                lo, hi = min(lo, hi), max(lo, hi)
        if rng[0] <= lo and hi <= rng[1]:
            return (lo, hi), False
        return rng, (lo, hi)

    if isinstance(x, (D.Rows, D.LastRows)):
        build = D.rows if isinstance(x, D.Rows) else D.last_rows
        out, worst = [], None
        for r in x:
            o, trunc = conv1(r)
            out.append(o)
            if trunc and (worst is None or trunc[1] > worst[1]):
                worst = trunc
        if worst and self._recording:
            self.record(
                "truncate", eqn,
                f"convert to {np.dtype(new).name} truncates inferred "
                f"[{worst[0]}, {worst[1]}]",
            )
        return [build(out)]
    o, trunc = conv1(x)
    if trunc and self._recording:
        # truncation of a non-proven-narrow value — the specific check
        # the PR 3 bug class calls for (a narrowing cast is only safe
        # when the interpreter has PROVEN the operand narrow)
        self.record(
            "truncate", eqn,
            f"convert to {np.dtype(new).name} truncates inferred "
            f"[{trunc[0]}, {trunc[1]}]",
        )
    return [o]


def _t_psum(self, eqn, ins):
    s = self._scaled(SPMD_AXIS_SCALE)
    return [
        self._map_struct(x, lambda r: D.iv_scale(r, s)) for x in ins
    ]


def _t_pminmax(self, eqn, ins):
    return list(ins)


def _t_axis_index(self, eqn, ins):
    self._scaled(SPMD_AXIS_SCALE)
    return [(0, SPMD_AXIS_SCALE - 1)]


_IV_TABLE = {
    "add": _ew(_k_add),
    "sub": _ew(_k_sub),
    "mul": _ew(_k_mul),
    "div": _ew(_k_div),
    "rem": _ew(_k_rem),
    "max": _ew(_k_max),
    "min": _ew(_k_min),
    "neg": _ew(_k_neg),
    "abs": _ew(_k_abs),
    "sign": _ew(_k_sign),
    "and": _ew(_k_and),
    "or": _ew(_k_or),
    "xor": _ew(_k_xor),
    "not": _ew(_k_not),
    "shift_left": _ew(_k_shl),
    "shift_right_arithmetic": _ew(_k_shr_arith),
    "shift_right_logical": _ew(_k_shr_logical),
    "select_n": _ew(_k_select_n),
    "clamp": _ew(_k_clamp),
    "integer_pow": _ew(_k_ipow),
    "iota": _t_iota,
    "eq": _t_bool,
    "ne": _t_bool,
    "lt": _t_bool,
    "le": _t_bool,
    "gt": _t_bool,
    "ge": _t_bool,
    "is_finite": _t_bool,
    "reduce_and": _t_bool,
    "reduce_or": _t_bool,
    "reduce_xor": _t_bool,
    "reduce_sum": _t_reduce_sum,
    "reduce_prod": _t_reduce_prod,
    "reduce_min": _t_reduce_min,
    "reduce_max": _t_reduce_max,
    "argmax": _t_argminmax,
    "argmin": _t_argminmax,
    "cumsum": _t_cumsum,
    "cumprod": _t_cumprod,
    "dot_general": _t_dot_general,
    "scatter-add": _t_scatter_add,
    "scatter": _t_scatter_set,
    "dynamic_update_slice": _t_dus,
    "pad": _t_pad,
    "gather": _t_gather,
    "dynamic_slice": _t_gather,
    "sort": _t_sort,
    "population_count": _t_popcount,
    "convert_element_type": _t_convert,
    "psum": _t_psum,
    "pmin": _t_pminmax,
    "pmax": _t_pminmax,
    "axis_index": _t_axis_index,
    "device_put": _t_pminmax,
    "broadcast_in_dim": _t_broadcast,
    "reshape": _t_reshape,
    "transpose": _t_transpose,
    "squeeze": _t_squeeze,
    "rev": _t_rev,
    "slice": _t_slice,
    "copy": _t_identity,
    "stop_gradient": _t_identity,
    "concatenate": _t_concat,
}


# ---------------------------------------------------------------------------
# Taint domain
# ---------------------------------------------------------------------------

_INDEX_OPERANDS = {
    "gather": lambda eqn: [1],
    "scatter": lambda eqn: [1],
    "scatter-add": lambda eqn: [1],
    "dynamic_slice": lambda eqn: list(range(1, len(eqn.invars))),
    "dynamic_update_slice": lambda eqn: list(range(2, len(eqn.invars))),
}


class TaintInterp(_Interp):
    def __init__(self, graph_name: str):
        super().__init__(graph_name)
        # informational: wire marks that steered a sort/index site —
        # clean by policy (public data cannot leak through timing) but
        # pinned in the certificate so a new steering site is visible
        self.wire_steered: set[str] = set()

    def abs_const(self, c):
        return D.NO_TAINT

    def abs_literal(self, lit):
        return D.NO_TAINT

    def join(self, a, b):
        return D.taint_join(a, b)

    def on_cond_pred(self, eqn, pred, record):
        if pred and record:
            self.record(
                "taint-branch", eqn,
                f"cond predicate carries {sorted(pred)} — "
                "data-dependent control flow",
            )

    def on_while_pred(self, eqn, pred, record):
        if pred and record:
            self.record(
                "taint-branch", eqn,
                f"while condition carries {sorted(pred)} — "
                "data-dependent trip count",
            )

    def transfer(self, eqn, prim, ins, record):
        if record:
            idx_of = _INDEX_OPERANDS.get(prim)
            if idx_of is not None:
                marks = D.taint_join(*(ins[i] for i in idx_of(eqn)))
                secret = D.taint_secret(marks)
                if secret:
                    self.record(
                        "taint-index", eqn,
                        f"{prim} index derives from {sorted(secret)} — "
                        "secret-dependent access pattern",
                    )
                wire = D.taint_wire(marks)
                if wire:
                    self.wire_steered.add(
                        f"{prim}@{_src_of(eqn)}: {','.join(sorted(wire))}"
                    )
            elif prim == "sort":
                nk = eqn.params.get("num_keys", 1)
                marks = D.taint_join(*ins[:nk])
                secret = D.taint_secret(marks)
                if secret:
                    self.record(
                        "taint-sort", eqn,
                        f"sort keys derive from {sorted(secret)} — "
                        "secret-dependent permutation",
                    )
                wire = D.taint_wire(marks)
                if wire:
                    self.wire_steered.add(
                        f"sort@{_src_of(eqn)}: {','.join(sorted(wire))}"
                    )
        joined = D.taint_join(*ins) if ins else D.NO_TAINT
        return [joined] * len(eqn.outvars)


# ---------------------------------------------------------------------------
# Specs (analysis/shapes.json) and certification
# ---------------------------------------------------------------------------

# named bound classes the specs refer to; `limb` is the nearly
# normalized field-limb bound (ops/field.B_MAX), `limb13` a normalized
# 13-bit row (e.g. scalars < L after Barrett)
BOUND_CLASSES = {
    "byte": (0, 255),
    "bit": (0, 1),
    "bool": (0, 1),
    "nibble": (0, 15),
    "limb": (0, 9500),
    "limb13": (0, 8191),
    "nblocks": (0, 64),
    "u16": (0, 2 ** 16 - 1),
    "i32": (-(2 ** 31), 2 ** 31 - 1),
    "nonneg": (0, 2 ** 31 - 1),
    "u32": (0, 2 ** 32 - 1),
}


def load_shapes(path: str | None = None) -> dict:
    with open(path or _SHAPES_PATH, encoding="utf-8") as f:
        return json.load(f)


def load_certified(path: str | None = None) -> dict:
    with open(path or _CERTIFIED_PATH, encoding="utf-8") as f:
        return json.load(f)


def _spec_of(name: str, shapes: dict | None = None) -> dict:
    shapes = shapes or load_shapes()
    spec = shapes["graphs"].get(name)
    if spec is None:
        raise KeyError(f"no input spec for graph {name!r} in shapes.json")
    return spec


def _trace_any(name: str, lanes: int | None):
    """Trace a registry graph or an absint-only aux target."""
    if name in graphs.REGISTRY:
        return graphs.trace_graph(name, lanes)
    import jax

    fn, args = AUX_REGISTRY[name](lanes)
    return jax.make_jaxpr(fn)(*args)


def input_intervals(name: str, closed, shapes: dict | None = None):
    spec = _spec_of(name, shapes)
    classes = spec["args"]
    invars = closed.jaxpr.invars
    if isinstance(classes, dict):
        # {"all": class, "<idx>": override} — the variadic aux targets
        base = classes.get("all")
        return [
            BOUND_CLASSES[classes.get(str(i), base)]
            for i in range(len(invars))
        ]
    if len(classes) != len(invars):
        raise ValueError(
            f"{name}: shapes.json lists {len(classes)} args, trace has "
            f"{len(invars)}"
        )
    return [BOUND_CLASSES[c] for c in classes]


def input_taints(name: str, closed, shapes: dict | None = None):
    spec = _spec_of(name, shapes)
    n = len(closed.jaxpr.invars)
    out = [D.NO_TAINT] * n
    for idx, mark in spec.get("taint", {}).items():
        level, label = mark.split(":", 1)
        out[int(idx)] = D.taint(level, label)
    return out


def certify_range(name: str, lanes: int | None = None,
                  shapes: dict | None = None) -> Report:
    """Interval/overflow certification of one graph at one lane count
    (None = the registry's default tile). A kernel's own shape guard
    firing at the swept lane count (e.g. sum_mod_l's t <= 2^17 assert)
    is a FAILED proof at that shape, not a crash of the gate."""
    shapes = shapes or load_shapes()
    try:
        closed = _trace_any(name, lanes)
    except Exception as e:
        return Report(
            graph=name, domain="range", lanes=lanes, ok=False,
            findings=[Finding(
                "trace-error", name, "trace", f"<trace@{lanes}>",
                f"{type(e).__name__}: {e}",
            )],
        )
    interp = IntervalInterp(name)
    interp.run_closed(closed, input_intervals(name, closed, shapes))
    spec = _spec_of(name, shapes)
    tile = lanes if lanes is not None else spec["default_tile"]
    universal = tile not in interp.scale_factors
    findings = _dedup(interp.findings)
    ok = not findings and (
        universal or bool(spec.get("lane_sensitive"))
    )
    return Report(
        graph=name, domain="range", lanes=lanes, ok=ok,
        findings=findings, eqns=interp.eqns,
        scale_factors=tuple(sorted(interp.scale_factors)),
        lane_universal=universal,
    )


def certify_taint(name: str, lanes: int | None = None,
                  shapes: dict | None = None) -> Report:
    """Secret-taint certification (taint structure is lane-count
    independent, so the caller usually passes the lane count whose
    trace is already cached)."""
    shapes = shapes or load_shapes()
    closed = _trace_any(name, lanes)
    interp = TaintInterp(name)
    outs = interp.run_closed(closed, input_taints(name, closed, shapes))
    out_marks = sorted(set().union(*outs)) if outs else []
    spec = _spec_of(name, shapes)
    findings = _dedup(interp.findings)
    if not spec.get("declassified_outputs", True):
        secret = [m for m in out_marks if m.startswith("secret:")]
        if secret:
            findings.append(Finding(
                "taint-output", name, "outvars", "<graph outputs>",
                f"secret marks {secret} reach host materialization",
            ))
    return Report(
        graph=name, domain="taint", lanes=lanes, ok=not findings,
        findings=findings, eqns=interp.eqns,
        output_taint=tuple(out_marks),
        wire_steered=tuple(sorted(interp.wire_steered)),
    )


def sweep_lanes(name: str, tier: str,
                shapes: dict | None = None) -> list[int | None]:
    spec = _spec_of(name, shapes)
    sw = spec.get("sweeps", {})
    lanes = sw.get(tier, sw.get("fast", [None]))
    return [None if v is None else int(v) for v in lanes]


def certify_graph(name: str, tier: str = "fast",
                  shapes: dict | None = None) -> list[Report]:
    """The spec's domains over the tier's lane sweep. The taint pass
    reuses the first swept lane count's trace (same cache key)."""
    shapes = shapes or load_shapes()
    spec = _spec_of(name, shapes)
    domains = spec.get("domains", ["range", "taint"])
    out = []
    lane_list = sweep_lanes(name, tier, shapes)
    if "range" in domains:
        for lanes in lane_list:
            out.append(certify_range(name, lanes, shapes))
    if "taint" in domains:
        out.append(certify_taint(name, lane_list[0], shapes))
    return out


def certify_all(tier: str = "fast", names: list[str] | None = None,
                shapes: dict | None = None) -> list[Report]:
    """Certify every (or the named) graph over the tier's sweeps, one
    graph at a time so each trace is consumed by both domains while it
    is still in trace_graph's LRU cache."""
    shapes = shapes or load_shapes()
    out: list[Report] = []
    for name in names if names is not None else certifiable_graphs():
        out.extend(certify_graph(name, tier, shapes))
    return out


def certified_payload(reports: list[Report],
                      shapes: dict | None = None) -> dict:
    """The certified.json pin structure for a report sweep: per graph,
    the range status ('proven' / 'lost' / 'skipped' for taint-only
    specs), the certified lane counts, and the pinned taint finding
    keys (sorted — machine-stable for CI diffing)."""
    shapes = shapes or load_shapes()
    pins: dict = {}
    for r in reports:
        g = pins.setdefault(r.graph, {})
        if r.domain == "range":
            lost = g.get("range") == "lost" or not r.ok
            g["range"] = "lost" if lost else "proven"
            g.setdefault("range_lanes", []).append(r.lanes)
            g["lane_universal"] = bool(
                g.get("lane_universal", True) and r.lane_universal
            )
        else:
            g["taint"] = "clean" if not r.findings else "pinned"
            g["taint_findings"] = sorted(f.key() for f in r.findings)
            g["output_taint"] = sorted(r.output_taint)
            g["wire_steered"] = sorted(r.wire_steered)
    for name in shapes["graphs"]:
        if name in pins and "range" not in pins[name]:
            pins[name]["range"] = "skipped"
    return pins


def write_certified(reports: list[Report], path: str | None = None,
                    shapes: dict | None = None) -> dict:
    payload = {
        "comment": (
            "octrange certification ratchet (analysis/absint.py; the "
            "certified.json twin of baseline.json). Every graph pins "
            "its range proof status and its exact taint finding keys; "
            "scripts/lint.py fails when a kernel edit loses a proof, "
            "grows a new taint finding, or leaves a pinned finding "
            "stale. Regenerate deliberately with "
            "scripts/lint.py --update-certified."
        ),
        "graphs": certified_payload(reports, shapes),
    }
    with open(path or _CERTIFIED_PATH, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    return payload


def check_certified(reports: list[Report],
                    certified: dict | None = None) -> list[str]:
    """Ratchet: every report must match its pinned certified.json
    status — a graph pinned `proven`/`clean` that now has findings (or
    taint findings beyond its pinned key set) is a violation, as is a
    report with no pin at all."""
    certified = certified if certified is not None else load_certified()
    pins = certified.get("graphs", {})
    violations = []
    for r in reports:
        pin = pins.get(r.graph)
        if pin is None:
            violations.append(
                f"{r.graph}: no certified.json entry (pin this graph)")
            continue
        if r.domain == "range":
            status = pin.get("range")
            if status != "proven":
                violations.append(
                    f"{r.graph}: certified.json range status is "
                    f"{status!r}, expected 'proven'")
            if not r.ok:
                msgs = "; ".join(f.format() for f in r.findings[:4])
                extra = (
                    msgs or "bounds are lane-dependent but the graph is "
                            "not marked lane_sensitive")
                violations.append(
                    f"{r.graph}: range proof LOST at lanes="
                    f"{r.lanes}: {extra}")
        else:
            pinned = set(pin.get("taint_findings", []))
            current = {f.key() for f in r.findings}
            new = current - pinned
            stale = pinned - current
            if pin.get("taint") == "clean" and current:
                violations.append(
                    f"{r.graph}: taint was pinned clean, now: " +
                    "; ".join(sorted(new or current)))
            elif new:
                violations.append(
                    f"{r.graph}: NEW taint findings: " +
                    "; ".join(sorted(new)))
            if stale:
                violations.append(
                    f"{r.graph}: stale pinned taint findings (tighten "
                    f"certified.json): " + "; ".join(sorted(stale)))
    return violations


# ---------------------------------------------------------------------------
# Absint-only aux targets (lane-sensitive leaf kernels + the sign path)
# ---------------------------------------------------------------------------


def _aux_sum_mod_l(nterms: int, default_t: int):
    def build(t=None):
        import jax
        from jax import numpy as jnp

        from ..ops.pk import limbs as fe

        tt = t or default_t

        def fn(*terms):
            return fe.sum_mod_l(list(terms))

        args = tuple(
            jax.ShapeDtypeStruct((20, tt), jnp.int32) for _ in range(nterms)
        )
        return fn, args

    return build


def _aux_mul_mod_l(t=None):
    import jax
    from jax import numpy as jnp

    from ..ops.pk import limbs as fe

    tt = t or 8192
    s = jax.ShapeDtypeStruct((20, tt), jnp.int32)
    return fe.mul_mod_l, (s, s)


def _aux_ed25519_sign(t=None):
    import jax
    from jax import numpy as jnp

    from ..ops import ed25519_batch as eb

    b = t or 4
    nb = 2

    def u8(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.uint8)

    def u32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.uint32)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    args = (
        u8(b, 32), u8(b, 32), u32(b, nb, 16, 2), i32(b),
        u32(b, nb, 16, 2), i32(b),
    )
    return eb.sign, args


AUX_REGISTRY = {
    # the PR 3 sum_mod_l carry-normalization proof obligations: 3 terms
    # at the ~87k-lane boundary (the documented overflow threshold is
    # 2^31/8191 = 262177 lane-terms; 3 x 87381 = 262143 sits just
    # under), the 40 x 8192 max-term regression shape, and the
    # 128 x 8192 "epoch" shape (= 2^20 lane-terms, the 1M-headers
    # equivalent of one aggregated window stream)
    "sum_mod_l_3t": _aux_sum_mod_l(3, 87381),
    "sum_mod_l_40t": _aux_sum_mod_l(40, 8192),
    "sum_mod_l_epoch": _aux_sum_mod_l(128, 8192),
    "mul_mod_l": _aux_mul_mod_l,
    # sign path: REAL secrets (clamped scalar a, nonce-hash blocks) —
    # the taint certificate pins whatever secret-indexed access the
    # XLA-twin fixed-base ladder performs
    "ed25519_sign": _aux_ed25519_sign,
}


# traced source modules per aux target (the scripts/lint.py --changed
# fast path; REGISTRY graphs use graphs.GRAPH_SOURCES)
_LIMBS = ["ouroboros_consensus_tpu/ops/pk/limbs.py",
          "ouroboros_consensus_tpu/ops/field.py"]
AUX_SOURCES: dict[str, list[str]] = {
    "sum_mod_l_3t": _LIMBS,
    "sum_mod_l_40t": _LIMBS,
    "sum_mod_l_epoch": _LIMBS,
    "mul_mod_l": _LIMBS,
    "ed25519_sign": [
        "ouroboros_consensus_tpu/ops/ed25519_batch.py",
        "ouroboros_consensus_tpu/ops/curve.py",
        "ouroboros_consensus_tpu/ops/scalar.py",
        "ouroboros_consensus_tpu/ops/bigint.py",
        "ouroboros_consensus_tpu/ops/field.py",
        "ouroboros_consensus_tpu/ops/sha512.py",
        "ouroboros_consensus_tpu/ops/u64.py",
    ],
}


def certifiable_graphs() -> list[str]:
    return sorted(set(graphs.REGISTRY) | set(AUX_REGISTRY))


def check_registry_drift(shapes: dict | None = None) -> list[str]:
    """Registry drift gate (scripts/lint.py): every graphs.py REGISTRY
    entry (and every aux target) must carry a shapes.json input spec
    and a GRAPH_SOURCES/AUX_SOURCES mapping. A missing spec used to
    surface only as a KeyError deep inside certification (or, for the
    --changed source mapping, as a graph silently never re-selected by
    the fast path) — this makes the drift a loud, named violation."""
    shapes = shapes or load_shapes()
    spec_names = set(shapes.get("graphs", {}))
    violations: list[str] = []
    for name in sorted(graphs.REGISTRY):
        if name not in spec_names:
            violations.append(
                f"{name}: REGISTRY entry has no shapes.json input spec "
                "(certification would be skipped)"
            )
        if name not in graphs.GRAPH_SOURCES:
            violations.append(
                f"{name}: REGISTRY entry has no GRAPH_SOURCES mapping "
                "(--changed would never re-select it)"
            )
    for name in sorted(AUX_REGISTRY):
        if name not in spec_names:
            violations.append(
                f"{name}: aux target has no shapes.json input spec "
                "(certification would be skipped)"
            )
        if name not in AUX_SOURCES:
            violations.append(
                f"{name}: aux target has no AUX_SOURCES mapping "
                "(--changed would never re-select it)"
            )
    return violations
