"""Pass 6 — octflow: exception-routing & degradation-lattice analyzer.

The reference design's core safety claim (ChainDB must refuse
corruption loudly, never launder it through chain selection) lives in
this tree as a hand-maintained lattice: the `node/exit.DISPOSITIONS`
failure taxonomy (REFUSE / REPAIR / RECOVER / PROPAGATE), the
`RecoverySupervisor` rung ladder, and the OCT_* kill-switch engines.
PR 13 found two real corruption-laundering bugs in that lattice by
review; octflow turns each reviewed invariant into a gate. Pure AST +
the octsync call-graph (analysis/concurrency.SyncPackage) — never
imports the modules it scans, never imports jax.

Rules
  FLOW301 unclassified-raise     a `raise SomeClass(...)` in the
                                 crash/verdict-bearing modules
                                 (storage/, tools/, protocol/,
                                 obs/recovery.py) whose class — or any
                                 statically visible ancestor — has no
                                 row in `node/exit.DISPOSITIONS`.
                                 Builtins with settled semantics
                                 (ValueError, TypeError, SystemExit …)
                                 are exempt by config; `Exception`
                                 itself deliberately is NOT.
  FLOW302 corruption-laundering  a handler reachable from the recovery
                                 ladder / the validate_chain retire
                                 loops that explicitly catches a
                                 REFUSE- or REPAIR-classified type
                                 without re-raising or consulting
                                 triage/recoverable — the exact PR 13
                                 bug class (the ladder absorbing what
                                 the open-with-repair scan owns).
  FLOW303 silent-verdict-fabrication
                                 a broad (bare/Exception/BaseException)
                                 handler on a verdict-producing path
                                 inside the crash/verdict-bearing
                                 modules whose body neither raises,
                                 calls anything, nor forwards the
                                 bound exception object — a swallowed
                                 device fault becomes a fabricated
                                 verdict. (`return st, i, e` forwards
                                 the fault as data: not a finding.)
  FLOW304 incomplete-degradation-lattice
                                 (a) the LADDERS escalation table must
                                 be closed: every rung routed by the
                                 `_run_rung` if-chain, every backend
                                 chain ending in a rung that calls the
                                 exact-host-reference terminal;
                                 (b) every device dispatch site
                                 (dispatch_prepared / run_batch /
                                 sharded_* …) must sit in a function
                                 statically reachable from a recovery
                                 protector (recover_window /
                                 recover_fold / elect_window_recovering
                                 or the ladder itself) so a device
                                 fault always has a rung to fall to.
  FLOW305 kill-switch-integrity  every documented `OCT_*=0` lever row
                                 must actually GUARD something: a dead
                                 lever (read but never consumed by any
                                 if/while/predicate test) and a
                                 false-branch re-entry (both branches
                                 of a levered `if` call the same
                                 callees) are findings.
  FLOW306 unsanctioned-broad-handler
                                 a bare `except:` or
                                 `except BaseException:` that does not
                                 re-raise, outside the sanctioned
                                 seams listed in flow_roots.json
                                 (e.g. the prefetch pump that forwards
                                 the exception object to its consumer).
  FLOW307 unpinned-redispatch    an anomaly re-dispatch site (the
                                 functions named in `redispatch_pins`)
                                 stopped calling one of its pinned
                                 exact-reference callees — the
                                 re-dispatch no longer routes into the
                                 reference set the differential suites
                                 pin.
  FLOW308 stale-suppression      an `# octflow: disable=...` comment
                                 that suppresses nothing on the
                                 current tree (mirrors OCT106/SYNC208).

Suppression grammar (same shape as octlint/octsync):

  raise OddError(x)   # octflow: disable=FLOW301  <why it is safe>
  # `# octflow: disable` (no rule list) suppresses all rules on that
  # line; the def-line suppresses the whole body;
  # `# octflow: disable-file=FLOW306` suppresses the file.

octflow is a static over-approximation. It does NOT prove anything
about dynamically installed handlers (sys.excepthook, signal handlers,
monkeypatched methods), the C++ native scanner (errors crossing that
boundary arrive as the Python classes it raises), exceptions raised by
name through a variable (`raise err`), or call edges the octsync
resolver cannot see (callbacks, getattr dispatch) — see
analysis/README.md §Pass 6 for the full caveat list.
"""

from __future__ import annotations

import ast
import dataclasses
import json
import os
import re

from .astlint import _attr_chain
from .concurrency import (
    SyncPackage,
    _call_name,
    _handler_is_silent,
    _is_broad_handler,
    _own_nodes,
)

RULES = {
    "FLOW301": "unclassified-raise",
    "FLOW302": "corruption-laundering",
    "FLOW303": "silent-verdict-fabrication",
    "FLOW304": "incomplete-degradation-lattice",
    "FLOW305": "kill-switch-integrity",
    "FLOW306": "unsanctioned-broad-handler",
    "FLOW307": "unpinned-redispatch",
    "FLOW308": "stale-suppression",
}

_RULE_LIST = r"[A-Z]+\d+(?:\s*,\s*[A-Z]+\d+)*"
_SUPPRESS_RE = re.compile(
    rf"#\s*octflow:\s*disable(?:=({_RULE_LIST}))?(?=[\s,]|$)"
)
_SUPPRESS_FILE_RE = re.compile(
    rf"#\s*octflow:\s*disable-file=({_RULE_LIST})"
)

_ROOTS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "flow_roots.json")
_BASELINE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "flow.json")


def load_roots(path: str | None = None) -> dict:
    with open(path or _ROOTS_PATH, encoding="utf-8") as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str
    line: int
    col: int
    message: str
    suppressed: bool = False
    seq: int = 0  # ordinal among same-keyed findings (see astlint)

    def format(self) -> str:
        tag = " (suppressed)" if self.suppressed else ""
        return f"{self.path}:{self.line}:{self.col}: {self.rule} " \
               f"[{RULES[self.rule]}] {self.message}{tag}"

    def key(self) -> str:
        base = f"{self.rule}::{self.path}::{self.message}"
        return base if self.seq == 0 else f"{base}::#{self.seq}"


# ---------------------------------------------------------------------------
# octflow suppressions (octsync grammar, octflow namespace)
# ---------------------------------------------------------------------------


class _Supp:
    def __init__(self, path: str, comment_lines) -> None:
        self.path = path
        self.suppress_file: set[str] = set()
        self.suppress_line: dict[int, set[str] | None] = {}
        self.decls: list[list] = []  # [line, rules|None, file_level, used]
        for i, line in comment_lines:
            m = _SUPPRESS_FILE_RE.search(line)
            if m:
                rules = {r.strip() for r in m.group(1).split(",")
                         if r.strip()}
                self.suppress_file |= rules
                self.decls.append([i, rules, True, False])
                continue
            m = _SUPPRESS_RE.search(line)
            if m:
                rules = m.group(1)
                if rules is None:
                    self.suppress_line[i] = None
                    self.decls.append([i, None, False, False])
                else:
                    rs = {r.strip() for r in rules.split(",") if r.strip()}
                    self.suppress_line[i] = rs
                    self.decls.append([i, rs, False, False])

    def _mark_used(self, line: int | None, rule: str,
                   file_level: bool) -> None:
        for d in self.decls:
            if d[2] != file_level:
                continue
            if file_level:
                if d[1] is not None and rule in d[1]:
                    d[3] = True
                    return
            elif d[0] == line and (d[1] is None or rule in d[1]):
                d[3] = True
                return

    def is_suppressed(self, rule: str, line: int,
                      def_line: int | None) -> bool:
        if rule in self.suppress_file:
            self._mark_used(None, rule, True)
            return True
        for ln in (line, def_line):
            if ln is None:
                continue
            rules = self.suppress_line.get(ln, "missing")
            if rules is None or (rules != "missing" and rule in rules):
                self._mark_used(ln, rule, False)
                return True
        return False

    def stale(self) -> list[Finding]:
        out = []
        for d in self.decls:
            if d[3]:
                continue
            line, rules, file_level, _ = d
            what = "all rules" if rules is None else ",".join(sorted(rules))
            kind = "disable-file" if file_level else "disable"
            sup = self.is_suppressed("FLOW308", line, None)
            out.append(Finding(
                "FLOW308", self.path, line, 0,
                f"`# octflow: {kind}={what}` suppresses nothing on the "
                "current tree — remove the stale comment",
                sup,
            ))
        return out


# ---------------------------------------------------------------------------
# The analysis context: octsync call graph + the failure taxonomy
# ---------------------------------------------------------------------------


def _matches(fq: str, name: str) -> bool:
    """`fq` names `name` exactly or by dotted suffix — so a config entry
    `RecoverySupervisor._run_rung` finds
    `ouroboros_consensus_tpu.obs.recovery.RecoverySupervisor._run_rung`
    on the real tree AND `flow_lattice.RecoverySupervisor._run_rung` in
    a fixture sweep."""
    return fq == name or fq.endswith("." + name)


def _in_scope(path: str, prefixes: list[str]) -> bool:
    return any(path == p or path.startswith(p) for p in prefixes)


class _Ctx:
    """Everything the rules share: the SyncPackage call graph, the
    parsed DISPOSITIONS taxonomy, the class hierarchy, per-node owner
    functions, and per-path octflow suppressions."""

    def __init__(self, pkg: SyncPackage, cfg: dict, rel_to: str):
        self.pkg = pkg
        self.cfg = cfg
        self.findings: list[Finding] = []
        # octflow suppressions ride the module's one-shot comment scan
        self.supp: dict[str, _Supp] = {}
        for model in pkg.modules.values():
            self.supp[model.modname] = _Supp(model.path,
                                             model.comment_lines)
        # fq -> _Func index + node-id -> owning _Func map; the node
        # lists are walked ONCE here and cached — every checker
        # re-iterates these lists instead of re-walking the AST
        self.funcs: dict[str, object] = {}
        self.owner: dict[int, object] = {}
        self._own: dict[int, list] = {}
        self._mod_nodes: dict[str, list] = {}
        for model in pkg.modules.values():
            for info in model.functions.values():
                fq = f"{model.modname}.{info.qualname}"
                self.funcs[fq] = info
                own = list(_own_nodes(info.node))
                self._own[id(info.node)] = own
                for sub in own:
                    self.owner[id(sub)] = info
            self._mod_nodes[model.modname] = list(ast.walk(model.tree))
        # class name -> statically visible base names (merged tree-wide;
        # an over-approximation is the safe direction for FLOW302)
        self.bases: dict[str, set[str]] = {}
        for model in pkg.modules.values():
            for node in self._mod_nodes[model.modname]:
                if isinstance(node, ast.ClassDef):
                    bs = self.bases.setdefault(node.name, set())
                    for b in node.bases:
                        chain = _attr_chain(b)
                        if chain:
                            bs.add(chain[-1])
        # the DISPOSITIONS table, parsed statically from any swept
        # module (node/exit.py on the real tree)
        self.dispo: dict[str, str] = {}
        table = cfg.get("dispositions_table", "DISPOSITIONS")
        for model in pkg.modules.values():
            for stmt in model.tree.body:
                tgt = None
                if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                        and isinstance(stmt.targets[0], ast.Name):
                    tgt = stmt.targets[0].id
                elif isinstance(stmt, ast.AnnAssign) \
                        and isinstance(stmt.target, ast.Name):
                    tgt = stmt.target.id
                if tgt != table or not isinstance(
                        getattr(stmt, "value", None), ast.Dict):
                    continue
                for k, v in zip(stmt.value.keys, stmt.value.values):
                    if not (isinstance(k, ast.Constant)
                            and isinstance(k.value, str)):
                        continue
                    if isinstance(v, ast.Attribute):
                        self.dispo[k.value] = v.attr.lower()
                    elif isinstance(v, ast.Constant) \
                            and isinstance(v.value, str):
                        self.dispo[k.value] = v.value.lower()

    # -- taxonomy ------------------------------------------------------------

    def disposition_of(self, name: str) -> str | None:
        """The class's own row, else the nearest classified ancestor in
        the statically visible hierarchy (BFS — the static analog of
        triage()'s MRO walk)."""
        seen, frontier = set(), [name]
        while frontier:
            nxt = []
            for n in frontier:
                if n in seen:
                    continue
                seen.add(n)
                d = self.dispo.get(n)
                if d is not None:
                    return d
                nxt.extend(self.bases.get(n, ()))
            frontier = nxt
        return None

    # -- plumbing ------------------------------------------------------------

    def owner_of(self, node: ast.AST):
        return self.owner.get(id(node))

    def own(self, info) -> list:
        """Cached `_own_nodes(info.node)` — the function body excluding
        nested def/class bodies."""
        cached = self._own.get(id(info.node))
        if cached is None:
            cached = list(_own_nodes(info.node))
            self._own[id(info.node)] = cached
        return cached

    def walk_module(self, model) -> list:
        """Cached `ast.walk(model.tree)`."""
        cached = self._mod_nodes.get(model.modname)
        if cached is None:
            cached = list(ast.walk(model.tree))
            self._mod_nodes[model.modname] = cached
        return cached

    def fq(self, model, info) -> str:
        return f"{model.modname}.{info.qualname}" if info is not None \
            else f"{model.modname}.<module>"

    def emit(self, rule: str, model, node, message: str) -> None:
        line = getattr(node, "lineno", 0)
        col = getattr(node, "col_offset", 0)
        info = self.owner.get(id(node))
        def_line = info.node.lineno if info is not None else None
        sup = self.supp[model.modname].is_suppressed(rule, line, def_line)
        self.findings.append(
            Finding(rule, model.path, line, col, message, sup))

    def closure(self, seed_names: list[str]) -> set[str]:
        """fq names of every function reachable from functions matching
        `seed_names`, through resolved call edges + lexical nesting."""
        out: set[str] = set()
        work = []
        for fq, info in self.funcs.items():
            if any(_matches(fq, s) for s in seed_names):
                out.add(fq)
                work.append(info)
        while work:
            info = work.pop()
            model = self.pkg.modules[info.module]
            nxt = list(info.calls)
            nxt.extend(model.functions[qn] for qn in info.children)
            for t in nxt:
                tfq = f"{t.module}.{t.qualname}"
                if tfq not in out:
                    out.add(tfq)
                    work.append(t)
        return out


# ---------------------------------------------------------------------------
# FLOW301 — unclassified raise sites in the crash/verdict-bearing plane
# ---------------------------------------------------------------------------


def _raise_class(node: ast.Raise) -> str | None:
    """`raise X(...)` / `raise mod.X(...)` -> "X"; bare re-raise and
    `raise err` (a variable — class unknowable statically) -> None."""
    if not isinstance(node.exc, ast.Call):
        return None
    chain = _attr_chain(node.exc.func)
    if not chain:
        return None
    name = chain[-1]
    return name if name[:1].isupper() else None


def _check_raises(ctx: _Ctx) -> None:
    scope = ctx.cfg.get("raise_scope", [])
    exempt = set(ctx.cfg.get("builtin_exempt", []))
    for model in ctx.pkg.modules.values():
        if not _in_scope(model.path, scope):
            continue
        for node in ctx.walk_module(model):
            if not isinstance(node, ast.Raise):
                continue
            name = _raise_class(node)
            if name is None or name in exempt:
                continue
            if ctx.disposition_of(name) is not None:
                continue
            ctx.emit(
                "FLOW301", model, node,
                f"`raise {name}(...)` in a crash/verdict-bearing module "
                f"but `{name}` (and every visible ancestor) has no "
                "DISPOSITIONS row — classify it in node/exit.py "
                "(REFUSE/REPAIR/RECOVER/PROPAGATE) so triage() and the "
                "recovery ladder route it consciously",
            )


# ---------------------------------------------------------------------------
# FLOW302 / FLOW303 — handlers on the recovery + verdict planes
# ---------------------------------------------------------------------------


def _handler_names(h: ast.ExceptHandler) -> list[str]:
    if h.type is None:
        return []
    elts = list(h.type.elts) if isinstance(h.type, ast.Tuple) else [h.type]
    out = []
    for e in elts:
        chain = _attr_chain(e)
        if chain:
            out.append(chain[-1])
    return out


def _handler_reraises(h: ast.ExceptHandler) -> bool:
    return any(isinstance(s, ast.Raise) for st in h.body
               for s in ast.walk(st))


def _handler_triages(h: ast.ExceptHandler) -> bool:
    for st in h.body:
        for s in ast.walk(st):
            if isinstance(s, ast.Call) and \
                    _call_name(s) in ("triage", "recoverable"):
                return True
    return False


def _handler_forwards(h: ast.ExceptHandler) -> bool:
    """`except X as e:` whose body USES `e` (returns it as a verdict
    tuple, records it, wraps it) forwards the fault instead of
    swallowing it — the PBft host fold's `return st, i, e` idiom."""
    if h.name is None:
        return False
    return any(isinstance(s, ast.Name) and s.id == h.name
               for st in h.body for s in ast.walk(st))


def _check_handlers(ctx: _Ctx) -> None:
    ladder = set(ctx.closure(ctx.cfg.get("ladder", {}).get("roots", [])))
    verdict = set(ctx.closure(ctx.cfg.get("verdict_roots", [])))
    scope = ctx.cfg.get("raise_scope", [])
    sanctioned = ctx.cfg.get("sanctioned_broad", [])
    for model in ctx.pkg.modules.values():
        for node in ctx.walk_module(model):
            if not isinstance(node, ast.ExceptHandler):
                continue
            info = ctx.owner_of(node)
            fq = ctx.fq(model, info)
            # FLOW302: the ladder explicitly absorbing REFUSE/REPAIR
            if fq in ladder and not _handler_reraises(node) \
                    and not _handler_triages(node):
                for name in _handler_names(node):
                    d = ctx.disposition_of(name)
                    if d in ("refuse", "repair"):
                        ctx.emit(
                            "FLOW302", model, node,
                            f"handler on the recovery/retire plane "
                            f"(`{fq}`) catches `{name}` — a "
                            f"{d.upper()}-classified type — without "
                            "re-raising or consulting triage(): the "
                            "ladder would launder what the "
                            f"{d}-owner must see (PR 13 bug class)",
                        )
            # FLOW303: silent broad handler on a verdict path, within
            # the crash/verdict-bearing module scope (observability
            # helpers deep in the closure are not verdict producers)
            if fq in verdict and _in_scope(model.path, scope) \
                    and _is_broad_handler(node) \
                    and _handler_is_silent(node) \
                    and not _handler_forwards(node):
                ctx.emit(
                    "FLOW303", model, node,
                    f"broad handler in `{fq}` on a verdict-producing "
                    "path neither raises nor calls anything — a "
                    "swallowed fault here fabricates a verdict; "
                    "re-raise, or route through the recovery ladder",
                )
            # FLOW306: bare / BaseException outside sanctioned seams
            bare = node.type is None
            base_exc = any(n == "BaseException"
                           for n in _handler_names(node))
            if (bare or base_exc) and not _handler_reraises(node):
                if info is not None and any(
                        _matches(fq, s) for s in sanctioned):
                    continue
                what = "bare `except:`" if bare \
                    else "`except BaseException:`"
                ctx.emit(
                    "FLOW306", model, node,
                    f"{what} in `{fq}` does not re-raise and is not a "
                    "sanctioned seam (flow_roots.json "
                    "`sanctioned_broad`) — it can absorb "
                    "KeyboardInterrupt/SystemExit and mask shutdown",
                )


# ---------------------------------------------------------------------------
# FLOW304 — the degradation lattice must be closed
# ---------------------------------------------------------------------------


def _parse_ladder_table(model, table_name: str) -> dict[str, list[str]]:
    out: dict[str, list[str]] = {}
    for stmt in model.tree.body:
        tgt = None
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                and isinstance(stmt.targets[0], ast.Name):
            tgt = stmt.targets[0].id
        elif isinstance(stmt, ast.AnnAssign) \
                and isinstance(stmt.target, ast.Name):
            tgt = stmt.target.id
        if tgt != table_name or not isinstance(
                getattr(stmt, "value", None), ast.Dict):
            continue
        for k, v in zip(stmt.value.keys, stmt.value.values):
            if not (isinstance(k, ast.Constant)
                    and isinstance(k.value, str)):
                continue
            if isinstance(v, (ast.Tuple, ast.List)):
                rungs = [e.value for e in v.elts
                         if isinstance(e, ast.Constant)
                         and isinstance(e.value, str)]
                out[k.value] = rungs
    return out


def _parse_router(info) -> dict[str, set[str]]:
    """The `_run_rung` if-chain: rung-name constant -> the call names in
    that branch (the rung's re-validation route)."""
    out: dict[str, set[str]] = {}
    for node in _own_nodes(info.node):
        if not isinstance(node, ast.If):
            continue
        t = node.test
        if not (isinstance(t, ast.Compare) and len(t.comparators) == 1):
            continue
        const = None
        for side in (t.left, t.comparators[0]):
            if isinstance(side, ast.Constant) and isinstance(side.value,
                                                             str):
                const = side.value
        if const is None:
            continue
        calls = {
            _call_name(s)
            for st in node.body for s in ast.walk(st)
            if isinstance(s, ast.Call) and _call_name(s)
        }
        out.setdefault(const, set()).update(calls)
    return out


def _check_lattice(ctx: _Ctx) -> list[str]:
    """(a) LADDERS wellformedness. Returns the rung-edge inventory."""
    spec = ctx.cfg.get("ladder", {})
    edges: list[str] = []
    lad_model = None
    for model in ctx.pkg.modules.values():
        if _matches(model.modname, spec.get("module", "")):
            lad_model = model
            break
    if lad_model is None:
        return edges
    table = _parse_ladder_table(lad_model, spec.get("table", "LADDERS"))
    router_info = None
    for fq, info in ctx.funcs.items():
        if info.module == lad_model.modname and \
                _matches(fq, spec.get("router", "")):
            router_info = info
            break
    routes = _parse_router(router_info) if router_info is not None else {}
    terminal = spec.get("terminal", "")
    anchor = router_info.node if router_info is not None \
        else lad_model.tree
    for backend, rungs in sorted(table.items()):
        for a, b in zip(rungs, rungs[1:]):
            edges.append(f"{backend}:{a}->{b}")
        for rung in rungs:
            if rung not in routes:
                ctx.emit(
                    "FLOW304", lad_model, anchor,
                    f"LADDERS[{backend!r}] names rung `{rung}` but the "
                    f"router `{spec.get('router')}` has no branch for "
                    "it — the escalation would die in ValueError "
                    "instead of degrading",
                )
        if not rungs or terminal not in routes.get(rungs[-1], set()):
            ctx.emit(
                "FLOW304", lad_model, anchor,
                f"LADDERS[{backend!r}] does not end in a rung that "
                f"routes to the exact-host-reference terminal "
                f"`{terminal}` — the `{backend}` chain has no floor "
                "that cannot fail for device reasons",
            )
    for rung, calls in sorted(routes.items()):
        for c in sorted(calls):
            edges.append(f"{rung}=>{c}")
    return sorted(set(edges))


def _check_dispatch_coverage(ctx: _Ctx) -> None:
    """(b) every device dispatch site reachable from a protector."""
    disp = ctx.cfg.get("dispatch", {})
    names = set(disp.get("functions", []))
    protectors = set(disp.get("protectors", []))
    exclude = disp.get("exclude", [])
    spec = ctx.cfg.get("ladder", {})
    # P: protector callers + the protectors themselves + the ladder
    seeds = []
    for fq, info in ctx.funcs.items():
        bare = fq.rsplit(".", 1)[-1]
        if bare in protectors:
            seeds.append(fq)
            continue
        for sub in ctx.own(info):
            if isinstance(sub, ast.Call) and _call_name(sub) in protectors:
                seeds.append(fq)
                break
    seeds.extend(spec.get("roots", []))
    covered = ctx.closure(seeds)
    for model in ctx.pkg.modules.values():
        if _in_scope(model.path, exclude):
            continue
        for node in ctx.walk_module(model):
            if not (isinstance(node, ast.Call)
                    and _call_name(node) in names):
                continue
            info = ctx.owner_of(node)
            fq = ctx.fq(model, info)
            if info is not None and fq in covered:
                continue
            ctx.emit(
                "FLOW304", model, node,
                f"device dispatch `{_call_name(node)}` in `{fq}` is "
                "not reachable from any recovery protector "
                f"({'/'.join(sorted(protectors))}) or the ladder — a "
                "device fault here has no rung to fall to and no "
                "exact-host-reference floor",
            )


# ---------------------------------------------------------------------------
# FLOW305 — kill-switch integrity
# ---------------------------------------------------------------------------


def _env_attr(node: ast.AST) -> bool:
    if isinstance(node, ast.Attribute) and node.attr == "environ":
        return isinstance(node.value, ast.Name) and node.value.id == "os"
    return isinstance(node, ast.Name) and node.id == "environ"


def _reads_in(node: ast.AST, consts: dict[str, str],
              levers: set[str]) -> set[str]:
    """Lever names read anywhere inside `node` (the envlevers stdlib
    seams, constant-aware through module/function string consts)."""
    def resolve(n):
        if isinstance(n, ast.Constant) and isinstance(n.value, str):
            return n.value
        if isinstance(n, ast.Name):
            return consts.get(n.id)
        return None

    out: set[str] = set()

    def note(n):
        name = resolve(n)
        if name in levers:
            out.add(name)

    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            fn = sub.func
            if isinstance(fn, ast.Attribute):
                if fn.attr in ("get", "pop") and _env_attr(fn.value) \
                        and sub.args:
                    note(sub.args[0])
                elif fn.attr == "getenv" \
                        and isinstance(fn.value, ast.Name) \
                        and fn.value.id == "os" and sub.args:
                    note(sub.args[0])
        elif isinstance(sub, ast.Subscript):
            if _env_attr(sub.value) and isinstance(sub.ctx, ast.Load):
                note(sub.slice)
        elif isinstance(sub, ast.Compare):
            if len(sub.ops) == 1 \
                    and isinstance(sub.ops[0], (ast.In, ast.NotIn)) \
                    and _env_attr(sub.comparators[0]):
                note(sub.left)
    return out


def _kill_switches(ctx: _Ctx, rel_to: str) -> list[str]:
    """The `=0` rows of the obs/README "## Levers" table (or the
    `kill_switches` config override in fixture sweeps)."""
    override = ctx.cfg.get("kill_switches")
    if override is not None:
        return sorted(override)
    from .envlevers import kill_switch_levers
    readme = os.path.join(rel_to, "ouroboros_consensus_tpu", "obs",
                          "README.md")
    if not os.path.exists(readme):
        return []
    return sorted(kill_switch_levers(readme))


def _check_levers(ctx: _Ctx, rel_to: str) -> list[str]:
    levers = set(_kill_switches(ctx, rel_to))
    if not levers:
        return []
    read_sites: dict[str, list] = {L: [] for L in levers}
    guards: dict[str, int] = {L: 0 for L in levers}
    # phase 1: per-function/module-level units + who reads what (a
    # function that reads L is a predicate-for-L: `if enabled():`
    # anywhere then counts as a guard on L)
    pred_bare: dict[str, set[str]] = {L: set() for L in levers}
    units = []  # (model, info|None, own_nodes, consts)
    for model in ctx.pkg.modules.values():
        consts = dict(model.str_consts)
        for info in model.functions.values():
            units.append((model, info, ctx.own(info), consts))
        top = [n for n in ctx.walk_module(model)
               if id(n) not in ctx.owner]
        units.append((model, None, top, consts))
    for model, info, nodes, consts in units:
        for sub in nodes:
            if not isinstance(sub, (ast.Call, ast.Subscript,
                                    ast.Compare)):
                continue
            for L in _reads_in(sub, consts, levers):
                read_sites[L].append((model.path, sub.lineno, model,
                                      sub))
                if info is not None:
                    pred_bare[L].add(info.qualname.rsplit(".", 1)[-1])

    def levers_of(expr: ast.AST, consts: dict,
                  env: dict[str, set[str]]) -> set[str]:
        """Levers an expression is derived from: direct env reads,
        lever-derived names (`PACKED_STAGE and not fused`), and
        predicate calls (`columnar = _columnar_enabled()`)."""
        out = set(_reads_in(expr, consts, levers))
        for t in ast.walk(expr):
            if isinstance(t, ast.Name) and t.id in env:
                out |= env[t.id]
            elif isinstance(t, ast.Call):
                cn = _call_name(t)
                for L in levers:
                    if cn in pred_bare[L]:
                        out.add(L)
        return out

    # phase 2: module-level lever-derived names (`PACKED_STAGE = ...`)
    mod_vars: dict[str, dict[str, set[str]]] = {}
    for model in ctx.pkg.modules.values():
        mv: dict[str, set[str]] = {}
        for stmt in model.tree.body:
            if isinstance(stmt, ast.Assign) \
                    and not isinstance(stmt.value, ast.Constant):
                ls = levers_of(stmt.value, model.str_consts, mv)
                if ls:
                    for t in stmt.targets:
                        if isinstance(t, ast.Name):
                            mv[t.id] = set(ls)
        mod_vars[model.modname] = mv
    # phase 3: guard sites — If/While/IfExp tests consuming a lever
    # read, a lever-derived local/module name, or a predicate call
    for model, info, nodes, consts in units:
        lever_vars: dict[str, set[str]] = dict(
            mod_vars.get(model.modname, {}))
        for sub in nodes:
            if isinstance(sub, ast.Assign) \
                    and not isinstance(sub.value, ast.Constant):
                ls = levers_of(sub.value, consts, lever_vars)
                for t in sub.targets:
                    if isinstance(t, ast.Name) and ls:
                        lever_vars[t.id] = set(ls)
        for sub in nodes:
            if not isinstance(sub, (ast.If, ast.While, ast.IfExp)):
                continue
            hit = levers_of(sub.test, consts, lever_vars)
            if not hit:
                continue
            for L in hit:
                guards[L] += 1
            if isinstance(sub, ast.If) and sub.orelse:
                body_calls = {
                    _call_name(s) for st in sub.body
                    for s in ast.walk(st)
                    if isinstance(s, ast.Call) and _call_name(s)}
                else_calls = {
                    _call_name(s) for st in sub.orelse
                    for s in ast.walk(st)
                    if isinstance(s, ast.Call) and _call_name(s)}
                if body_calls and body_calls == else_calls:
                    for L in sorted(hit):
                        ctx.emit(
                            "FLOW305", model, sub,
                            f"kill-switch `{L}` gates branches with "
                            "identical callees "
                            f"({', '.join(sorted(body_calls))}) — the "
                            "false branch re-enters the levered "
                            "implementation, so `=0` changes nothing",
                        )
    for L in sorted(levers):
        if guards[L]:
            continue
        sites = sorted(read_sites[L], key=lambda s: (s[0], s[1]))
        msg = (f"documented kill-switch `{L}` never guards a branch — "
               "no if/while/predicate test consumes it (dead lever: "
               "operators set `=0` and silently get nothing)")
        if sites:
            _, _, model, node = sites[0]
            ctx.emit("FLOW305", model, node, msg)
        else:
            ctx.findings.append(Finding(
                "FLOW305", "ouroboros_consensus_tpu/obs/README.md", 0, 0,
                msg + " — and nothing under the swept roots reads it",
            ))
    return [f"{L}:guards={guards[L]}" for L in sorted(levers)]


# ---------------------------------------------------------------------------
# FLOW307 — pinned exact-reference re-dispatch routes
# ---------------------------------------------------------------------------


def _check_redispatch(ctx: _Ctx) -> None:
    pins: dict[str, list[str]] = ctx.cfg.get("redispatch_pins", {})
    for pin, required in sorted(pins.items()):
        # only when the pin's module is part of this sweep (partial
        # `--paths` sweeps must not fabricate missing-function
        # findings); longest modname wins so `pkg.protocol.tpraos.X`
        # anchors to the tpraos module, not the package __init__
        owner_model = None
        for model in ctx.pkg.modules.values():
            if pin == model.modname \
                    or pin.startswith(model.modname + "."):
                if owner_model is None or \
                        len(model.modname) > len(owner_model.modname):
                    owner_model = model
        if owner_model is None:
            continue
        matched = [info for fq, info in ctx.funcs.items()
                   if _matches(fq, pin)]
        if not matched:
            ctx.emit(
                "FLOW307", owner_model, owner_model.tree,
                f"redispatch pin `{pin}` names a function that no "
                "longer exists — re-route the pin or restore the "
                "reference seam",
            )
            continue
        for info in matched:
            called = {
                _call_name(s) for s in ctx.own(info)
                if isinstance(s, ast.Call) and _call_name(s)}
            missing = [r for r in required if r not in called]
            if missing:
                model = ctx.pkg.modules[info.module]
                ctx.emit(
                    "FLOW307", model, info.node,
                    f"re-dispatch site `{pin}` no longer calls its "
                    f"pinned exact-reference callee(s) "
                    f"{', '.join(missing)} — the anomaly route has "
                    "drifted off the reference set the differential "
                    "suites pin",
                )


# ---------------------------------------------------------------------------
# Inventory + sweep
# ---------------------------------------------------------------------------


def _inventory(ctx: _Ctx, rung_edges: list[str],
               levers: list[str]) -> dict:
    raises_inv = set()
    scope = ctx.cfg.get("raise_scope", [])
    handlers = set()
    for model in ctx.pkg.modules.values():
        for node in ctx.walk_module(model):
            if isinstance(node, ast.Raise) and \
                    _in_scope(model.path, scope):
                name = _raise_class(node)
                if name:
                    info = ctx.owner_of(node)
                    raises_inv.add(f"{ctx.fq(model, info)}:{name}")
            elif isinstance(node, ast.ExceptHandler):
                info = ctx.owner_of(node)
                names = _handler_names(node)
                spec = "bare" if node.type is None \
                    else "+".join(sorted(names)) if names else "dynamic"
                handlers.add(f"{ctx.fq(model, info)}:{spec}")
    return {
        "raise_sites": sorted(raises_inv),
        "handlers": sorted(handlers),
        "rung_edges": rung_edges,
        "levers": levers,
    }


@dataclasses.dataclass
class FlowReport:
    findings: list
    inventory: dict


def sweep_paths(paths: list[str], rel_to: str | None = None,
                roots_table: dict | None = None) -> FlowReport:
    rel = rel_to or os.path.dirname(os.path.abspath(paths[0]))
    cfg = roots_table or load_roots()
    pkg = SyncPackage([p for p in paths if os.path.exists(p)], rel,
                      threads=False)
    ctx = _Ctx(pkg, cfg, rel)
    _check_raises(ctx)
    _check_handlers(ctx)
    rung_edges = _check_lattice(ctx)
    _check_dispatch_coverage(ctx)
    levers = _check_levers(ctx, rel)
    _check_redispatch(ctx)
    # FLOW308 runs last: it audits which declarations the rules above
    # actually consumed
    for supp in ctx.supp.values():
        ctx.findings.extend(supp.stale())
    findings = sorted(ctx.findings, key=lambda f: (f.path, f.line, f.rule))
    counts: dict[str, int] = {}
    out: list[Finding] = []
    for f in findings:
        base = f"{f.rule}::{f.path}::{f.message}"
        n = counts.get(base, 0)
        counts[base] = n + 1
        out.append(dataclasses.replace(f, seq=n) if n else f)
    return FlowReport(out, _inventory(ctx, rung_edges, levers))


def sweep_source(source: str, name: str = "<memory>",
                 roots_table: dict | None = None) -> list[Finding]:
    """Sweep a single source string (fixture tests)."""
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, f"{name}.py")
        with open(p, "w", encoding="utf-8") as f:
            f.write(source)
        rep = sweep_paths([p], rel_to=d, roots_table=roots_table)
    return [dataclasses.replace(f, path=name) for f in rep.findings]


def default_roots(repo_root: str | None = None) -> list[str]:
    repo = repo_root or os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return [os.path.join(repo, "ouroboros_consensus_tpu"),
            os.path.join(repo, "scripts"),
            os.path.join(repo, "bench.py")]


def load_baseline(path: str | None = None) -> dict:
    with open(path or _BASELINE_PATH, encoding="utf-8") as f:
        return json.load(f)


def baseline_payload(report: FlowReport) -> dict:
    return {
        "comment": "octflow ratchet (scripts/lint.py --update-flow): "
                   "grandfathered finding keys + the line-number-free "
                   "failure-routing inventory (raise sites, handlers, "
                   "rung edges, kill-switch guard counts). Shrink-only "
                   "in normal operation.",
        "findings": sorted({f.key() for f in report.findings
                            if not f.suppressed}),
        "inventory": report.inventory,
    }


def write_baseline(report: FlowReport, path: str | None = None) -> dict:
    payload = baseline_payload(report)
    with open(path or _BASELINE_PATH, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    return payload


def check_flow(report: FlowReport, baseline: dict | None = None) \
        -> tuple[list[str], list[str]]:
    """(violations, stale_notes) vs the flow.json ratchet: a new
    unsuppressed finding or inventory drift is a violation; a baseline
    key that stopped firing is a ratchet-tightening note."""
    base = baseline if baseline is not None else load_baseline()
    known = set(base.get("findings", []))
    violations = [
        f.format() for f in report.findings
        if not f.suppressed and f.key() not in known
    ]
    pinned = base.get("inventory", {})
    for section, now in report.inventory.items():
        then = pinned.get(section, [])
        gained = sorted(set(now) - set(then))
        lost = sorted(set(then) - set(now))
        if gained or lost:
            delta = "; ".join(
                ([f"new: {', '.join(gained)}"] if gained else []) +
                ([f"gone: {', '.join(lost)}"] if lost else [])
            )
            violations.append(
                f"inventory drift in `{section}` ({delta}) — review and "
                "re-pin with scripts/lint.py --update-flow"
            )
    current = {f.key() for f in report.findings if not f.suppressed}
    stale = sorted(known - current)
    return violations, stale
