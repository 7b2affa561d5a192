"""Device resource accounting: what do the compiled programs consume?

PR 6's flight recorder measures host walls and octwall (PR 8) predicts
compile cost, but nothing accounted for what a dispatched program
actually *uses* on device — FLOPs, bytes accessed, peak HBM — even
though `jax.stages.Compiled.cost_analysis()` / `memory_analysis()`
expose exactly that without executing anything (the same compile-free
spirit as octrange/octwall, and the ledger a continuous-batching
scheduler admits work against: ROADMAP #3's serving loop will admit
windows against these per-stage HBM numbers).

Two capture paths, one vocabulary:

  runtime   `capture_stage` rides the FIRST-execute hooks that already
            exist for warmup forensics (`ops/pk/kernels._stage_call`,
            `protocol/batch._warm_timed`): an AOT executable is a
            `jax.stages.Compiled` whose analyses are free; a jit path
            re-lowers once (tracing cost only, no XLA compile) for the
            HLO cost analysis. Results land in the process-wide
            `RESOURCES` recorder, surface as `oct_stage_*` gauges in
            the default metrics registry, and are banked into the bench
            round JSON / run ledger as `device_resources`.

  static    `analysis/graphs.measure_graph` lowers (and, for the
            ratchet pins, compiles) a registered analysis graph at its
            certification tile on whatever backend is pinned — the
            numbers behind the budgets.json `device_resources` section,
            regenerated by `scripts/lint.py --update-resources` and
            checked (exit 6) by `check_device_resources`: every
            registry graph must carry a pin whose octwall
            `feature_hash` matches the
            freshly-extracted features (a stale-structure pin fails
            loudly instead of silently vouching for a program that no
            longer exists), and pinned values must stay under their
            ratchet ceilings.

What the numbers do NOT cover (COVERAGE.md §5.14): Pallas/Mosaic kernel
bodies are opaque custom calls to the HLO cost analysis (their flops
read ~0 — argument/output bytes are still real), host C++ (chunk scan,
native crypto) is invisible, and XLA:CPU memory stats stand in for HBM
when pins are regenerated off-TPU (`measured_at` records the backend).

Env lever: OCT_STAGE_RESOURCES=1 turns runtime capture on; unset (or
=0) it is off — an installed flight recorder does not turn it on:
capture makes every stage's first execute trace and lower its program a
second time, and tracing must not change what set-up costs."""

from __future__ import annotations

import os
import threading

_ENV = "OCT_STAGE_RESOURCES"

# canonical per-program resource vocabulary (every sink — gauges, bench
# JSON, ledger records, budgets.json pins — speaks exactly these keys)
RESOURCE_FIELDS = (
    "flops", "bytes_accessed", "argument_bytes", "output_bytes",
    "temp_bytes", "generated_code_bytes", "peak_hbm_bytes",
)

# pin keys the ratchet ceilings may bound ("<key>_max" in budgets.json)
CEILING_KEYS = ("flops", "bytes_accessed", "peak_hbm_bytes")


def enabled() -> bool:
    """Runtime-capture lever: on only where OCT_STAGE_RESOURCES says so
    (a re-lower per stage is not what installing the recorder asks
    for)."""
    return os.environ.get(_ENV, "0") not in ("0", "")


# ---------------------------------------------------------------------------
# Extraction from jax.stages objects
# ---------------------------------------------------------------------------


def _cost_dict(ca) -> dict | None:
    """Normalize a cost_analysis() result: recent jax returns one dict
    from Lowered and a per-partition list from Compiled."""
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    return ca if isinstance(ca, dict) else None


def from_cost_analysis(ca) -> dict:
    """-> {"flops", "bytes_accessed"} from an HLO cost analysis (keys
    the XLA analysis spells 'flops' / 'bytes accessed')."""
    d = _cost_dict(ca)
    if not d:
        return {}
    out = {}
    if "flops" in d:
        out["flops"] = int(d["flops"])
    if "bytes accessed" in d:
        out["bytes_accessed"] = int(d["bytes accessed"])
    return out


def from_memory_analysis(ma) -> dict:
    """-> HBM byte fields from a CompiledMemoryStats (missing attrs on
    older runtimes simply drop out). peak_hbm_bytes is the executable's
    live-at-once footprint: arguments + outputs + temps + generated
    code (the PJRT stats expose the parts, not the sum)."""
    if ma is None:
        return {}
    out = {}
    for field, attr in (
        ("argument_bytes", "argument_size_in_bytes"),
        ("output_bytes", "output_size_in_bytes"),
        ("temp_bytes", "temp_size_in_bytes"),
        ("generated_code_bytes", "generated_code_size_in_bytes"),
    ):
        v = getattr(ma, attr, None)
        if v is not None:
            out[field] = int(v)
    if out:
        out["peak_hbm_bytes"] = (
            out.get("argument_bytes", 0) + out.get("output_bytes", 0)
            + out.get("temp_bytes", 0) + out.get("generated_code_bytes", 0)
        )
    return out


def from_compiled(compiled) -> dict | None:
    """Resources of an already-compiled executable (jax.stages.Compiled
    or a deserialized AOT executable) — no tracing, no compile."""
    out: dict = {}
    try:
        out.update(from_cost_analysis(compiled.cost_analysis()))
    except Exception:  # noqa: BLE001 — analyses are best-effort by contract
        pass
    try:
        out.update(from_memory_analysis(compiled.memory_analysis()))
    except Exception:  # noqa: BLE001
        pass
    return out or None


def from_lowered(lowered) -> dict | None:
    """Resources visible pre-compile (HLO cost analysis only — memory
    stats need the compiled executable)."""
    try:
        return from_cost_analysis(lowered.cost_analysis()) or None
    except Exception:  # noqa: BLE001
        return None


# ---------------------------------------------------------------------------
# Process-wide runtime recorder (the WARMUP idiom)
# ---------------------------------------------------------------------------


class StageResources:
    """Per-stage resource notes keyed (stage, lanes, depth): FIRST
    capture wins (the numbers are shape-static per key). Mirrors into
    the default metrics registry as oct_stage_* gauges."""

    def __init__(self):
        self._lock = threading.Lock()
        self.stages: dict[str, dict] = {}

    def note_stage(self, stage: str, lanes: int | None, depth: int | None,
                   res: dict, via: str = "jit",
                   capture_s: float | None = None) -> bool:
        """Record one stage program's resources. `stage` already carries
        the caller's bucket qualifier (e.g. "ed@b8192"); lanes/depth
        ride as fields so the ledger can group by shape; `capture_s` is
        what the capture itself cost (accountable telemetry). Returns
        True when this was the first note for the key."""
        key = f"{stage}|{lanes}|{depth}"
        row = {k: int(v) for k, v in res.items() if k in RESOURCE_FIELDS}
        if not row:
            return False
        row["via"] = via
        if lanes is not None:
            row["lanes"] = int(lanes)
        if depth is not None:
            row["depth"] = int(depth)
        if capture_s is not None:
            row["capture_s"] = round(float(capture_s), 3)
        with self._lock:
            if key in self.stages:
                return False
            self.stages[key] = row
        self._mirror(stage, row)
        return True

    def _mirror(self, stage: str, row: dict) -> None:
        """oct_stage_* gauges in the default registry (labels carry the
        stage name; HBM parts share one family under a kind label)."""
        try:
            from .registry import default_registry

            register_families(default_registry(), stage, row)
        except Exception:  # noqa: BLE001 — telemetry must not break dispatch
            pass

    def report(self) -> dict:
        """The `device_resources` block (bench round JSON / ledger)."""
        with self._lock:
            return {k: dict(v) for k, v in self.stages.items()}

    def reset(self) -> None:
        with self._lock:
            self.stages.clear()


RESOURCES = StageResources()


def register_families(reg, stage: str | None = None,
                      row: dict | None = None):
    """Register (and, when a row is given, set) the oct_stage_* gauge
    families. Split out so the metric-name drift gate can enumerate the
    families without having captured anything."""
    flops = reg.gauge(
        "oct_stage_flops",
        "optimized-HLO flops of one dispatched stage program", ("stage",)
    )
    byts = reg.gauge(
        "oct_stage_bytes_accessed",
        "HLO bytes accessed by one dispatched stage program", ("stage",)
    )
    hbm = reg.gauge(
        "oct_stage_hbm_bytes",
        "per-stage HBM footprint by kind "
        "(argument|output|temp|peak)", ("stage", "kind"),
    )
    if row is not None and stage is not None:
        if "flops" in row:
            flops.labels(stage=stage).set(row["flops"])
        if "bytes_accessed" in row:
            byts.labels(stage=stage).set(row["bytes_accessed"])
        for kind, key in (("argument", "argument_bytes"),
                          ("output", "output_bytes"),
                          ("temp", "temp_bytes"),
                          ("peak", "peak_hbm_bytes")):
            if key in row:
                hbm.labels(stage=stage, kind=kind).set(row[key])
    return flops, byts, hbm


# captures slower than this leave a warmup-report breadcrumb, so the
# forensics can explain every second of a warmup wall, including ours
CAPTURE_NOTE_THRESHOLD_S = 1.0


def capture_stage(stage: str, fn, args, *, lanes: int | None = None,
                  depth: int | None = None, via: str = "jit") -> bool:
    """Best-effort resource capture on a stage's FIRST execute (the
    warmup hooks guarantee once-per-key). `fn` is the jitted callable
    on the jit path (one re-lower = a trace, no XLA compile — but the
    trace itself costs seconds on the big composed twins, hence the
    self-accounting below) or the loaded
    executable on the AOT path (analyses are free). Never raises —
    resource accounting must not break dispatch."""
    if not enabled():
        return False
    try:
        import time

        # capability, not via-string: the store/write-back paths hand
        # an already-compiled executable under via "xla-aot"/"jit" too
        # (round 10) — its analyses are free; only a jitted callable
        # (has .lower) needs the re-lower
        t0 = time.monotonic()
        if hasattr(fn, "lower"):
            res = from_lowered(fn.lower(*args))
        else:
            res = from_compiled(fn)
        capture_s = time.monotonic() - t0
        if not res:
            return False
        # the capture's own cost is accountable forensics: it rides the
        # row, and slow captures leave a warmup breadcrumb so a wall
        # burned here is attributed, not mysterious
        if capture_s > CAPTURE_NOTE_THRESHOLD_S:
            from .warmup import WARMUP

            WARMUP.note(
                f"{stage} resource capture took {capture_s:.1f}s"
            )
        return RESOURCES.note_stage(
            stage, lanes, depth, res, via=via, capture_s=capture_s,
        )
    except Exception:  # noqa: BLE001 — fail-soft by contract
        return False


# ---------------------------------------------------------------------------
# Static measurement + the budgets.json "device_resources" ratchet
# ---------------------------------------------------------------------------


def pin_payload(measurements: dict[str, dict],
                feature_hashes: dict[str, str]) -> dict:
    """budgets.json "device_resources"."graphs": per graph the measured
    resource fields + the octwall feature hash of the structure they
    were measured against (the staleness key)."""
    out: dict = {}
    for name, res in measurements.items():
        pin = {k: int(res[k]) for k in RESOURCE_FIELDS if k in res}
        pin["at_lanes"] = res.get("at_lanes")
        pin["source"] = res.get("source", "compiled")
        pin["feature_hash"] = feature_hashes.get(name)
        out[name] = pin
    return out


def check_device_resources(cost_features, budgets: dict) -> list[str]:
    """Sixth ratcheted metric (scripts/lint.py exit 6). Cheap by
    construction — dict compares against the cost features the lint
    gate already extracted; the expensive compiles only ever run under
    --update-resources:

      * every registry graph in `cost_features` must carry a pin;
      * the pin's feature_hash must match the freshly-extracted octwall
        hash — a structural edit that outran --update-resources fails
        loudly instead of leaving pins that describe a program that no
        longer exists;
      * pinned values must stay under the "ceilings" section's
        "<key>_max" bounds (the ratchet: --update-resources refreshes
        pins but never relaxes an existing ceiling)."""
    sec = budgets.get("device_resources", {})
    pins = sec.get("graphs", {})
    ceilings = sec.get("ceilings", {})
    violations: list[str] = []
    for f in cost_features:
        pin = pins.get(f.name)
        if pin is None:
            violations.append(
                f"{f.name}: no device_resources pin in budgets.json "
                "(run scripts/lint.py --update-resources)"
            )
            continue
        if pin.get("feature_hash") != f.hash():
            violations.append(
                f"{f.name}: jaxpr structure drifted from its "
                "device_resources pin — the pinned FLOP/byte/HBM numbers "
                "describe a program that no longer exists (run "
                "scripts/lint.py --update-resources)"
            )
            continue
        ceil = ceilings.get(f.name, {})
        for key in CEILING_KEYS:
            cmax = ceil.get(f"{key}_max")
            if cmax is None or key not in pin:
                continue
            if int(pin[key]) > int(cmax):
                violations.append(
                    f"{f.name}: pinned {key} = {int(pin[key])} exceeds "
                    f"ceiling {int(cmax)} (raise the budgets.json "
                    "device_resources ceiling deliberately or shrink "
                    "the program)"
                )
    return violations


# headroom for freshly-created ceilings: resource numbers are
# shape-static, so 1.2x absorbs only XLA-version drift, not regressions
CEILING_HEADROOM = 1.2


def update_budgets_section(budgets: dict, measurements: dict[str, dict],
                           feature_hashes: dict[str, str],
                           measured_at: str = "") -> dict:
    """Rewrite budgets["device_resources"]: pins are replaced wholesale;
    ceilings are created at CEILING_HEADROOM x for graphs missing one
    and PRESERVED otherwise (that asymmetry is the ratchet — a grown
    program trips its old ceiling until someone raises it on purpose)."""
    sec = budgets.setdefault("device_resources", {})
    sec["comment"] = (
        "Sixth ratcheted metric (obs/resources.py): per-graph device "
        "resource pins — optimized-HLO flops / bytes accessed + "
        "compiled memory stats (peak = argument+output+temp+generated) "
        "at each graph's certification tile, keyed by the octwall "
        "feature_hash so a structural edit that outruns "
        "--update-resources fails scripts/lint.py (exit 6) loudly. "
        "Ceilings ratchet: --update-resources refreshes pins and only "
        "CREATES missing ceilings (at ~1.2x); raising one is a "
        "deliberate reviewed edit. Pallas/Mosaic bodies are opaque to "
        "the HLO analysis and host C++ is invisible — see COVERAGE.md "
        "5.14."
    )
    if measured_at:
        sec["measured_at"] = measured_at
    sec["graphs"] = pin_payload(measurements, feature_hashes)
    ceilings = sec.setdefault("ceilings", {})
    for name, pin in sec["graphs"].items():
        ceil = ceilings.setdefault(name, {})
        for key in CEILING_KEYS:
            if key in pin and f"{key}_max" not in ceil:
                ceil[f"{key}_max"] = int(pin[key] * CEILING_HEADROOM)
    # drop ceilings for graphs that left the registry
    for name in list(ceilings):
        if name not in sec["graphs"]:
            del ceilings[name]
    return budgets


def measured_at_string() -> str:
    import jax

    try:
        plat = jax.devices()[0].platform
    except Exception:  # noqa: BLE001
        plat = "unknown"
    return f"jax {jax.__version__}, {plat} backend, certification tiles"


def resources_payload(names, budgets: dict, cost_features) -> dict:
    """The `resources` CLI subcommand's JSON body: per graph the pin,
    the fresh feature hash, and drift/ceiling status (sorted-keys
    stable; no lowering or compiling happens here)."""
    feats = {f.name: f for f in cost_features}
    sec = budgets.get("device_resources", {})
    pins = sec.get("graphs", {})
    ceilings = sec.get("ceilings", {})
    rows: dict = {}
    for name in names:
        pin = pins.get(name)
        f = feats.get(name)
        rows[name] = {
            "pin": pin,
            "ceilings": ceilings.get(name),
            "feature_hash": f.hash() if f is not None else None,
            "pinned_hash": pin.get("feature_hash") if pin else None,
            "fresh": bool(
                pin and f is not None
                and pin.get("feature_hash") == f.hash()
            ),
        }
    return rows
