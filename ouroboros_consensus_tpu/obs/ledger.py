"""The run ledger: append-only provenance for every replay.

Five bench rounds in, the single biggest fact about the trajectory —
r01 banked a device number, r02–r05 banked nothing — was only
discoverable by hand-diffing `BENCH_r0*.json`; WHAT changed between
rounds (git state, PJRT build, kill-switch flips) was archaeology. The
ledger turns it into a query: every `bench.py` run, `bench_suite`
config and `profile_replay` invocation appends ONE provenance-complete
JSONL record, so "what was different when r01 banked?" is a
`read_runs()` filter, and `scripts/perf_report.py` folds the ledger
into the cross-round trajectory report.

Layout: `<repo>/.oct_ledger/runs-YYYYMMDD.jsonl`, one JSON object per
line, keyed by day so a long-lived box rotates naturally and a day's
runs diff cleanly. Append-only by construction — records are never
rewritten; a corrupt line (a crash mid-append) is skipped and counted
by `read_runs`, never fatal.

Record schema (SCHEMA_VERSION = 1, validated by `validate_record` and
the tier-1 schema test):

    schema        int     — SCHEMA_VERSION
    kind          str     — "bench" | "bench_suite" | "profile_replay"
                            | "replay" | ...
    ts_unix       float   — epoch seconds at append
    ts_iso        str     — UTC ISO-8601 twin (human grep)
    git           dict    — {"rev": str|None, "dirty": bool|None}
    build_id      str|None— PJRT platform_version when a backend is up
    env           dict    — every OCT_* value plus JAX_PLATFORMS and
                            BENCH_* (the kill-switch state that made
                            r02–r05 archaeology)
    host          dict    — {"platform", "pid", "argv"}
    config        dict|None — chain/config shape (headers, max_batch,
                            kes_depth, ...)
    result        dict|None — the banked outcome (bench's JSON line,
                            a suite row, profile numbers)
    wall_s        float|None
    phases_s      dict|None — per-phase wall attribution
    warmup_report dict|None — the obs/warmup block
    metrics       dict|None — a MetricsRegistry snapshot
    metrics_summary dict|None
    device_resources dict|None — obs/resources.RESOURCES.report()
    extra         dict|None

Env lever: `OCT_LEDGER=<dir>` overrides the directory; `OCT_LEDGER=0`
is the kill-switch (record_run becomes a no-op returning None).
Everything is fail-soft: a read-only filesystem or a git-less checkout
degrades to partial provenance, never a crashed replay."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

_ENV = "OCT_LEDGER"

SCHEMA_VERSION = 1

# env keys banked verbatim: the OCT_* kill-switch family plus the knobs
# that shaped the run (chain scale, platform pin)
_ENV_PREFIXES = ("OCT_", "BENCH_")
_ENV_EXTRA = ("JAX_PLATFORMS",)

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_REPO, ".oct_ledger")

# optional dict-typed payload sections (None when the run had none)
_OPTIONAL_DICTS = (
    "config", "result", "phases_s", "warmup_report", "metrics",
    "metrics_summary", "device_resources", "extra",
)


def ledger_dir() -> str | None:
    """Resolved ledger directory, or None when the kill-switch is on."""
    v = os.environ.get(_ENV)
    if v == "0":
        return None
    return v or DEFAULT_DIR


def day_file(dir_: str, ts: float | None = None) -> str:
    day = time.strftime("%Y%m%d", time.gmtime(
        time.time() if ts is None else ts))
    return os.path.join(dir_, f"runs-{day}.jsonl")


# ---------------------------------------------------------------------------
# Provenance probes (each best-effort: None beats a crashed replay)
# ---------------------------------------------------------------------------


def git_provenance(repo: str | None = None) -> dict:
    """{"rev": ..., "dirty": ...} of the working tree, None/None when
    git is unavailable — the r01→r02 question ('what code was this?')
    answered at append time, not reconstructed later."""
    repo = repo or _REPO
    rev = dirty = None
    try:
        rev = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=repo, timeout=10, check=True,
        ).stdout.strip() or None
        status = subprocess.run(
            ["git", "status", "--porcelain"], capture_output=True,
            text=True, cwd=repo, timeout=10, check=True,
        ).stdout
        dirty = bool(status.strip())
    except Exception:  # noqa: BLE001 — git-less checkouts stay recordable
        pass
    return {"rev": rev, "dirty": dirty}


def runtime_build_id() -> str | None:
    """PJRT platform_version of an ALREADY-INITIALIZED backend. Never
    initializes one: a process that touches JAX holds the chip, and the
    parent bench process deliberately never touches the backend."""
    if "jax" not in sys.modules:
        return None
    try:
        from jax._src import xla_bridge

        if not getattr(xla_bridge, "_backends", None):
            return None
        import jax

        return str(jax.devices()[0].client.platform_version)
    except Exception:  # noqa: BLE001
        return None


def env_snapshot() -> dict:
    return {
        k: v for k, v in sorted(os.environ.items())
        if k.startswith(_ENV_PREFIXES) or k in _ENV_EXTRA
    }


# ---------------------------------------------------------------------------
# Record construction / validation / append
# ---------------------------------------------------------------------------


def build_record(kind: str, *, config: dict | None = None,
                 result: dict | None = None,
                 wall_s: float | None = None,
                 phases_s: dict | None = None,
                 warmup_report: dict | None = None,
                 metrics: dict | None = None,
                 metrics_summary: dict | None = None,
                 device_resources: dict | None = None,
                 build_id: str | None = None,
                 extra: dict | None = None) -> dict:
    """One provenance-complete record (not yet appended)."""
    now = time.time()
    return {
        "schema": SCHEMA_VERSION,
        "kind": str(kind),
        "ts_unix": now,
        "ts_iso": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime(now)),
        "git": git_provenance(),
        "build_id": build_id if build_id is not None else runtime_build_id(),
        "env": env_snapshot(),
        "host": {
            "platform": sys.platform,
            "pid": os.getpid(),
            "argv": list(sys.argv),
        },
        "config": config,
        "result": result,
        "wall_s": None if wall_s is None else float(wall_s),
        "phases_s": phases_s,
        "warmup_report": warmup_report,
        "metrics": metrics,
        "metrics_summary": metrics_summary,
        "device_resources": device_resources,
        "extra": extra,
    }


def validate_record(rec) -> list[str]:
    """Schema gate (tier-1 runs this over every appended record):
    returns problems, [] = well-formed."""
    errs: list[str] = []
    if not isinstance(rec, dict):
        return ["record is not an object"]
    if rec.get("schema") != SCHEMA_VERSION:
        errs.append(f"schema must be {SCHEMA_VERSION}, got "
                    f"{rec.get('schema')!r}")
    if not isinstance(rec.get("kind"), str) or not rec.get("kind"):
        errs.append("kind missing or not a non-empty string")
    if not isinstance(rec.get("ts_unix"), (int, float)):
        errs.append("ts_unix missing or not a number")
    if not isinstance(rec.get("ts_iso"), str):
        errs.append("ts_iso missing or not a string")
    git = rec.get("git")
    if not isinstance(git, dict) or "rev" not in git or "dirty" not in git:
        errs.append("git must be a dict with rev and dirty")
    if not (rec.get("build_id") is None
            or isinstance(rec.get("build_id"), str)):
        errs.append("build_id must be a string or null")
    if not isinstance(rec.get("env"), dict):
        errs.append("env missing or not a dict")
    host = rec.get("host")
    if not isinstance(host, dict) or "platform" not in host:
        errs.append("host must be a dict with platform")
    for key in _OPTIONAL_DICTS:
        v = rec.get(key)
        if v is not None and not isinstance(v, dict):
            errs.append(f"{key} must be a dict or null")
    w = rec.get("wall_s")
    if w is not None and not isinstance(w, (int, float)):
        errs.append("wall_s must be a number or null")
    try:
        json.dumps(rec, allow_nan=False)
    except (TypeError, ValueError) as e:
        errs.append(f"not strict-JSON-serializable: {e}")
    return errs


def append(rec: dict, path: str | None = None) -> str | None:
    """Append one record as one JSONL line (single write — concurrent
    appenders interleave at line granularity under O_APPEND). Returns
    the file written, or None when the ledger is disabled/unwritable
    (telemetry never breaks the run it describes)."""
    if path is None:
        dir_ = ledger_dir()
        if dir_ is None:
            return None
        path = day_file(dir_)
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        line = json.dumps(rec, sort_keys=True, allow_nan=False)
        # The ledger is an append-only JSONL journal, not a
        # rewrite-in-place document: O_APPEND keeps concurrent appenders
        # line-atomic and iter_runs tolerates a torn tail line, so
        # tmp+rename would break (not add) the durability protocol here.
        with open(path, "a", encoding="utf-8") as f:  # octsync: disable=SYNC207
            f.write(line + "\n")
        return path
    except (OSError, TypeError, ValueError):
        return None


def record_run(kind: str, **kw) -> dict | None:
    """build_record + append in one call — the one-liner every script
    uses. Returns the record (with `_path` noting where it landed) or
    None when the kill-switch is on."""
    if ledger_dir() is None:
        return None
    rec = build_record(kind, **kw)
    path = append(rec)
    if path is None:
        return None
    rec["_path"] = path
    return rec


def record_replay(kind: str, recorder=None, **kw) -> dict | None:
    """record_run with the obs state folded in automatically: the
    flight recorder's registry snapshot + latency summary, the warmup
    report, and the stage resource ledger — what profile_replay and the
    bench child bank without each caller re-plumbing obs."""
    from .resources import RESOURCES
    from .warmup import WARMUP

    if recorder is not None:
        kw.setdefault("metrics", recorder.registry.snapshot())
        kw.setdefault("metrics_summary", recorder.latency_summary())
    kw.setdefault("warmup_report", WARMUP.report())
    res = RESOURCES.report()
    if res:
        kw.setdefault("device_resources", res)
    return record_run(kind, **kw)


# ---------------------------------------------------------------------------
# Queries
# ---------------------------------------------------------------------------


def iter_runs(dir_: str | None = None):
    """Yield (record, file, lineno) over every day file, oldest day
    first; corrupt lines are skipped (never fatal)."""
    dir_ = dir_ if dir_ is not None else ledger_dir()
    if dir_ is None or not os.path.isdir(dir_):
        return
    for name in sorted(os.listdir(dir_)):
        if not (name.startswith("runs-") and name.endswith(".jsonl")):
            continue
        path = os.path.join(dir_, name)
        try:
            with open(path, encoding="utf-8") as f:
                for i, line in enumerate(f):
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        yield json.loads(line), path, i
                    except json.JSONDecodeError:
                        continue  # torn append: skip, keep reading
        except OSError:
            continue


def read_runs(dir_: str | None = None, kind: str | None = None) -> list[dict]:
    """All (optionally kind-filtered) records, append order."""
    return [rec for rec, _p, _i in iter_runs(dir_)
            if kind is None or rec.get("kind") == kind]


# ---------------------------------------------------------------------------
# CLI: `python -m ouroboros_consensus_tpu.obs.ledger tail --last N`
# ---------------------------------------------------------------------------


def _result_blurb(rec: dict) -> str:
    """One human line out of a record's banked result — "what did this
    run do" without hand-parsing JSONL."""
    res = rec.get("result") or {}
    parts = []
    if res.get("value") is not None:
        unit = res.get("unit", "")
        parts.append(f"{res['value']} {unit}".strip())
    elif res.get("rate_per_s") is not None:
        parts.append(f"{res['rate_per_s']} headers/s")
    elif res.get("ceiling_per_s") is not None:
        parts.append(f"ceiling {res['ceiling_per_s']} headers/s")
    if res.get("device_unavailable"):
        parts.append("NO-DEVICE"
                     + (f" ({res['no_device_reason']})"
                        if res.get("no_device_reason") else ""))
    if res.get("headers") is not None:
        parts.append(f"{res['headers']} headers")
    ms = rec.get("metrics_summary") or {}
    if ms.get("windows"):
        parts.append(f"{ms['windows']} windows")
    metrics = rec.get("metrics") or {}
    stalls = sum(
        int(s.get("value", 0))
        for s in (metrics.get("oct_stalls_total") or {}).get("samples", [])
    )
    if stalls:
        parts.append(f"{stalls} STALL(s)")
    shard_fams = [k for k in metrics if k.startswith("oct_shard_")]
    if shard_fams:
        shards = {
            (s.get("labels") or {}).get("shard")
            for k in shard_fams
            for s in (metrics.get(k) or {}).get("samples", [])
        }
        parts.append(f"per-shard telemetry x{len(shards - {None})}")
    return ", ".join(parts) or "(no result banked)"


def format_run(rec: dict) -> str:
    build = rec.get("build_id") or "-"
    if len(build) > 24:
        build = build[:21] + "..."
    wall = rec.get("wall_s")
    wall_s = f"{wall:.0f}s" if isinstance(wall, (int, float)) else "?"
    return (
        f"{rec.get('ts_iso', '?'):20s} {rec.get('kind', '?'):14s} "
        f"build={build:24s} wall={wall_s:6s} " + _result_blurb(rec)
    )


def main(argv: list[str] | None = None) -> int:
    """`tail --last N [--kind K] [--build-id SUBSTR] [--json]`: the
    "what did the last live session do" one-liner over read_runs."""
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m ouroboros_consensus_tpu.obs.ledger",
        description="query the append-only run ledger",
    )
    sub = ap.add_subparsers(dest="cmd", required=True)
    tail = sub.add_parser(
        "tail", help="newest runs, one line each (newest last)"
    )
    tail.add_argument("--last", type=int, default=10, metavar="N",
                      help="show the newest N runs (default 10)")
    tail.add_argument("--kind", default=None,
                      help="filter by record kind (bench / multichip / "
                           "profile_replay / ...)")
    tail.add_argument("--build-id", default=None, dest="build_id",
                      help="substring filter over the PJRT build id")
    tail.add_argument("--dir", default=None,
                      help="ledger directory (default: the repo ledger / "
                           "OCT_LEDGER)")
    tail.add_argument("--json", action="store_true",
                      help="print the full records as JSONL instead")
    args = ap.parse_args(argv)

    runs = read_runs(args.dir, kind=args.kind)
    if args.build_id is not None:
        runs = [r for r in runs if args.build_id in (r.get("build_id") or "")]
    runs = runs[-args.last:] if args.last > 0 else []
    if not runs:
        print("(no matching ledger records)")
        return 1
    for rec in runs:
        if args.json:
            print(json.dumps(rec, sort_keys=True))
        else:
            print(format_run(rec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
