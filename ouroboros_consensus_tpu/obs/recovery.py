"""Self-healing replay: crash-consistent checkpoints + the supervised
degradation ladder.

The reference's ChainDB is built around crash recovery — replay resumes
from the last on-disk ledger snapshot, never from genesis (SURVEY.md:
ImmutableDB + VolatileDB + LedgerDB) — while rounds r02-r05 each died
mid-replay and banked NOTHING, restarting from header zero every time.
This module is the batched pipeline's equivalent of that contract,
in two halves:

**Checkpoint/resume** — when ``OCT_CHECKPOINT=<file>`` is set,
`validate_chain`'s retire path persists a tiny progress record per
retired window (cumulative chain position, the full `PraosState` —
nonce carry + per-pool counter map — and an integrity digest) with the
same tmp+rename atomicity as the heartbeat: a SIGKILL mid-write leaves
the previous complete record. `db_analyser.revalidate(resume=...)`
reopens it, skips the retired prefix of the window stream and seeds
the fold from the host record — proven verdict-identical to an
uninterrupted replay by the differential suite (tests/test_recovery.py),
including resume across an epoch boundary and a mid-ladder-swap kill.
The record is keyed by a ``chain_tag`` (db path + params) so a resume
against a different chain silently starts fresh, and a COMPLETED
replay marks its record ``complete`` so the next invocation never
skips work that was already banked.

**RecoverySupervisor** — a window whose dispatch/materialize raises a
recoverable error (device runtime errors, the chaos taxonomy, I/O) is
not the end of the replay: the supervisor escalates through an explicit
ladder, each rung a full re-validation of JUST that window —

    retry            the same path again, after jittered backoff
                     (transient device blips)
    stage-split      the per-lane/stage-split packed path (OCT_VRF_AGG
                     semantics forced off for the call — the
                     materialize_verdicts anomaly taxonomy path)
    xla-twin         the XLA twin of the pk pipeline (impl forced
                     "xla"; on CPU hosts this equals stage-split's
                     backend and still exercises the distinct flag)
    host-reference   the exact sequential reference fold (pure host,
                     cannot fail for device reasons) — the floor

— every transition a first-class `RecoveryEvent` through the batch
tracer (-> ``oct_recovery_total{action=}``), mirrored into the warmup
report (`WARMUP.note_recovery`) so it is banked in the round JSON and
the run ledger like every other forensic. Verdict-correct by
construction: each rung is a complete re-validation with identical
semantics (the differential suites pin all of them), so a recovered
replay's verdicts, error taxonomy and final nonce carry equal the
uninterrupted run's.

**ParentPolicy** — the bench parent's side of the same policy: it
tails the child's heartbeat classification and, when the child is
``stalled`` (its own watchdog tripped) or ``dead`` (heartbeat stopped)
past a grace window, SIGTERMs it (the child's faulthandler banks the
stacks), kills it, and relaunches with ``OCT_RESUME=1`` — the retry
resumes from the last retired window instead of burning the remaining
wall re-validating what was already banked.

Kill-switches: ``OCT_RECOVERY=0`` disables the supervisor (errors
propagate raw — the pre-PR-12 behavior); leaving ``OCT_CHECKPOINT``
unset disables checkpointing (the retire seam is one None check)."""

from __future__ import annotations

import hashlib
import json
import os
import threading
import time

_CKPT_ENV = "OCT_CHECKPOINT"
_RESUME_ENV = "OCT_RESUME"
_ENABLE_ENV = "OCT_RECOVERY"
_BACKOFF_ENV = "OCT_RECOVERY_BACKOFF_S"

SCHEMA_VERSION = 1

# the explicit escalation policy per backend — each rung re-validates
# the failing window completely, so any rung that returns IS the
# window's verdict (retry tries the SAME failed path again first)
LADDERS = {
    "device": ("retry", "stage-split", "xla-twin", "host-reference"),
    "sharded": ("retry", "xla-twin", "host-reference"),
    "native": ("retry", "host-reference"),
}


def checkpoint_path() -> str | None:
    return os.environ.get(_CKPT_ENV) or None


def resume_requested() -> bool:
    return os.environ.get(_RESUME_ENV, "0") not in ("0", "")


def enabled() -> bool:
    """OCT_RECOVERY (default on): the supervisor ladder. =0 restores
    raise-through (read per call so tests can A/B both behaviors)."""
    return os.environ.get(_ENABLE_ENV, "1") != "0"


# ---------------------------------------------------------------------------
# PraosState <-> JSON (the host progress record)
# ---------------------------------------------------------------------------


def _hx(b: bytes | None) -> str | None:
    return b.hex() if b is not None else None


def _unhx(s: str | None) -> bytes | None:
    return bytes.fromhex(s) if s is not None else None


def encode_state(st) -> dict:
    """PraosState -> a JSON-safe dict. The checkpoint is the WHOLE
    sequential fold state: nonce carry, per-pool counter map, last
    slot — everything `validate_chain` threads between windows.
    (Nothing of the fold lives on the device: the host folds the
    nonces window by window — COVERAGE.md §5.16.)"""
    return {
        "last_slot": st.last_slot,
        "ocert_counters": {k.hex(): int(v)
                          for k, v in sorted(st.ocert_counters.items())},
        "evolving_nonce": _hx(st.evolving_nonce),
        "candidate_nonce": _hx(st.candidate_nonce),
        "epoch_nonce": _hx(st.epoch_nonce),
        "lab_nonce": _hx(st.lab_nonce),
        "last_epoch_block_nonce": _hx(st.last_epoch_block_nonce),
    }


def decode_state(d: dict):
    from ..protocol.praos import PraosState

    return PraosState(
        last_slot=d.get("last_slot"),
        ocert_counters={bytes.fromhex(k): int(v)
                        for k, v in (d.get("ocert_counters") or {}).items()},
        evolving_nonce=_unhx(d.get("evolving_nonce")),
        candidate_nonce=_unhx(d.get("candidate_nonce")),
        epoch_nonce=_unhx(d.get("epoch_nonce")),
        lab_nonce=_unhx(d.get("lab_nonce")),
        last_epoch_block_nonce=_unhx(d.get("last_epoch_block_nonce")),
    )


def _digest(chain_tag: str, headers: int, windows: int, state: dict) -> str:
    """Integrity digest over everything resume trusts: a torn or
    hand-edited record fails closed (fresh start), never a silently
    wrong re-seed."""
    blob = json.dumps(
        {"chain_tag": chain_tag, "headers": headers, "windows": windows,
         "state": state},
        sort_keys=True, separators=(",", ":"),
    ).encode()
    return hashlib.blake2s(blob, digest_size=16).hexdigest()


def chain_tag(db_path: str, params) -> str:
    """Identity of the replay a checkpoint belongs to: the chain on
    disk plus the protocol parameters that shape its verdicts. A
    record tagged for another chain is ignored on resume (bench warms
    on the 100k chain, measures the 1M one — positions do not
    transfer)."""
    blob = f"{os.path.abspath(db_path)}|{params!r}".encode()
    return hashlib.blake2s(blob, digest_size=8).hexdigest()


# ---------------------------------------------------------------------------
# ProgressWriter: the per-retired-window atomic record
# ---------------------------------------------------------------------------


def _emit(ev) -> None:
    from ..protocol import batch as pbatch

    if pbatch.BATCH_TRACER is not None:
        pbatch.BATCH_TRACER(ev)


class ProgressWriter:
    """Accumulates the global chain position across the replay's
    retired windows (one `validate_stream` pipeline on the device
    backend, one `validate_chain` call per epoch segment elsewhere) and
    atomically rewrites the progress record per retired window —
    tmp+rename, the same crash contract as the heartbeat and warmup
    report. One tiny JSON write per window (~hundreds per replay), so
    the hot path is untaxed."""

    def __init__(self, path: str, chain_tag_: str,
                 headers: int = 0, windows: int = 0):
        self.path = path
        self.chain_tag = chain_tag_
        self._lock = threading.Lock()
        self.headers = headers  # guarded-by: _lock
        self.windows = windows  # guarded-by: _lock

    def note(self, state, n_new: int) -> None:
        from ..utils.trace import CheckpointEvent

        with self._lock:
            self.headers += int(n_new)
            self.windows += 1
            self._write(state, complete=False, error=None)
        _emit(CheckpointEvent("write", self.headers, self.windows))

    def finalize(self, state, error=None) -> None:
        """The replay COMPLETED (cleanly or at a validation error):
        mark the record so a later resume never skips a fresh run's
        work based on a finished one's position."""
        from ..utils.trace import CheckpointEvent

        with self._lock:
            self._write(state, complete=True,
                        error=None if error is None else repr(error)[:200])
        _emit(CheckpointEvent("complete", self.headers, self.windows))

    def _write(self, state, complete: bool, error) -> None:
        enc = encode_state(state)
        doc = {
            "schema": SCHEMA_VERSION,
            "kind": "oct-checkpoint",
            "chain_tag": self.chain_tag,
            "headers": self.headers,
            "windows": self.windows,
            "state": enc,
            "digest": _digest(self.chain_tag, self.headers, self.windows,
                              enc),
            "complete": complete,
            "error": error,
            "pid": os.getpid(),
            "ts_unix": time.time(),
        }
        try:
            tmp = self.path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(doc, f)
            os.replace(tmp, self.path)
        except OSError:
            pass  # checkpoints are best-effort; never break the replay


_WRITER: ProgressWriter | None = None


def arm_writer(chain_tag_: str, resumed_headers: int = 0,
               resumed_windows: int = 0) -> ProgressWriter | None:
    """Mount the process checkpoint writer iff OCT_CHECKPOINT is set
    (called by db_analyser.revalidate; the batch loop's seam is
    `note_window`). Resuming passes the record's position so the
    cumulative count stays genesis-anchored."""
    global _WRITER
    path = checkpoint_path()
    if path is None:
        _WRITER = None
        return None
    _WRITER = ProgressWriter(path, chain_tag_, resumed_headers,
                             resumed_windows)
    return _WRITER


def disarm_writer() -> None:
    global _WRITER
    _WRITER = None


def note_window(state, n_new: int) -> None:
    """The retire seam (protocol/batch._device_loop and the non-device
    loop): one None check when checkpointing is disarmed."""
    w = _WRITER
    if w is not None:
        w.note(state, n_new)


def read_checkpoint(path: str | None = None) -> dict | None:
    """Read + integrity-check a progress record; None when absent,
    torn, schema-alien or digest-mismatched (fail closed: a fresh
    start is always correct, a wrong re-seed never is)."""
    path = path or checkpoint_path()
    if not path:
        return None
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError, ValueError):
        return None
    if not isinstance(doc, dict) or doc.get("kind") != "oct-checkpoint":
        return None
    if doc.get("schema") != SCHEMA_VERSION:
        return None
    try:
        want = _digest(doc["chain_tag"], doc["headers"], doc["windows"],
                       doc["state"])
    except (KeyError, TypeError):
        return None
    if doc.get("digest") != want:
        return None
    return doc


def note_resume(doc: dict) -> None:
    """A replay seeded itself from a progress record instead of
    genesis: bank the fact (warmup note + CheckpointEvent("resume")
    -> oct_checkpoint_events_total{kind="resume"})."""
    from ..utils.trace import CheckpointEvent
    from .warmup import WARMUP

    WARMUP.note(
        f"resumed from checkpoint: {doc['headers']} headers / "
        f"{doc['windows']} windows already retired"
    )
    _emit(CheckpointEvent("resume", int(doc["headers"]),
                          int(doc["windows"])))


def resume_record(chain_tag_: str, path: str | None = None) -> dict | None:
    """The record a replay of `chain_tag_` may resume from: valid,
    same chain, not complete, with at least one retired window."""
    doc = read_checkpoint(path)
    if doc is None or doc.get("complete"):
        return None
    if doc.get("chain_tag") != chain_tag_:
        return None
    if not doc.get("headers"):
        return None
    return doc


# ---------------------------------------------------------------------------
# RecoverySupervisor: the in-process degradation ladder
# ---------------------------------------------------------------------------


def recoverable(exc: BaseException) -> bool:
    """Failure classes the ladder may absorb. Deliberately narrow —
    the per-class policy lives in `node/exit.triage` (the
    consensusRethrowPolicy analog): only `RECOVER`-class faults
    (device/runtime errors, I/O, the chaos taxonomy) ride the ladder.
    `REFUSE` (DB locked, wrong chain magic), `REPAIR` (on-disk
    corruption — the open-with-repair scan owns it) and `PROPAGATE`
    (TypeError-class programming bugs) all surface raw: recovery must
    never mask a wrong program OR launder a refusal."""
    from ..node import exit as node_exit

    return node_exit.triage(exc) is node_exit.Disposition.RECOVER


def note_recovery_event(action: str, window: int, lanes: int,
                        attempt: int, exc: BaseException,
                        ok: bool | None = None) -> None:
    """One recovery-ladder transition, banked everywhere at once: the
    warmup report (-> round JSON + ledger) and the batch tracer
    (-> oct_recovery_total{action=}). Shared by the supervisor and the
    non-window recoveries (db_analyser's chunk reread)."""
    from ..utils.trace import RecoveryEvent
    from .warmup import WARMUP

    fault = type(exc).__name__
    detail = repr(exc)[:200]
    WARMUP.note_recovery(action=action, window=window, attempt=attempt,
                         fault=fault, detail=detail, ok=ok)
    _emit(RecoveryEvent(action=action, window=window, lanes=lanes,
                        attempt=attempt, fault=fault, detail=detail,
                        ok=ok))


class RecoverySupervisor:
    """Escalates a failing window through LADDERS[backend]; every
    transition is a RecoveryEvent + warmup note. Injectable sleep for
    stubbed-clock tests; backoff jitter rides the chaos RNG when
    armed (deterministic recovery timing under a seeded fault plan)."""

    def __init__(self, backoff_s: float | None = None, sleep=time.sleep):
        if backoff_s is None:
            try:
                backoff_s = float(os.environ.get(_BACKOFF_ENV, "0.05"))
            except ValueError:
                backoff_s = 0.05
        self.backoff_s = backoff_s
        self.sleep = sleep
        self.episodes = 0
        self.recovered = 0

    # -- event plumbing -----------------------------------------------------

    def _note(self, action: str, window: int, lanes: int, attempt: int,
              exc: BaseException, ok: bool | None = None) -> None:
        note_recovery_event(action, window, lanes, attempt, exc, ok)

    def _jitter(self) -> float:
        from ..testing import chaos

        return chaos.jitter()

    # -- the ladder ---------------------------------------------------------

    def _run_rung(self, rung: str, params, ticked, hvs, backend, mesh):
        from ..protocol import batch as pbatch

        if rung == "retry":
            return pbatch.validate_batch(params, ticked, hvs,
                                         backend=backend, mesh=mesh)
        if rung == "stage-split":
            with pbatch.recovery_overrides(agg=False):
                return pbatch.validate_batch(params, ticked, hvs,
                                             backend="device")
        if rung == "xla-twin":
            with pbatch.recovery_overrides(agg=False, impl="xla"):
                return pbatch.validate_batch(params, ticked, hvs,
                                             backend="device")
        if rung == "host-reference":
            return host_reference_fold(params, ticked, hvs)
        raise ValueError(f"unknown recovery rung {rung!r}")

    def recover_window(self, params, ticked, hvs, exc: BaseException,
                       backend: str = "device", mesh=None,
                       window: int = -1):
        """One failing window -> its BatchResult, or the original
        exception re-raised (supervisor disabled / unrecoverable fault
        class / every rung failed — 'exhausted' is itself forensics)."""
        if not enabled() or not recoverable(exc):
            raise exc
        lanes = len(hvs)
        self.episodes += 1
        last: BaseException = exc
        ladder = LADDERS.get(backend, LADDERS["device"])
        for attempt, rung in enumerate(ladder, start=1):
            self._note(rung, window, lanes, attempt, last)
            if rung == "retry" and self.backoff_s > 0:
                self.sleep(self.backoff_s * self._jitter())
            try:
                res = self._run_rung(rung, params, ticked, hvs, backend,
                                     mesh)
            except Exception as e:  # noqa: BLE001 — escalate the ladder
                last = e
                continue
            self.recovered += 1
            self._note("recovered", window, lanes, attempt, exc, ok=True)
            return res
        self._note("exhausted", window, lanes, len(ladder), last, ok=False)
        raise last


def host_reference_fold(params, ticked, hvs):
    """The ladder's floor: the exact sequential reference fold of one
    within-epoch window (tick + update per header, pure host crypto) —
    the same semantics every differential suite pins `validate_batch`
    against, with no device in the loop at all."""
    from ..protocol import praos
    from ..protocol.views import ViewColumns
    from ..protocol.batch import BatchResult, rules_of

    update = rules_of(params).update  # the protocol's own reference
    views = hvs.views() if isinstance(hvs, ViewColumns) else hvs
    lview = ticked.ledger_view
    st = ticked.state
    t = ticked
    for i, hv in enumerate(views):
        if i:
            t = praos.tick(params, lview, hv.slot, st)
        try:
            new_st = update(params, hv, hv.slot, t)
        except praos.PraosValidationError as e:
            return BatchResult(st, i, e, None)
        st = new_st
    return BatchResult(st, len(views), None, None)


_SUPERVISOR: RecoverySupervisor | None = None
_SUP_LOCK = threading.Lock()


def supervisor() -> RecoverySupervisor:
    global _SUPERVISOR
    with _SUP_LOCK:
        if _SUPERVISOR is None:
            _SUPERVISOR = RecoverySupervisor()
        return _SUPERVISOR


def reset_for_tests() -> None:
    global _SUPERVISOR, _WRITER
    with _SUP_LOCK:
        _SUPERVISOR = None
    _WRITER = None


# ---------------------------------------------------------------------------
# ParentPolicy: the bench parent's escalation
# ---------------------------------------------------------------------------


class ParentPolicy:
    """Decide when a live child has to die for its own good. Consumes
    `obs/live.classify()` states (the bench heartbeat tail's
    vocabulary): a child continuously `stalled` — its OWN watchdog has
    tripped and stayed tripped — for `stall_grace_s`, or `dead` (the
    heartbeat file stopped moving) for `dead_grace_s`, should be
    SIGTERM'd for forensics and relaunched with resume. Compiling /
    staging / running states always reset the fuse: the policy only
    ever fires on sustained no-progress evidence, never on a slow
    compile (the watchdog's own fingerprint already treats warmup
    notes as progress)."""

    def __init__(self, stall_grace_s: float = 60.0,
                 dead_grace_s: float = 30.0, clock=time.monotonic):
        self.stall_grace_s = stall_grace_s
        self.dead_grace_s = dead_grace_s
        self.clock = clock
        self._since: float | None = None
        self._state: str | None = None

    def observe(self, state: str, now: float | None = None) -> str:
        """-> "keep" | "kill". Call once per poll with the current
        classification."""
        now = self.clock() if now is None else now
        if state not in ("stalled", "dead"):
            self._since, self._state = None, None
            return "keep"
        if self._state != state:
            self._since, self._state = now, state
            return "keep"
        grace = (self.stall_grace_s if state == "stalled"
                 else self.dead_grace_s)
        if self._since is not None and now - self._since >= grace:
            return "kill"
        return "keep"


def terminate_for_forensics(proc, sigterm_wait_s: float = 10.0) -> None:
    """SIGTERM (the child's registered faulthandler banks all-thread
    stacks into the teed log), a bounded wait, then SIGKILL."""
    import subprocess

    try:
        proc.terminate()
        try:
            proc.wait(timeout=sigterm_wait_s)
            return
        except subprocess.TimeoutExpired:
            pass
        proc.kill()
        proc.wait()
    except OSError:
        pass
