"""Follow-the-tip serving plane: continuous batching of multi-peer
candidate suffixes into shared packed device windows.

The reference's production workload is not one long replay — it is
ChainSel plus thousands of concurrent per-peer ChainSync instances each
pushing a SHORT candidate suffix at the tip (SURVEY.md §3.2/§3.5; the
ROADMAP north-star shape). A naive port would dispatch one device
window per peer: at tip-follow depth (a handful of headers per
candidate) that pads every window to the minimum bucket and burns the
whole dispatch wall per peer. This module applies the inference-server
answer — continuous batching (Orca-style iteration-level scheduling;
vLLM-style slot reuse) — to header validation:

  * every peer (tenant) owns a FIFO of candidate suffixes and its own
    sequential fold state (PraosState: nonce carry + OCert counters);
  * a single scheduler thread fills SHARED packed windows from whatever
    lanes are pending across tenants of one window shape, dispatches
    through the existing packed-stage path (`prepare_window` /
    `dispatch_prepared` / `materialize_verdicts` — the same programs
    the replay plane compiled), and scatters per-tenant first-failure
    verdicts back by slicing the window's HostChecks/Verdicts columns
    per tenant segment and running the sequential `_epilogue` against
    THAT tenant's state;
  * correctness of sharing: every per-lane device check depends only on
    (params, ledger view, epoch nonce, header bytes) — the ONLY
    cross-lane state is the sequential fold, which never runs on shared
    lanes: each tenant's epilogue folds its own segment against its own
    state, so lanes from different tenants cannot bleed into each
    other's verdicts by construction. Nothing is carried on the device
    from one window to the next: the nonce fold is the host's, inside
    each tenant's epilogue;
  * admission is priced (protocol/admission.py): a cold tenant whose
    window shape misses the warm/AOT store rides the warm-compile rung
    ladder instead of stalling warm traffic;
  * a device fault mid-window sheds each affected tenant segment down
    the PR 12 recovery ladder (`recover_window` — retry / stage-split /
    xla-twin / host-reference), every rung a full re-validation with
    identical semantics, so the shed verdicts are byte-identical and no
    tenant is dropped; the episode is recorded as a DEGRADED interval
    on the SLO surface instead of a run abort;
  * `OCT_SERVE_DEVICE=0` kill-switches the device plane entirely: every
    window reroutes to the per-tenant host reference fold (the ladder's
    floor — real host crypto, no staging, no JAX dispatch);
  * `OCT_SERVE_CHECKPOINT=<file>` persists a per-retired-window
    atomic progress record (tmp+rename, digest, fail-closed read) so a
    SIGKILL'd service relaunches with per-tenant carry resume: seeded
    traffic regenerates byte-identically (testing/traffic.py) and
    `submit` fast-forwards past already-banked suffixes.

The SLO surface is `slo_snapshot()` — p50/p99 verdict latency,
aggregate headers/s, queue depths, the degraded flag and its
intervals — served live by obs/server.py's `/slo` route when a
MetricsServer is mounted with `slo_doc=service.slo_snapshot`."""

from __future__ import annotations

import hashlib
import json
import os
import threading
import time
from collections import deque
from dataclasses import dataclass, field

import numpy as np

from ..obs import recovery as _recovery
from ..obs import registry as _registry
from ..protocol import batch as pbatch
from ..protocol import praos
from ..protocol.admission import AdmissionPolicy, WindowShape, shape_of

__all__ = [
    "SuffixVerdict", "Tenant", "ValidationService", "read_serve_checkpoint",
]

_DEVICE_ENV = "OCT_SERVE_DEVICE"
_CKPT_ENV = "OCT_SERVE_CHECKPOINT"

SCHEMA_VERSION = 1


def _device_serving() -> bool:
    """OCT_SERVE_DEVICE (default on): the packed device window path.
    =0 kill-switches the device plane — every window reroutes to the
    per-tenant host reference fold (read per window so a flip mid-run
    takes effect at the next window boundary)."""
    return os.environ.get(_DEVICE_ENV, "1") != "0"


@dataclass(frozen=True)
class SuffixVerdict:
    """One resolved candidate suffix: how many headers of it extended
    the tenant's chain, and the first-failure error (None = the whole
    suffix was valid). `n_valid` counts valid headers WITHIN the
    suffix — the reference's first-failure contract: everything after
    the first invalid header is discarded unexamined."""

    tenant_id: str
    seq: int
    n_valid: int
    error: str | None

    def row(self) -> list:
        """Canonical comparable form (checkpoint + byte-identity
        assertions across degraded/host/device paths)."""
        return [self.seq, self.n_valid, self.error]


def _canon_error(err) -> str | None:
    """Canonical error string: class name + message, identical across
    the device epilogue, every recovery rung and the host fold (all
    raise the same reference taxonomy classes with the same args)."""
    if err is None:
        return None
    return f"{type(err).__name__}: {err}"


@dataclass
class _Job:
    """One queued candidate suffix; `offset` = headers already folded
    into the tenant's state (a suffix may span several windows)."""

    seq: int
    hvs: tuple
    shape: WindowShape
    offset: int = 0
    t_submit: float = 0.0


@dataclass
class Tenant:
    """One simulated peer's server-side lane: fold state, suffix FIFO
    and resolved verdicts. All mutation happens on the scheduler
    thread (pump) or under the service lock."""

    tenant_id: str
    state: praos.PraosState
    queue: deque = field(default_factory=deque)
    verdicts: list = field(default_factory=list)
    seen: int = 0  # suffixes ever submitted (resume fast-forward key)
    done: int = 0  # suffixes finalized (verdict banked)
    headers_done: int = 0
    resume_offset: int = 0  # of suffix `done`, folded pre-relaunch

    def pending_headers(self) -> int:
        return sum(len(j.hvs) - j.offset for j in self.queue)


def _doc_digest(doc: dict) -> str:
    blob = json.dumps(doc, sort_keys=True, separators=(",", ":")).encode()
    return hashlib.blake2s(blob, digest_size=16).hexdigest()


def read_serve_checkpoint(path: str | None) -> dict | None:
    """Read + integrity-check a serve progress record; None when
    absent, torn, schema-alien or digest-mismatched (fail closed — the
    same contract as obs/recovery.read_checkpoint: a fresh start is
    always correct, a wrong re-seed never is)."""
    if not path:
        return None
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError, ValueError):
        return None
    if not isinstance(doc, dict) or doc.get("kind") != "oct-serve-checkpoint":
        return None
    if doc.get("schema") != SCHEMA_VERSION:
        return None
    digest = doc.get("digest")
    body = {k: v for k, v in doc.items() if k != "digest"}
    if digest != _doc_digest(body):
        return None
    return doc


class ValidationService:
    """The long-lived serving plane: tenants `submit()` candidate
    suffixes, `pump()` runs one continuous-batching iteration (fill one
    shared window, dispatch, scatter verdicts), `run_until_drained()`
    loops it. One scheduler thread owns pump(); `submit`, `register`
    and `slo_snapshot` may be called from other threads (the service
    lock guards the shared tenant/interval structures)."""

    def __init__(self, params, lview, eta0: bytes, *, registry=None,
                 policy: AdmissionPolicy | None = None,
                 max_window: int = 256, checkpoint: str | None = None,
                 serve_tag: str | None = None):
        self.params = params
        self.lview = lview
        self.eta0 = eta0
        self.registry = (registry if registry is not None
                         else _registry.default_registry())
        self.policy = policy if policy is not None else AdmissionPolicy()
        self.max_window = max(1, int(max_window))
        self.checkpoint = (checkpoint if checkpoint is not None
                           else os.environ.get(_CKPT_ENV) or None)
        if serve_tag is None:
            blob = f"{params!r}|{eta0.hex()}".encode()
            serve_tag = hashlib.blake2s(blob, digest_size=8).hexdigest()
        self.serve_tag = serve_tag
        self._lock = threading.Lock()
        self.tenants: dict[str, Tenant] = {}  # guarded-by: _lock
        self.windows = 0  # guarded-by: _lock
        self.degraded = False  # guarded-by: _lock
        # [t_open, t_close | None, fault-class] — guarded-by: _lock
        self.degraded_intervals: list[list] = []
        self._clean_streak = 0
        self._rr = 0  # window fill rotation cursor (scheduler thread)
        self.resumed = False
        self._t0 = time.monotonic()
        r = self.registry
        self._m_suffixes = r.counter(
            "oct_serve_suffixes_total",
            "candidate suffixes resolved by the serving plane",
            ("result",),
        )
        self._m_headers = r.counter(
            "oct_serve_headers_total",
            "headers validated by the serving plane",
        )
        self._m_windows = r.counter(
            "oct_serve_windows_total",
            "shared serving windows retired", ("mode",),
        )
        self._m_degraded = r.gauge(
            "oct_serve_degraded",
            "1 while serving rides the recovery ladder (degraded mode)",
        )
        self._m_queue = r.gauge(
            "oct_serve_queue_depth",
            "pending headers across all tenant queues",
        )
        self._m_latency = r.histogram(
            "oct_serve_verdict_latency_seconds",
            "submit->verdict wall per candidate suffix",
        )
        if self.checkpoint:
            self._try_resume()

    # -- tenants ------------------------------------------------------------

    def register(self, tenant_id: str, state=None) -> Tenant:
        """Idempotent: an existing tenant is returned unchanged (its
        fold state is the server's truth, not the caller's)."""
        with self._lock:
            t = self.tenants.get(tenant_id)
            if t is None:
                if state is None:
                    state = praos.PraosState(epoch_nonce=self.eta0)
                t = Tenant(tenant_id, state)
                self.tenants[tenant_id] = t
            return t

    def submit(self, tenant_id: str, hvs) -> int:
        """Enqueue one candidate suffix; returns its per-tenant
        sequence number. Malformed suffixes raise AdmissionRefused at
        the door (disposition REFUSE — nothing else is touched). After
        a resume, suffixes whose verdicts are already banked are
        fast-forwarded (the seeded traffic source re-submits the whole
        stream; the service knows what it already folded)."""
        from ..protocol.admission import AdmissionRefused

        t = self.register(tenant_id)
        try:
            shape = shape_of(tenant_id, hvs)
        except AdmissionRefused:
            self._m_suffixes.labels(result="refused").inc()
            raise
        with self._lock:
            seq = t.seen
            t.seen += 1
            if seq < t.done:
                return seq  # verdict already banked pre-relaunch
            job = _Job(seq, tuple(hvs), shape, t_submit=time.monotonic())
            if seq == t.done and t.resume_offset:
                # the killed process folded a prefix of this suffix:
                # its headers are already in the restored state
                job.offset = min(t.resume_offset, len(job.hvs))
                t.resume_offset = 0
            t.queue.append(job)
        self._update_queue_gauge()
        return seq

    def verdicts(self, tenant_id: str) -> list:
        with self._lock:
            t = self.tenants.get(tenant_id)
            return list(t.verdicts) if t is not None else []

    # -- the continuous-batching scheduler ----------------------------------

    def pump(self) -> bool:
        """One iteration-level scheduling step: pick a window shape
        with pending lanes, fill ONE shared window fairly across its
        tenants (rotating quantum fill — a cold tenant's lanes ride
        their own rung-capped windows, so it cannot starve warm
        traffic), dispatch, scatter per-tenant verdicts. Returns False
        when no tenant has pending work."""
        from ..testing import chaos

        with self._lock:
            groups: dict[WindowShape, list[Tenant]] = {}
            for t in self.tenants.values():
                if t.queue:
                    groups.setdefault(t.queue[0].shape, []).append(t)
            if not groups:
                return False
            shapes = sorted(groups, key=lambda s: (s.proof_len, s.body_len))
            shape = shapes[self._rr % len(shapes)]
            tenants = groups[shape]
            order = (tenants[self._rr % len(tenants):]
                     + tenants[:self._rr % len(tenants)])
            self._rr += 1
            pending = sum(len(t.queue[0].hvs) - t.queue[0].offset
                          for t in order)
        decision = self.policy.admit(shape, min(pending, self.max_window))
        cap = min(decision.lane_cap, self.max_window)
        # fair fill: rotating passes granting up to one quantum per
        # tenant per pass until the window is full or the shape drains
        takes = {t.tenant_id: 0 for t in order}
        avail = {t.tenant_id: len(t.queue[0].hvs) - t.queue[0].offset
                 for t in order}
        quantum = max(1, cap // max(1, len(order)))
        space = cap
        while space > 0:
            progressed = False
            for t in order:
                room = min(avail[t.tenant_id] - takes[t.tenant_id],
                           quantum, space)
                if room > 0:
                    takes[t.tenant_id] += room
                    space -= room
                    progressed = True
            if not progressed:
                break
        whvs: list = []
        segments: list[tuple] = []  # (tenant, job, lo, hi)
        for t in order:
            n = takes[t.tenant_id]
            if not n:
                continue
            job = t.queue[0]
            lo = len(whvs)
            whvs.extend(job.hvs[job.offset:job.offset + n])
            segments.append((t, job, lo, lo + n))
        if not whvs:
            return False
        results, fault = self._run_window(whvs, segments, self.windows)
        mode = decision.mode if _device_serving() else "host"
        self._m_windows.labels(mode=mode).inc()
        with self._lock:
            for (t, job, lo, hi), res in zip(segments, results):
                t.state = res.state
                t.headers_done += res.n_valid
                job.offset += res.n_valid
                self._m_headers.inc(res.n_valid)
                if res.error is not None:
                    self._finalize(t, job, res.error)
                elif job.offset >= len(job.hvs):
                    self._finalize(t, job, None)
            self.windows += 1
            self._note_fault(fault)
        if fault is None and mode != "host":
            # promotion is earned: only a CLEAN device window warms its
            # bucket for the admission ladder
            self.policy.note_window(shape, len(whvs))
        self._update_queue_gauge()
        self._write_checkpoint()
        # checkpoint-before-kill ordering: the record for THIS window is
        # durable before the sigkill seam can fire (chaos: sigkill@serve:N)
        chaos.fire("serve")
        return True

    def run_until_drained(self, max_windows: int = 100_000) -> int:
        n = 0
        while n < max_windows and self.pump():
            n += 1
        return n

    # -- one window ---------------------------------------------------------

    def _run_window(self, whvs, segments, widx):
        """Dispatch one shared window and fold each tenant segment.
        Device faults shed each affected segment down the recovery
        ladder (full re-validation per rung — verdicts byte-identical
        by construction); with the device plane kill-switched every
        window reroutes to the per-tenant host reference fold."""
        from ..testing import chaos

        if not _device_serving():
            return self._host_window(whvs, segments), None
        try:
            # the serving dispatch seam (chaos:
            # device-error@serve-dispatch:N) fires BEFORE staging so a
            # faulted window sheds whole segments, never half-built state
            chaos.fire("serve-dispatch")
            sw = pbatch.prepare_window(
                self.params, self.lview, self.eta0, whvs,
                pbatch.window_lanes(self.max_window),
            )
            pre, tagged, b = pbatch.dispatch_prepared(sw)
            v = pbatch.materialize_verdicts(tagged, b)
            results = []
            if len(segments) == 1:
                t, _job, _lo, _hi = segments[0]
                ticked = praos.tick(self.params, self.lview, whvs[0].slot,
                                    t.state)
                results.append(
                    pbatch._epilogue(self.params, ticked, whvs, pre, v)
                )
            else:
                full = (v.full() if isinstance(v, pbatch.PackedVerdicts)
                        else v)
                for t, _job, lo, hi in segments:
                    results.append(
                        self._segment_epilogue(t, whvs, pre, full, lo, hi)
                    )
            return results, None
        except Exception as exc:  # noqa: BLE001 — routed through triage:
            # recover_window absorbs ONLY RECOVER-class faults (device
            # runtime errors, the chaos taxonomy); anything else
            # re-raises out of the ladder unmasked
            results = []
            for t, _job, lo, hi in segments:
                seg = list(whvs[lo:hi])
                ticked = praos.tick(self.params, self.lview, seg[0].slot,
                                    t.state)
                results.append(_recovery.supervisor().recover_window(
                    self.params, ticked, seg, exc, backend="device",
                    window=widx,
                ))
            return results, exc

    def _segment_epilogue(self, tenant, whvs, pre, full, lo, hi):
        """Scatter one tenant's slice of a shared window: slice the
        positional HostChecks/Verdicts columns and run the sequential
        fold against THAT tenant's state — the only stateful step, so
        cross-tenant bleed is structurally impossible."""
        seg = list(whvs[lo:hi])
        ticked = praos.tick(self.params, self.lview, seg[0].slot,
                            tenant.state)
        pre_t = pbatch.HostChecks(
            kes_window_errors=list(pre.kes_window_errors[lo:hi]),
            vrf_lookup_errors=list(pre.vrf_lookup_errors[lo:hi]),
            kes_evolution=np.asarray(pre.kes_evolution)[lo:hi],
        )
        v_t = pbatch.Verdicts(
            *(np.asarray(col)[lo:hi] for col in full)
        )
        return pbatch._epilogue(self.params, ticked, seg, pre_t, v_t)

    def _host_window(self, whvs, segments):
        """The OCT_SERVE_DEVICE=0 reroute: per-tenant sequential host
        reference fold (the recovery ladder's floor) — no staging, no
        device dispatch, real host crypto."""
        results = []
        for t, _job, lo, hi in segments:
            seg = list(whvs[lo:hi])
            ticked = praos.tick(self.params, self.lview, seg[0].slot,
                                t.state)
            results.append(
                _recovery.host_reference_fold(self.params, ticked, seg)
            )
        return results

    # -- bookkeeping (callers hold self._lock where noted) -------------------

    def _finalize(self, tenant, job, error) -> None:
        # caller holds self._lock
        tenant.queue.popleft()
        tenant.done += 1
        err = _canon_error(error)
        tenant.verdicts.append(
            SuffixVerdict(tenant.tenant_id, job.seq, job.offset, err)
        )
        self._m_suffixes.labels(
            result="valid" if err is None else "invalid"
        ).inc()
        if job.t_submit:
            self._m_latency.observe(time.monotonic() - job.t_submit)

    def _note_fault(self, fault) -> None:
        # caller holds self._lock
        now = time.monotonic() - self._t0
        if fault is not None:
            self._clean_streak = 0
            if not self.degraded:
                self.degraded = True
                self.degraded_intervals.append(
                    [now, None, type(fault).__name__]
                )
                self._m_degraded.set(1)
            return
        self._clean_streak += 1
        if self.degraded and self._clean_streak >= 2:
            # two consecutive clean windows close the degraded interval
            self.degraded = False
            self.degraded_intervals[-1][1] = now
            self._m_degraded.set(0)

    def _update_queue_gauge(self) -> None:
        with self._lock:
            depth = sum(t.pending_headers() for t in self.tenants.values())
        self._m_queue.set(depth)

    # -- the SLO surface -----------------------------------------------------

    def slo_snapshot(self) -> dict:
        """The live SLO document (obs/server.py `/slo`): verdict-latency
        tails, aggregate throughput, queue depths, degraded state and
        the admission decision mix."""
        with self._lock:
            headers = sum(t.headers_done for t in self.tenants.values())
            depths = [t.pending_headers() for t in self.tenants.values()]
            doc = {
                "kind": "oct-serve-slo",
                "schema": SCHEMA_VERSION,
                "serve_tag": self.serve_tag,
                "tenants": len(self.tenants),
                "windows": self.windows,
                "headers": headers,
                "suffixes_done": sum(t.done
                                     for t in self.tenants.values()),
                "queue_depth": sum(depths),
                "queue_depth_max": max(depths, default=0),
                "degraded": self.degraded,
                "degraded_intervals": [list(iv) for iv
                                       in self.degraded_intervals],
                "resumed": self.resumed,
            }
        elapsed = max(time.monotonic() - self._t0, 1e-9)
        doc["headers_per_s"] = headers / elapsed
        doc["verdict_latency_p50_s"] = self._m_latency.quantile(0.5)
        doc["verdict_latency_p99_s"] = self._m_latency.quantile(0.99)
        doc["admission"] = dict(self.policy.decisions)
        doc["device_serving"] = _device_serving()
        doc["ts_unix"] = time.time()
        return doc

    # -- checkpoint / resume -------------------------------------------------

    def _write_checkpoint(self) -> None:
        """Per-retired-window atomic progress record (tmp+rename, the
        obs/recovery crash contract): tenant fold states, banked
        verdicts and the in-progress suffix offset — everything a
        relaunch needs to resume without re-folding or double-counting."""
        if not self.checkpoint:
            return
        with self._lock:
            doc = {
                "schema": SCHEMA_VERSION,
                "kind": "oct-serve-checkpoint",
                "serve_tag": self.serve_tag,
                "windows": self.windows,
                "tenants": {
                    tid: {
                        "state": _recovery.encode_state(t.state),
                        "done": t.done,
                        "headers_done": t.headers_done,
                        "offset": (t.queue[0].offset if t.queue else 0),
                        "verdicts": [v.row() for v in t.verdicts],
                    }
                    for tid, t in sorted(self.tenants.items())
                },
                "pid": os.getpid(),
                "ts_unix": time.time(),
            }
        doc["digest"] = _doc_digest(doc)
        try:
            tmp = self.checkpoint + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(doc, f)
            os.replace(tmp, self.checkpoint)
        except OSError:
            pass  # best-effort, never breaks serving

    def _try_resume(self) -> bool:
        doc = read_serve_checkpoint(self.checkpoint)
        if doc is None or doc.get("serve_tag") != self.serve_tag:
            return False
        for tid, row in doc["tenants"].items():
            t = self.register(tid,
                              state=_recovery.decode_state(row["state"]))
            t.done = int(row["done"])
            t.headers_done = int(row["headers_done"])
            t.resume_offset = int(row["offset"])
            t.verdicts = [SuffixVerdict(tid, *r) for r in row["verdicts"]]
        with self._lock:
            self.windows = int(doc["windows"])
        self.resumed = True
        return True
