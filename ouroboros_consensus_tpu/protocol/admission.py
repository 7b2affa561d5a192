"""Priced admission for the serving plane: warm shapes go straight to
the device; cold shapes ride the rung ladder instead of stalling warm
traffic.

The serving scheduler (node/serve.py) fills shared packed windows from
whatever lanes are pending across tenants. Every window pads to a
power-of-two-family bucket (protocol/batch.bucket_size), and each
DISTINCT (proof format, body length, bucket) shape is one compiled
device program: the first dispatch of a shape pays its compile wall.
On a TPU session that wall is minutes (PERF.md round 6) — letting one
cold tenant's odd shape compile INLINE would stall every warm tenant
behind it, the exact head-of-line blocking the round-10 warm ladder
exists to avoid during replays.

This module is the serving-side twin of that ladder, as an admission
decision instead of a window re-tiler:

  * a WARM shape (its bucket has already dispatched this process, or
    an AOT-pinned rung program covers it) is admitted at full size;
  * a COLD shape is CAPPED to the warm-compile rung ladder
    (analysis/costmodel.LADDER_RUNGS, the same rungs the replay ladder
    compiles and octwall pins): the tenant serves on rung-sized
    windows — individually cheap compiles, promoted bucket by bucket
    as each retires warm — and escalates to its full requested shape
    only once the ladder has walked there;
  * pricing is the octwall surface: `costmodel.predicted_wall` for the
    shape's registered graph twin and `costmodel.preflight` under an
    exported $OCT_WALL_DEADLINE, with the per-stage
    `obs.resources.RESOURCES` device-resources rows attached to the
    decision so the SLO surface can show WHY a tenant is rung-capped.

Malformed submissions are REFUSED at the door (`AdmissionRefused`,
disposition REFUSE in node/exit.DISPOSITIONS): an empty suffix, a
suffix mixing proof formats (a window must stage one uniform proof
column), or non-increasing slots (a candidate suffix is a chain).

Single-writer discipline: one scheduler thread owns a policy instance
(node/serve.py's pump loop); the class keeps no locks by design."""

from __future__ import annotations

import os
from dataclasses import dataclass

from .batch import bucket_size

_DEVICE_ENV = "OCT_SERVE_DEVICE"


class AdmissionRefused(Exception):
    """A submission the serving plane rejects at the door (malformed
    suffix — never a capacity decision; capacity cold-starts are CAPPED,
    not refused). Disposition REFUSE: the tenant's input is wrong and
    retrying the identical submission cannot succeed."""

    def __init__(self, tenant_id: str, reason: str):
        self.tenant_id = tenant_id
        self.reason = reason
        super().__init__(f"tenant {tenant_id}: {reason}")


@dataclass(frozen=True)
class WindowShape:
    """The compile-relevant shape of a candidate suffix: what selects
    the staged layout (and therefore the compiled program family)."""

    proof_len: int  # 80 draft-03 | 128 batch-compatible
    body_len: int  # KES-signed body bytes (packed layout body column)

    def graph(self) -> str:
        """Registered costmodel graph twin of this shape's packed
        program (the xla-packed path's structural twin — the serving
        rig's dispatch impl)."""
        return ("verify_praos_core" if self.proof_len == 80
                else "verify_praos_core_bc")

    def stage_label(self, lanes: int) -> str:
        """Warmup-vocabulary stage label for preflight pricing (the
        xla-packed label family of protocol/batch._jitted_packed_xla)."""
        return f"xla-packed:{self.body_len}b:p{self.proof_len}@{lanes}"


@dataclass(frozen=True)
class AdmissionDecision:
    """One priced admission: how many lanes this shape may fill in the
    next shared window, and why."""

    mode: str  # "warm" | "rung" | "host"
    lane_cap: int  # max lanes of this shape in the next window
    bucket: int  # the padded bucket the cap dispatches as
    predicted_wall_s: float | None  # octwall price of that bucket (cold)
    device_resources: dict | None  # per-stage ledger rows, when banked


def shape_of(tenant_id: str, hvs) -> WindowShape:
    """Validate one candidate suffix at the door and derive its shape.
    Raises AdmissionRefused on the malformed cases the packed stage
    cannot window (the caller scatters the refusal back to the tenant
    without touching any other tenant's traffic)."""
    if not len(hvs):
        raise AdmissionRefused(tenant_id, "empty candidate suffix")
    plen = len(hvs[0].vrf_proof)
    blen = len(hvs[0].signed_bytes)
    prev_slot = None
    for hv in hvs:
        if len(hv.vrf_proof) != plen:
            raise AdmissionRefused(
                tenant_id,
                f"suffix mixes proof formats ({plen} and "
                f"{len(hv.vrf_proof)} bytes) — one window stages one "
                "uniform proof column",
            )
        if len(hv.signed_bytes) != blen:
            raise AdmissionRefused(
                tenant_id,
                "suffix mixes body lengths — packed staging needs "
                "rectangular columns",
            )
        if prev_slot is not None and hv.slot <= prev_slot:
            raise AdmissionRefused(
                tenant_id,
                f"non-increasing slot {hv.slot} after {prev_slot} — a "
                "candidate suffix is a chain",
            )
        prev_slot = hv.slot
    return WindowShape(proof_len=plen, body_len=blen)


class AdmissionPolicy:
    """Warm-shape tracking + rung-ladder capping for one service.

    `admit(shape, requested)` prices the shape's next window;
    `note_window(shape, lanes)` marks the dispatched bucket warm after
    the window retires (promotion is EARNED, never assumed — a shed or
    recovered window does not warm its bucket). One scheduler thread
    owns the instance; no locks by design."""

    def __init__(self, rungs: tuple | None = None):
        from ..analysis import costmodel

        self._costmodel = costmodel
        self.rungs = tuple(sorted(rungs if rungs is not None
                                  else costmodel.LADDER_RUNGS))
        # shape -> set of buckets proven warm in this process
        self._warm: dict[WindowShape, set] = {}
        self.decisions: dict[str, int] = {"warm": 0, "rung": 0, "host": 0}

    # -- warm-set bookkeeping ----------------------------------------------

    def is_warm(self, shape: WindowShape, bucket: int) -> bool:
        if bucket in self._warm.get(shape, ()):
            return True
        # an octwall rung pin covers the bucket: the program was
        # AOT-priced and its compile is known to fit the rung budget —
        # treat the PINNED rungs as warm-startable, exactly like the
        # replay ladder does when choosing its first rung
        pin = self._costmodel.ladder_pin_name(shape.graph(), bucket)
        return self._costmodel.pinned(pin) is not None

    def note_window(self, shape: WindowShape, lanes: int) -> None:
        """A window of this shape retired cleanly at `lanes`: its
        bucket (and every smaller one — bucket_size is monotone) is
        warm for the rest of the process."""
        self._warm.setdefault(shape, set()).add(bucket_size(lanes))

    def warm_buckets(self, shape: WindowShape) -> tuple:
        return tuple(sorted(self._warm.get(shape, ())))

    # -- pricing ------------------------------------------------------------

    def price(self, shape: WindowShape, bucket: int) -> float | None:
        """Predicted cold-compile wall of this shape at `bucket` lanes:
        the rung pin when octwall has one, else the base graph pin.
        None = unpriced (the gate never blocks on ignorance)."""
        cm = self._costmodel
        pred = cm.predicted_wall(cm.ladder_pin_name(shape.graph(), bucket))
        if pred is None:
            pred = cm.predicted_wall(shape.graph())
        return pred

    def _resources_rows(self, shape: WindowShape) -> dict | None:
        """The per-stage device-resources ledger rows banked for this
        shape's graph family, when the resources plane is armed —
        attached to decisions so the SLO surface can show the price."""
        from ..obs.resources import RESOURCES

        report = RESOURCES.report()
        if not report:
            return None
        base = shape.graph()
        rows = {k: v for k, v in report.items() if base in k}
        return rows or None

    # -- the decision -------------------------------------------------------

    def admit(self, shape: WindowShape, requested: int) -> AdmissionDecision:
        """Lane cap for this shape's next window.

        Warm bucket -> full size. Cold -> the rung ladder: serve at the
        largest already-warm bucket of this shape, else at the
        octwall-chosen starting rung (`costmodel.choose_rung` against
        $OCT_WALL_DEADLINE), escalating one rung per warm window until
        the requested bucket is reachable. With the device plane
        kill-switched (OCT_SERVE_DEVICE=0) every shape is mode="host":
        the host fold has no compile wall to price."""
        requested = max(1, int(requested))
        if os.environ.get(_DEVICE_ENV, "1") == "0":
            self.decisions["host"] += 1
            return AdmissionDecision("host", requested,
                                     bucket_size(requested), None, None)
        bucket = bucket_size(requested)
        if self.is_warm(shape, bucket):
            self.decisions["warm"] += 1
            return AdmissionDecision("warm", requested, bucket,
                                     self.price(shape, bucket), None)
        warm = self.warm_buckets(shape)
        if warm:
            # escalate one rung past the largest earned bucket; the
            # ladder positions are the octwall rungs plus the requested
            # bucket as its top
            ladder = sorted({*(r for r in self.rungs), bucket})
            nxt = next((r for r in ladder if r > warm[-1]), bucket)
            cap = min(requested, nxt)
        else:
            start = self._costmodel.choose_rung(shape.graph())
            cap = min(requested, start if start else min(self.rungs))
        # octwall preflight on the capped shape: under a wall deadline a
        # rung whose own compile does not fit sheds further down
        while cap > 1 and not self._costmodel.preflight(
            shape.stage_label(bucket_size(cap)),
            graph=self._costmodel.ladder_pin_name(
                shape.graph(), bucket_size(cap)),
            action="serve-rung-shed",
        ):
            lower = [r for r in self.rungs if r < cap]
            if not lower:
                break
            cap = lower[-1]
        self.decisions["rung"] += 1
        return AdmissionDecision(
            "rung", cap, bucket_size(cap),
            self.price(shape, bucket_size(cap)),
            self._resources_rows(shape),
        )
