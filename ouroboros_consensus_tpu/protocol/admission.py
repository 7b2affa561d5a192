"""Admission for the serving plane: warm shapes go straight to the
device at full size; cold shapes climb a rung ladder instead of
stalling warm traffic.

The serving scheduler (node/serve.py) fills shared packed windows from
whatever lanes are pending across tenants. Every window pads to a
power-of-two-family bucket (protocol/batch.bucket_size), and each
DISTINCT (proof format, body length, bucket) shape is one compiled
device program: the first dispatch of a shape pays its compile wall.
Letting one cold tenant's odd shape compile INLINE at full size would
stall every warm tenant behind it.

  * a WARM shape (its bucket has already retired a window in this
    process) is admitted at full size;
  * a COLD shape is CAPPED to the rung ladder (`RUNGS`): the tenant
    starts at the largest rung and escalates one rung per warm window
    until its requested bucket is reachable. Warmth is EARNED by a
    retired window, never assumed.

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

# the lane counts a cold shape may serve at before its requested bucket
# has been earned
RUNGS = (1024, 2048)


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
    body_len: int  # the widest KES-signed body (packed body column width)


@dataclass(frozen=True)
class AdmissionDecision:
    """One admission: how many lanes this shape may fill in the next
    shared window, and why."""

    mode: str  # "warm" | "rung" | "host"
    lane_cap: int  # max lanes of this shape in the next window
    bucket: int  # the padded bucket the cap dispatches as


def shape_of(tenant_id: str, hvs) -> WindowShape:
    """Validate one candidate suffix at the door and derive its shape.
    Raises AdmissionRefused on the malformed cases the packed stage
    cannot window (the caller scatters the refusal back to the tenant
    without touching any other tenant's traffic)."""
    if not len(hvs):
        raise AdmissionRefused(tenant_id, "empty candidate suffix")
    plen = len(hvs[0].vrf_proof)
    prev_slot = None
    for hv in hvs:
        if len(hv.vrf_proof) != plen:
            raise AdmissionRefused(
                tenant_id,
                f"suffix mixes proof formats ({plen} and "
                f"{len(hv.vrf_proof)} bytes) — one window stages one "
                "uniform proof column",
            )
        if prev_slot is not None and hv.slot <= prev_slot:
            raise AdmissionRefused(
                tenant_id,
                f"non-increasing slot {hv.slot} after {prev_slot} — a "
                "candidate suffix is a chain",
            )
        prev_slot = hv.slot
    # bodies of several lengths stage as one packed window (a row each
    # of its layout table, `batch.BODY_TAB_COLS`)
    return WindowShape(proof_len=plen,
                       body_len=max(len(hv.signed_bytes) for hv in hvs))


class AdmissionPolicy:
    """Warm-shape tracking + rung-ladder capping for one service.

    `admit(shape, requested)` caps the shape's next window;
    `note_window(shape, lanes)` marks the dispatched bucket warm after
    the window retires (promotion is EARNED, never assumed — a shed or
    recovered window does not warm its bucket). One scheduler thread
    owns the instance; no locks by design."""

    def __init__(self, rungs: tuple | None = None):
        self.rungs = tuple(sorted(rungs if rungs is not None else RUNGS))
        # shape -> set of buckets proven warm in this process
        self._warm: dict[WindowShape, set] = {}
        self.decisions: dict[str, int] = {"warm": 0, "rung": 0, "host": 0}

    def note_window(self, shape: WindowShape, lanes: int) -> None:
        """A window of this shape retired cleanly at `lanes`: its
        bucket is warm for the rest of the process."""
        self._warm.setdefault(shape, set()).add(bucket_size(lanes))

    def admit(self, shape: WindowShape, requested: int) -> AdmissionDecision:
        """Lane cap for this shape's next window.

        Warm bucket -> full size. Cold -> the rung ladder: one rung
        past the largest bucket this shape has earned, else the largest
        rung, until the requested bucket is reachable. With the device
        plane kill-switched (OCT_SERVE_DEVICE=0) every shape is
        mode="host": the host fold has no compile wall to climb."""
        requested = max(1, int(requested))
        bucket = bucket_size(requested)
        if os.environ.get(_DEVICE_ENV, "1") == "0":
            self.decisions["host"] += 1
            return AdmissionDecision("host", requested, bucket)
        warm = sorted(self._warm.get(shape, ()))
        if bucket in warm:
            self.decisions["warm"] += 1
            return AdmissionDecision("warm", requested, bucket)
        if warm:
            # the ladder positions are the rungs plus the requested
            # bucket as its top
            ladder = sorted({*self.rungs, bucket})
            cap = next((r for r in ladder if r > warm[-1]), bucket)
        else:
            cap = self.rungs[-1]
        cap = min(requested, cap)
        self.decisions["rung"] += 1
        return AdmissionDecision("rung", cap, bucket_size(cap))
