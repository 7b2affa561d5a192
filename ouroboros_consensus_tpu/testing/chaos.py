"""Deterministic fault injection: every death mode, reproducible on CPU.

Rounds r02-r05 each died a DIFFERENT death — probe timeout, ~410 s
compile wall, AOT format rejection, driver kill — and every one was
only ever observed on live hardware, where it cost a session. This
module makes each of those modes an injectable, seeded, deterministic
event so the recovery plane (obs/recovery.py) is proven against them
in tier-1, on CPU, in milliseconds.

Armed by ``OCT_CHAOS=<spec>``; the spec is a comma-separated list of
injections, each ``<fault>@<trigger>:<arg>`` (the trigger clause is
optional for fault kinds that need none):

    compile-stall@window:3        sleep OCT_CHAOS_STALL_S at the 3rd
                                  dispatched window (a simulated wall)
    compile-stall@stage:ed        ...at stage 'ed's dispatch (pk path)
    device-error@dispatch:2       raise DeviceChaosError at the 2nd
                                  window dispatch (fake XlaRuntimeError)
    device-error@stage:finish     ...inside _stage_call for 'finish'
    device-error@shard:0          ...at the 0th sharded dispatch
    staging-thread-death@window:5 raise inside prepare_window for the
                                  5th staged window (producer thread)
    sigkill@window:7              SIGKILL self when the 7th window
                                  retires (AFTER its checkpoint lands)
    chunk-corrupt@epoch:1         raise ChunkChaosError on the 2nd
                                  chunk read (index 1; chunk index
                                  stands in for the epoch on the
                                  synthesized chains, one chunk/epoch)
    aot-reject@stage:aggregate    ops/pk/aot.load reports the entry
                                  rejected ("incompatible" class) for
                                  any stage whose name contains the arg
    probe-timeout                 bench's device probe hangs past its
                                  timeout (one attempt per injection;
                                  list it twice to kill two attempts)

Write-path faults (the durable-store matrix, PR 13) land at the chunk
writer's seam (`ImmutableDB.append_block` consumes them via
`write_fault()` and owns the disk mutation) and the marker writer's
(`storage/guard.write_clean_marker`):

    torn-write@append:4           the 5th block append crashes mid-
                                  write: a PREFIX of the block lands
                                  in the chunk, no index entry, and
                                  the writer dies (TornWriteChaos)
    bitflip@chunk:2               silent bit rot: one byte of a block
                                  appended into chunk 2 flips on disk;
                                  the write "succeeds" and the writer
                                  carries on (the index CRC records
                                  the truth, so a deep walk catches it)
    index-truncate@epoch:1        the chunk-1 index file is torn mid-
                                  entry right after an append lands,
                                  and the writer dies (IndexTornChaos)
    sigkill@append:3              SIGKILL self between the 4th block's
                                  chunk append and its index append —
                                  a REAL kill leaving the index lagging
    partial-rename@marker         the clean-shutdown marker write dies
                                  between the tmp write and the atomic
                                  rename (PartialRenameChaos): durable
                                  tmp, no marker — the next open is
                                  dirty (optionally @marker:clean to
                                  name a specific marker)

Columnar-sidecar faults (PR 17) land at the sidecar writer's and
freshness probe's seams (`storage/sidecar.write_sidecar` /
`load_sidecar` consume them via `sidecar_fault()` and own the
semantics — a fault here may NEVER change a replay verdict, only
force the parse fallback):

    sidecar-torn@build:2          the 3rd sidecar build bypasses the
                                  tmp+rename protocol and lands a torn
                                  prefix at the final name; the probe
                                  must reject it by seal
    sidecar-stale@open:0          the 1st freshness probe reports
                                  stale regardless of the seal — the
                                  replay falls back to parse and (a
                                  writer open) rebuilds
    sigkill@build:1               SIGKILL self between the 2nd sidecar
                                  build's tmp write and its rename —
                                  only the durable tmp survives (the
                                  next open sweeps it)

Forge-pipeline faults (PR 18) land at the batched synthesizer's seams
(`protocol/forge.py`): the per-window election dispatch and the
per-forged-block retire (after the append + state fold land, before
the next block is forged):

    device-error@forge-dispatch:0 raise DeviceChaosError at the 1st
                                  window's leader-election dispatch;
                                  the forge recovery ladder retries,
                                  then drops to the exact host loop
    sigkill@forge:10              SIGKILL self right after the 11th
                                  forged block's append lands — the
                                  store reopens dirty and resume=True
                                  must converge byte-identically

Serving-plane faults (PR 20) land at the continuous-batching
scheduler's seams (`node/serve.py`): the shared-window dispatch and
the per-retired-window checkpoint:

    device-error@serve-dispatch:2 raise DeviceChaosError at the 3rd
                                  shared serving window's dispatch;
                                  every affected tenant segment sheds
                                  down the recovery ladder (degraded-
                                  mode serving, byte-identical verdicts,
                                  no tenant dropped)
    sigkill@serve:10              SIGKILL self right after the 11th
                                  serving window's checkpoint lands —
                                  the relaunched service resumes every
                                  tenant's fold state and banked
                                  verdicts from the progress record

Triggers are matched against per-seam sequence counters (each seam
counts its own firings from 0 in dispatch order) or, for ``stage:``,
by substring against the stage label. Each injection fires EXACTLY
once (append ``xN`` to the arg for N firings: ``device-error@dispatch:
2x3``), so a retried operation succeeds — chaos faults are transient
by construction, which is precisely the contract the recovery ladder
is allowed to assume (COVERAGE.md §5.16 for what that excludes).

Determinism: the spec and the per-seam counters fully determine WHERE
every fault lands; ``OCT_CHAOS_SEED`` seeds the one RNG exposed here
(`rng()`), used for backoff jitter by consumers that want reproducible
recovery timing, never for fault placement.

Zero overhead disarmed: every seam is ``chaos.fire(site, ...)`` whose
first instruction checks a module bool refreshed from the env once per
process (and by `reset()` in tests); with OCT_CHAOS unset the call is
one attribute load + a falsy test, entirely host-side — the
instrumentation-purity ratchet proves the seams add no equations to
any traced program (tests/test_chaos.py)."""

from __future__ import annotations

import os
import random
import threading
import time

_ENV = "OCT_CHAOS"
_SEED_ENV = "OCT_CHAOS_SEED"
_STALL_ENV = "OCT_CHAOS_STALL_S"

FAULT_KINDS = (
    "compile-stall",
    "device-error",
    "staging-thread-death",
    "sigkill",
    "chunk-corrupt",
    "aot-reject",
    "probe-timeout",
    # write-path faults (the durable-store torn-write/bit-rot matrix)
    "torn-write",
    "bitflip",
    "index-truncate",
    "partial-rename",
    # columnar-sidecar faults (storage/sidecar.py; verdict-neutral by
    # contract — they may only force the parse fallback)
    "sidecar-torn",
    "sidecar-stale",
)

# which seam(s) each fault kind is checked at — fire(site) only
# consults injections mapped to that site, so a spec can never detonate
# at a seam its fault kind does not model
_KIND_SITES = {
    "compile-stall": ("dispatch", "stage-call"),
    "device-error": ("dispatch", "stage-call", "shard", "forge-dispatch",
                     "serve-dispatch"),
    "staging-thread-death": ("stage",),
    "sigkill": ("retire", "append", "sidecar-build", "forge", "serve"),
    "chunk-corrupt": ("chunk",),
    "aot-reject": ("aot",),
    "probe-timeout": ("probe",),
    # the chunk writer's seam (write_fault in append_block) and the
    # marker writer's (guard.write_clean_marker)
    "torn-write": ("append",),
    "bitflip": ("append",),
    "index-truncate": ("append",),
    "partial-rename": ("marker",),
    # the sidecar writer's seam (sidecar_fault in write_sidecar) and
    # the freshness probe's (load_sidecar)
    "sidecar-torn": ("sidecar-build",),
    "sidecar-stale": ("sidecar-open",),
}

# the trigger keys each seam actually provides (its explicit ctx= kwargs
# plus its _SITE_SEQ_KEYS) — parse_spec refuses a trigger no seam of the
# fault's kind can ever satisfy: such a spec would arm and then silently
# never fire, exactly the fake-green matrix the fail-loud rule forbids
_SITE_TRIGGER_KEYS = {
    "dispatch": ("window", "dispatch"),
    "stage-call": ("stage",),
    "stage": ("window",),
    "retire": ("window",),
    "shard": ("shard",),
    "chunk": ("chunk",),
    "append": ("append", "chunk"),
    "aot": ("stage",),
    "marker": ("marker",),
    "probe": ("attempt",),
    "sidecar-build": ("build", "chunk"),
    "sidecar-open": ("open", "chunk"),
    "forge": ("forge",),
    "forge-dispatch": ("forge-dispatch",),
    "serve": ("serve",),
    "serve-dispatch": ("serve-dispatch",),
}


class ChaosError(RuntimeError):
    """Base of the injected-fault taxonomy. Transient by contract:
    the injection that raised it is spent, so a retry succeeds."""


class DeviceChaosError(ChaosError):
    """Stands in for a runtime device error (XlaRuntimeError class)."""


class StagingChaosError(ChaosError):
    """The staging producer thread died mid-prepare."""


class ChunkChaosError(ChaosError):
    """A chunk read/extract came back corrupted (transient I/O)."""


class TornWriteChaos(ChaosError):
    """A block append crashed mid-write: a torn prefix is on disk."""


class IndexTornChaos(ChaosError):
    """The secondary index was torn mid-entry after an append."""


class PartialRenameChaos(ChaosError):
    """A marker write died between the tmp write and the rename."""


class AotRejectChaos(ChaosError):
    """An AOT store entry is rejected as format-incompatible. The
    message deliberately matches ops/pk/aot.INCOMPATIBLE_PATTERNS so
    the real classification machinery sees the real failure shape."""

    def __init__(self, stage: str):
        super().__init__(
            f"serialized executable is incompatible (chaos-injected "
            f"rejection for stage {stage})"
        )


# wildcard arg: "any value at this trigger key" — only the grammar
# forms that document it (partial-rename@marker) may parse to this
ANY = object()


class _Injection:
    __slots__ = ("kind", "trigger", "arg", "count", "fired")

    def __init__(self, kind: str, trigger: str | None, arg, count: int):
        self.kind = kind
        self.trigger = trigger  # "window"|"dispatch"|"stage"|"epoch"|
        # "shard"|"append"|"marker"|None — the ctx key the seam
        # matches against
        self.arg = arg  # int seq / str stage-substring / ANY / None
        self.count = count  # firings remaining
        self.fired = 0

    def matches(self, ctx: dict) -> bool:
        if self.count <= 0:
            return False
        if self.trigger is None:
            return True
        if self.trigger not in ctx:
            return False
        if self.arg is ANY:
            return True
        v = ctx[self.trigger]
        if isinstance(self.arg, str):
            return self.arg in str(v)
        return v == self.arg

    def spend(self) -> None:
        self.count -= 1
        self.fired += 1

    def describe(self) -> str:
        if self.trigger is None:
            return self.kind
        if self.arg is ANY:
            return f"{self.kind}@{self.trigger}"
        return f"{self.kind}@{self.trigger}:{self.arg}"


class ChaosPlan:
    """Parsed OCT_CHAOS spec + the per-seam sequence counters."""

    def __init__(self, injections: list[_Injection], seed: int):
        self.injections = injections
        self.seed = seed
        self._lock = threading.Lock()
        self._counters: dict[str, int] = {}
        self._by_site: dict[str, list[_Injection]] = {}
        for inj in injections:
            for site in _KIND_SITES[inj.kind]:
                self._by_site.setdefault(site, []).append(inj)

    def next_seq(self, site: str) -> int:
        with self._lock:
            n = self._counters.get(site, 0)
            self._counters[site] = n + 1
            return n

    def for_site(self, site: str) -> list[_Injection]:
        return self._by_site.get(site, ())

    def fired(self) -> list[str]:
        return [i.describe() for i in self.injections if i.fired]


def parse_spec(spec: str) -> list[_Injection]:
    """Parse the OCT_CHAOS grammar; raises ValueError on a malformed
    spec — an unparseable chaos plan must fail LOUDLY, a typo'd fault
    that silently never fires would fake a green chaos matrix."""
    out: list[_Injection] = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        kind, _, tail = part.partition("@")
        kind = kind.strip()
        if kind not in _KIND_SITES:
            raise ValueError(
                f"OCT_CHAOS: unknown fault kind {kind!r} "
                f"(know {', '.join(FAULT_KINDS)})"
            )
        trigger: str | None = None
        arg = None
        count = 1
        if tail and kind == "probe-timeout":
            # a trigger clause here would be SILENTLY unhonored
            # (probe_timeout_pending spends injections in list order) —
            # reject it loudly instead of misplacing the fault
            raise ValueError(
                "OCT_CHAOS: probe-timeout takes no @trigger clause "
                "(list it N times to kill N attempts)"
            )
        if tail:
            trigger, _, argtxt = tail.partition(":")
            trigger = trigger.strip()
            argtxt = argtxt.strip()
            if "x" in argtxt and argtxt.rsplit("x", 1)[1].isdigit():
                argtxt, _, n = argtxt.rpartition("x")
                count = int(n)
            if not argtxt and kind == "partial-rename" and trigger == "marker":
                # the documented no-arg form: ANY marker write (there
                # is normally exactly one — the clean-shutdown marker)
                arg = ANY
            elif not trigger or not argtxt:
                # an empty arg would parse as the match-ANYTHING ''
                # substring — a silently mis-placed fault, exactly what
                # the fail-loud rule exists to prevent
                raise ValueError(
                    f"OCT_CHAOS: {part!r} has an empty trigger or arg "
                    "(want <fault>@<trigger>:<arg>)"
                )
            else:
                arg = int(argtxt) if argtxt.lstrip("-").isdigit() else argtxt
            if trigger == "epoch":  # chunk index stands in for epoch
                trigger = "chunk"
        elif kind == "probe-timeout":
            trigger, arg, count = "attempt", None, 1
        else:
            raise ValueError(
                f"OCT_CHAOS: fault {kind!r} needs a @trigger:arg clause"
            )
        if arg is not None and trigger is not None:
            satisfiable = {
                k for site in _KIND_SITES[kind]
                for k in _SITE_TRIGGER_KEYS.get(site, ())
            }
            if trigger not in satisfiable:
                raise ValueError(
                    f"OCT_CHAOS: {part!r} can never fire — trigger "
                    f"{trigger!r} is not provided at any {kind!r} seam "
                    f"(know: {', '.join(sorted(satisfiable))})"
                )
        out.append(_Injection(kind, trigger if arg is not None else None,
                              arg, count))
    return out


_ARMED = False
_PLAN: ChaosPlan | None = None
_RNG: random.Random | None = None


def _load() -> None:
    global _ARMED, _PLAN, _RNG
    spec = os.environ.get(_ENV, "")
    seed = int(os.environ.get(_SEED_ENV, "0") or 0)
    _RNG = random.Random(seed)
    if not spec:
        _ARMED, _PLAN = False, None
        return
    _PLAN = ChaosPlan(parse_spec(spec), seed)
    _ARMED = True


_load()


def reset() -> None:
    """Re-read OCT_CHAOS / OCT_CHAOS_SEED and zero every counter
    (tests arm/disarm per case; production reads the env once)."""
    _load()


def armed() -> bool:
    return _ARMED


def plan() -> ChaosPlan | None:
    return _PLAN


def rng() -> random.Random:
    """The seeded RNG — backoff jitter determinism for consumers
    (obs/recovery.py, bench probe), never fault placement."""
    assert _RNG is not None
    return _RNG


def jitter() -> float:
    """The one backoff-jitter policy every recovery consumer shares
    (obs/recovery.RecoverySupervisor, bench's probe retries): a
    multiplicative factor in [1.0, 1.5), drawn from the seeded chaos
    RNG when armed — reproducible recovery timing under a seeded fault
    plan — and the process RNG otherwise."""
    r = rng() if _ARMED else random
    return 1.0 + 0.5 * r.random()


def stall_s() -> float:
    try:
        return float(os.environ.get(_STALL_ENV, "0.2"))
    except ValueError:
        return 0.2


def _execute(inj: _Injection, site: str, ctx: dict) -> None:
    inj.spend()
    where = f"{site} {ctx}" if ctx else site
    if inj.kind == "compile-stall":
        time.sleep(stall_s())
        return
    if inj.kind == "device-error":
        raise DeviceChaosError(f"chaos: injected device error at {where}")
    if inj.kind == "staging-thread-death":
        raise StagingChaosError(f"chaos: staging producer died at {where}")
    if inj.kind == "chunk-corrupt":
        raise ChunkChaosError(f"chaos: chunk read corrupted at {where}")
    if inj.kind == "aot-reject":
        raise AotRejectChaos(str(ctx.get("stage", "?")))
    if inj.kind == "partial-rename":
        # the marker writer already wrote (and fsynced) the tmp file;
        # raising HERE models the crash between tmp and rename — the
        # durable tmp survives, the final marker never appears
        raise PartialRenameChaos(
            f"chaos: marker rename died at {where}"
        )
    if inj.kind == "sigkill":
        import signal

        os.kill(os.getpid(), signal.SIGKILL)
    # probe-timeout is consumed by bench.probe_device via
    # probe_timeout_pending(), never raised at a seam


# which trigger keys each seam's OWN sequence counter may answer for:
# a seam only ever defaults its canonical aliases, so an injection
# whose trigger names ANOTHER seam (device-error@dispatch:N vs the
# stage-call seam both sites of the same fault kind) can never match
# off this seam's counter — the spec and the per-seam counters fully
# determine WHERE every fault lands, which is the module's contract
_SITE_SEQ_KEYS = {
    "dispatch": ("window", "dispatch"),  # one dispatch per window
    "stage": ("window",),  # prepare_window: one staging per window
    "retire": ("window",),  # one retire per window
    "shard": ("shard",),
    "chunk": ("chunk",),
    "append": ("append",),  # one block append per seq (write_fault);
    # the CHUNK NUMBER rides the explicit chunk= ctx, so bitflip@chunk:N
    # and index-truncate@epoch:N place by chunk, torn-write@append:N and
    # sigkill@append:N by append order
    # "stage-call" / "aot" match only on the explicit stage= ctx;
    # "marker" matches only on the explicit marker= ctx;
    # "probe" is consumed via probe_timeout_pending()
    "sidecar-build": ("build",),  # one sidecar build per seq; the
    # CHUNK NUMBER rides the explicit chunk= ctx (sidecar-torn@chunk:N)
    "sidecar-open": ("open",),  # one freshness probe per seq
    "forge": ("forge",),  # one forged-block retire per seq
    "forge-dispatch": ("forge-dispatch",),  # one election dispatch/seq
    "serve": ("serve",),  # one serving-window checkpoint per seq
    "serve-dispatch": ("serve-dispatch",),  # one shared window per seq
}


def _match(site: str, ctx: dict):
    """THE injection matcher — one implementation of the semantics
    every seam shares (armed check, per-site plan lookup, sequence
    advance, _SITE_SEQ_KEYS defaulting, first un-spent match). Returns
    ``(injection, seq)`` or None; the caller decides what a match DOES
    (fire() executes it, write_fault() hands its kind to the writer).
    The sequence counter only advances when the plan has injections at
    this site, so a disarmed or unrelated run never drifts counters."""
    if not _ARMED:
        return None
    p = _PLAN
    if p is None:
        return None
    injections = p.for_site(site)
    if not injections:
        return None
    seq = p.next_seq(site)
    full = dict(ctx)
    for k in _SITE_SEQ_KEYS.get(site, ()):
        full.setdefault(k, seq)
    for inj in injections:
        if inj.matches(full):
            return inj, seq
    return None


def fire(site: str, **ctx) -> None:
    """The one seam entry point. Cheap no-op disarmed (module bool);
    armed, the first matching un-spent injection (`_match`) is
    executed — raise / sleep / kill per its fault kind."""
    m = _match(site, ctx)
    if m is not None:
        inj, seq = m
        _execute(inj, site, ctx or {"seq": seq})


def write_fault(**ctx) -> str | None:
    """The chunk writer's seam (`ImmutableDB.append_block`): matching
    identical to `fire()` at the ``append`` site (`_match`), but the
    injection's KIND is returned instead of executed — the writer owns
    the disk-mutation semantics (a torn prefix for ``torn-write``, a
    flipped byte for ``bitflip``, a torn index entry for
    ``index-truncate``, a SIGKILL between the chunk and index appends
    for ``sigkill@append``). None = no fault this append."""
    m = _match("append", ctx)
    if m is None:
        return None
    inj, _seq = m
    inj.spend()
    return inj.kind


def sidecar_fault(site: str, **ctx) -> str | None:
    """The columnar-sidecar seams (`storage/sidecar.write_sidecar` at
    ``sidecar-build``, `load_sidecar` at ``sidecar-open``): matching
    identical to `fire()` (`_match`), but the injection's KIND is
    returned instead of executed — the sidecar module owns the
    semantics (a torn prefix at the final name for ``sidecar-torn``, a
    SIGKILL between tmp and rename for ``sigkill@build``, a forced
    stale verdict for ``sidecar-stale``). None = no fault here."""
    m = _match(site, ctx)
    if m is None:
        return None
    inj, _seq = m
    inj.spend()
    return inj.kind


def probe_timeout_pending() -> bool:
    """bench.probe_device's seam: True (and one injection consumed)
    when the next probe attempt should hang past its timeout."""
    if not _ARMED or _PLAN is None:
        return False
    for inj in _PLAN.for_site("probe"):
        if inj.count > 0:
            inj.spend()
            return True
    return False
