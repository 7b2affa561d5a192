"""Device-batched chain synthesis: forge at the speed you verify.

Reference: the `runForge` loop (Tools/DBSynthesizer/Forging.hs:54-57)
checks leadership per slot per credential and forges the winner — a
strictly sequential host loop. The TPU build splits that loop into the
part with no chain dependency and the part with one:

  * **Leader election has no chain dependency.** The VRF input is
    `mkInputVRF(slot, eta0)` (Praos/VRF.hs:47) and eta0 is
    epoch-constant, so the election for EVERY (slot, pool) pair of a
    window is one packed dispatch: `forge_sweep` evaluates
    `ops/ecvrf_batch.prove` over the pools×slots grid and brackets the
    leader value against the per-pool thresholds on device (the same
    two-threshold bracket the verify side dispatches), scattering the
    elected (slot, pool) pairs back as a host column. The host resolves
    only the ambiguous band exactly (empty in practice).

  * **Header assembly keeps one chain dependency.** Each body embeds
    the previous header's hash INSIDE the KES-signed bytes, so the
    per-block leaf signature is inherently sequential. Everything else
    is hoisted: OCert issue signatures dedup per (pool, counter,
    evolution-window) — `forge_sign` batches them on device — and the
    KES leaf seed + sibling path per (pool, period) are
    message-independent (`ops/host/kes.leaf_path`), leaving splice →
    leaf-sign → hash as the only per-block tail.

Engines (`engine_from_env`): "device" dispatches the packed sweep,
"host" runs the same staged election with native per-pair proves and
vectorized threshold compares, "loop" (`OCT_FORGE_DEVICE=0`) is the
untouched per-slot reference loop in tools/db_synthesizer. All three
are byte-identical for the same seed/params (tests/test_forge.py).

On the chip (`batch._impl() == "pk"`) the device engine is the
LEADER-VALUE sweep (`LeaderSweep`, ops/pk/elect.py): a pair's
leadership needs only beta, one variable-base multiplication where a
proof needs three, so the grid is bracketed on the verify side's own
ladder, two bits a pair come back, and the host proves the pairs that
won. 512 pools x 86,400 slots is 4.4e7 pairs: hours of host proves,
about 100 s of that sweep. `synthesize(elector=...)` takes the elected
(slot, pool) rows from elsewhere, so the election can run in the
process that holds the chip and the assembly in another.

Failure citizenship: election dispatches ride a recovery ladder
(retry → host-reference exact loop, obs/recovery.py vocabulary) and
carry the `forge-dispatch` / `forge` chaos seams (testing/chaos.py).
"""

from __future__ import annotations

import os
import threading
import time
from fractions import Fraction
from typing import NamedTuple

import numpy as np

from ..block.forge import evaluate_vrf
from ..ops.host import fast
from ..ops.host import kes as host_kes
from ..testing import chaos
from ..utils.trace import RecoveryEvent
from . import nonces
from .leader import check_leader_value
from .praos import PraosIsLeader, PraosParams
from .views import LedgerView

_ENV_DEVICE = "OCT_FORGE_DEVICE"

# one packed dispatch's lane count (the jit caches exactly one shape);
# module-level so the differential tests can shrink it
FORGE_BUCKET = 4096


def engine_from_env(vrf_backend: str = "auto") -> str:
    """Resolve the forging engine: the OCT_FORGE_DEVICE lever wins
    ("1" = packed device sweep, "0" = the per-slot reference loop);
    unset, the synthesizer's vrf_backend picks device and everything
    else lands on the batched host engine (the default fast path)."""
    v = os.environ.get(_ENV_DEVICE, "").strip()
    if v == "0":
        return "loop"
    if v == "1":
        return "device"
    if vrf_backend == "device":
        return "device"
    return "host"


class Elected(NamedTuple):
    """One won slot scattered back from the election sweep."""

    slot: int
    pool: int  # index into the credentials list (first winner per slot)
    is_leader: PraosIsLeader


# ---------------------------------------------------------------------------
# Registry graphs (analysis/graphs.py: forge_sweep / forge_sign)
# ---------------------------------------------------------------------------


def forge_sweep(x, prefix, pk, slots, nonce, thr_lo, thr_hi):
    """The leader-election sweep kernel: one packed dispatch electing a
    pools×slots grid. alpha = mkInputVRF(slot, eta0) on device
    (alpha_from_slots — byte-identical to the host), the full VRF prove
    (both proof serializations come back as columns), then the verify
    side's leader tail: lv = Blake2b("L" ‖ beta) bracketed against the
    per-pair thresholds with the cumsum `_lt_be` compare.

    x/prefix/pk/nonce/thr_* are [B, 32] / [32] int32 byte arrays,
    slots [B] int32. Returns the five proof columns + beta plus the
    [B] win/ambiguous verdict bitmaps (ambiguous lanes get the exact
    host Fraction check — the same division of labor as verify)."""
    import jax.numpy as jnp

    from ..ops import blake2b, ecvrf_batch
    from .batch import _lt_be

    alpha = ecvrf_batch.alpha_from_slots(slots, nonce)
    g_enc, c16, u_enc, v_enc, s32, beta = ecvrf_batch.prove(
        x, prefix, pk, alpha
    )
    tag_l = jnp.broadcast_to(
        jnp.asarray([ord("L")], jnp.int32), (*beta.shape[:-1], 1)
    )
    lv = blake2b.blake2b_fixed(
        jnp.concatenate([tag_l, beta], axis=-1), 65, 32
    )
    thr_lo = jnp.asarray(thr_lo).astype(jnp.int32)
    thr_hi = jnp.asarray(thr_hi).astype(jnp.int32)
    win = _lt_be(lv, thr_lo)
    ambiguous = ~win & _lt_be(lv, thr_hi)
    return g_enc, c16, u_enc, v_enc, s32, beta, win, ambiguous


def forge_sign(a, a_enc, rblocks, rnblocks, hblocks, hnblocks):
    """The packed OCert-issue signer: the certified ed25519 sign kernel
    under its forge-lane registry name, so the sign direction of the
    forging pipeline carries its own budget/cost/resource pins at the
    shape the synthesizer dispatches (deduped OCert signables, not
    headers)."""
    from ..ops import ed25519_batch

    return ed25519_batch.sign(a, a_enc, rblocks, rnblocks, hblocks, hnblocks)


# test seam: install_stub_forge (testing/stubs.py) swaps these for
# hash-twin kernels that compile in seconds on XLA:CPU, and resets the
# jit memo — production never touches them
_SWEEP_FN = forge_sweep
_SIGN_FN = forge_sign
_JITS: dict = {}


def _make_sweep_neutral(sweep_fn):
    """The neutral-nonce sweep variant: epoch 0 of a fresh chain (and
    any window before the first epoch transition establishes a real
    nonce) elects under `epoch_nonce=None`, which `alpha_from_slots`
    folds as a STATIC trace-time branch (8-byte alpha input instead of
    40) — the same per-layout staticness the verify side bakes through
    `layout.has_nonce`. A separate traced program under its own stage /
    AOT-store name; `None` cannot ride as a runtime argument (the
    warm-store signature walks arg shapes). A FACTORY for the same
    reason as make_stub_forge_sweep: jax's tracing cache keys on
    function identity, and a module-level wrapper would serve a stale
    install's trace after install_stub_forge swaps the kernel."""

    def sweep_neutral(x, prefix, pk, slots, thr_lo, thr_hi):
        return sweep_fn(x, prefix, pk, slots, None, thr_lo, thr_hi)

    return sweep_neutral


def _jit_of(name: str, fn):
    if name not in _JITS:
        import jax

        from . import batch as pbatch

        _JITS[name] = pbatch._warm_timed(name, jax.jit(fn))
    return _JITS[name]


# ---------------------------------------------------------------------------
# Window staging (host, once per run / per window)
# ---------------------------------------------------------------------------


class PoolStaging(NamedTuple):
    """Per-pool device columns, staged once per synthesis run."""

    x: np.ndarray  # [P, 32] expanded VRF scalars
    prefix: np.ndarray  # [P, 32] nonce prefixes
    pk: np.ndarray  # [P, 32] VRF verification keys


def stage_pools(pools) -> PoolStaging:
    from ..ops import ecvrf_batch

    x, prefix, pk = ecvrf_batch.stage_prove_np([p.vrf_seed for p in pools])
    return PoolStaging(x, prefix, pk)


def stage_engine(params: PraosParams, pools, engine: str):
    """What an engine stages once a run: the leader sweep's device
    columns on the chip, the full-prove sweep's host columns on the XLA
    twin, nothing on the host engine."""
    if engine != "device":
        return None
    if _leader_sweep_enabled():
        return LeaderSweep(params, pools)
    return stage_pools(pools)


def pool_thresholds(params: PraosParams, lview: LedgerView, pools):
    """Per-pool (lo_rows [P,32], hi_rows [P,32], sigmas) — the
    unknown-pool sigma-0 convention and clamped bracket encoding of
    batch._threshold_rows, keyed by the window's ledger view."""
    from . import batch as pbatch

    f = Fraction(params.active_slot_coeff)
    lo_rows, hi_rows, sigmas = [], [], []
    for pool in pools:
        entry = lview.pool_distr.get(pool.pool_id)
        sigma = entry.stake if entry is not None else Fraction(0)
        lo, hi = pbatch._threshold_rows(sigma, f)
        lo_rows.append(lo)
        hi_rows.append(hi)
        sigmas.append(sigma)
    return np.stack(lo_rows), np.stack(hi_rows), sigmas


def window_slots(n_pools: int) -> int:
    """Slots per election window: ~4 packed buckets of (slot, pool)
    pairs — enough to amortize dispatch, small enough that the
    blocks-limit overshoot stays bounded."""
    return max(1, (4 * FORGE_BUCKET) // max(1, n_pools))


# ---------------------------------------------------------------------------
# Election engines
# ---------------------------------------------------------------------------


def _first_winners(params, slots, pools, sigmas, win, amb, lv_rows,
                   beta_of, proof_of) -> list[Elected]:
    """Shared election tail: resolve the ambiguous band with the exact
    Fraction check, then scatter the first winning pool per slot
    (list order — the reference's first-credential-forges rule)."""
    p = len(pools)
    f = params.active_slot_coeff
    for idx in np.nonzero(amb)[0]:
        lv_val = int.from_bytes(bytes(lv_rows[idx]), "big")
        win[idx] = check_leader_value(lv_val, sigmas[idx % p], f)
    winm = win.reshape(len(slots), p)
    has = winm.any(axis=1)
    first = winm.argmax(axis=1)
    out = []
    slots = list(slots)
    for j in np.nonzero(has)[0]:
        i = int(first[j])
        idx = j * p + i
        out.append(
            Elected(
                int(slots[j]), i,
                PraosIsLeader(beta_of(idx), proof_of(idx)),
            )
        )
    return out


def _elect_window_host(params, pools, thr, slots, eta0) -> list[Elected]:
    """Batched host engine: native per-pair proves, then ONE vectorized
    threshold compare over the whole window (the per-pair Fraction
    check — the legacy loop's dominant cost — survives only for the
    ambiguous band)."""
    from . import batch as pbatch

    lo_rows, hi_rows, sigmas = thr
    p = len(pools)
    ns = len(slots)
    b = ns * p
    from ..ops.host.hashes import blake2b_256

    betas: list[bytes] = []
    proofs: list[bytes] = []
    lv_rows = np.empty((b, 32), np.uint8)
    k = 0
    for s in slots:
        alpha = nonces.mk_input_vrf(s, eta0)
        for pool in pools:
            proof = fast.ecvrf_prove(pool.vrf_seed, alpha)
            beta = fast.ecvrf_proof_to_hash(proof)
            proofs.append(proof)
            betas.append(beta)
            lv_rows[k] = np.frombuffer(blake2b_256(b"L" + beta), np.uint8)
            k += 1
    thr_lo = np.tile(lo_rows, (ns, 1))
    thr_hi = np.tile(hi_rows, (ns, 1))
    win = pbatch._lt_be_rows(lv_rows, thr_lo)
    amb = ~win & pbatch._lt_be_rows(lv_rows, thr_hi)
    return _first_winners(
        params, slots, pools, sigmas, win, amb, lv_rows,
        lambda i: betas[i], lambda i: proofs[i],
    )


def _elect_window_device(params, pools, stg: PoolStaging, thr, slots,
                         eta0) -> list[Elected]:
    """Packed device engine: the whole pools×slots grid through
    forge_sweep in FORGE_BUCKET dispatches (padded to one cached
    shape), verdict bitmaps and proof columns scattered back. On the
    chip `stg` is a `LeaderSweep`: bitmaps alone, winners proved here."""
    if isinstance(stg, LeaderSweep):
        return _elect_window_leader(stg, thr, slots, eta0)
    lo_rows, hi_rows, sigmas = thr
    p = len(pools)
    ns = len(slots)
    b = ns * p
    # pair order is slot-major (s0p0, s0p1, s1p0, ...): the first
    # winning POOL per slot must be the list-order first
    x = np.tile(stg.x, (ns, 1))
    prefix = np.tile(stg.prefix, (ns, 1))
    pk = np.tile(stg.pk, (ns, 1))
    slot_col = np.repeat(np.asarray(list(slots), np.int64), p)
    thr_lo = np.tile(lo_rows, (ns, 1))
    thr_hi = np.tile(hi_rows, (ns, 1))
    if eta0 is None:
        # neutral nonce (fresh chain, epoch 0): dispatch the statically
        # nonce-free variant — a distinct compiled program, same family
        sweep = _jit_of("forge_sweep-neutral", _make_sweep_neutral(_SWEEP_FN))
        nonce_args = ()
    else:
        sweep = _jit_of("forge_sweep", _SWEEP_FN)
        nonce_args = (np.frombuffer(eta0, np.uint8),)
    cols = [[] for _ in range(6)]
    win = np.zeros(b, bool)
    amb = np.zeros(b, bool)
    for lo in range(0, b, FORGE_BUCKET):
        n = min(FORGE_BUCKET, b - lo)
        sl = slice(lo, lo + n)

        def pad(a):
            if n == FORGE_BUCKET:
                return a[sl]
            reps = np.concatenate(
                [a[sl], np.repeat(a[lo:lo + 1], FORGE_BUCKET - n, axis=0)]
            )
            return reps

        out = sweep(
            pad(x), pad(prefix), pad(pk),
            pad(slot_col.reshape(-1, 1)).reshape(-1).astype(np.int32),
            *nonce_args, pad(thr_lo), pad(thr_hi),
        )
        for acc, col in zip(cols, out[:6]):
            acc.append(np.asarray(col[:n]).astype(np.uint8))
        win[sl] = np.asarray(out[6][:n])
        amb[sl] = np.asarray(out[7][:n])
    g_enc, c16, u_enc, v_enc, s32, beta = (
        np.concatenate(a) for a in cols
    )
    compat = fast.vrf_batch_compat()
    # lv is re-derived host-side only for the (normally empty)
    # ambiguous band — the device already folded it into win/amb
    from ..ops.host.hashes import blake2b_256

    lv_rows = {
        int(i): np.frombuffer(
            blake2b_256(b"L" + bytes(beta[i])), np.uint8
        )
        for i in np.nonzero(amb)[0]
    }

    def proof_of(i):
        if compat:
            parts = (g_enc[i], u_enc[i], v_enc[i], s32[i])
        else:
            parts = (g_enc[i], c16[i], s32[i])
        return b"".join(bytes(q) for q in parts)

    return _first_winners(
        params, slots, pools, sigmas, win, amb,
        _LazyRows(lv_rows), lambda i: bytes(beta[i]), proof_of,
    )


class _LazyRows:
    """lv rows materialized only for the ambiguous indices."""

    def __init__(self, rows: dict):
        self._rows = rows

    def __getitem__(self, i):
        return self._rows[int(i)]


# lanes of one leader-sweep dispatch: whole slots x every pool, one
# compiled shape a pool count. Module-level so tests can shrink it.
SWEEP_LANES = 1 << 18
# dispatches kept in flight ahead of the one being read
SWEEP_DEPTH = 3
# None: the leader-value sweep is the device engine wherever the
# Pallas kernels are (`batch._impl() == "pk"`); tests set True
LEADER_SWEEP: bool | None = None
# test seam (testing/stubs.install_stub_forge): the sweep program
_LEADER_FN = None
# what each sweep program cost this process: {"name", "lanes", "via"
# ("store" | "built" | "jit"), "wall_s"}, for whoever reports set-up
SWEEP_PROGRAMS: list = []


def _leader_sweep_enabled() -> bool:
    if LEADER_SWEEP is not None:
        return LEADER_SWEEP
    from . import batch as pbatch

    return pbatch._impl() == "pk"


def _sweep_program(args):
    """The compiled leader sweep for these argument shapes: loaded
    from the store of stage programs (ops/pk/aot), else built and, where
    write-back is on, stored, so that a later process loads it. Its
    first execute is no warm-up STAGE note: that vocabulary is the
    replay's ("a first execute outside the per-lane stages" means a
    window left its path); `SWEEP_PROGRAMS` says what it cost."""
    from ..ops.pk import aot, elect
    from ..ops.pk import kernels as pk_kernels

    if _LEADER_FN is not None:  # a stub: the plain jit
        if "leader_sweep-stub" not in _JITS:
            import jax

            _JITS["leader_sweep-stub"] = jax.jit(_LEADER_FN)
        return _JITS["leader_sweep-stub"]
    lanes = elect.sweep_lanes(args[-1].shape[0], args[0].shape[0])
    key = ("leader_sweep", aot.sig_of(args))
    if key in _JITS:
        return _JITS[key]
    name = "elect_" + elect.source_tag()
    t0 = time.monotonic()
    ex, via = None, "jit"
    if aot.enabled():
        ex = aot.load(name, lanes, 0, pk_kernels.TILE, key[1])
        via = "store" if ex is not None else via
    if ex is None and aot.writeback_enabled():
        ex = aot.compile_and_store(name, lanes, 0, pk_kernels.TILE,
                                   elect.jitted_sweep(), args)
        via = "built" if ex is not None else via
    if ex is None:
        ex = elect.jitted_sweep()
    SWEEP_PROGRAMS.append({"name": name, "lanes": lanes, "via": via,
                           "wall_s": round(time.monotonic() - t0, 3)})
    _JITS[key] = ex
    return ex


class LeaderSweep:
    """The election of every (slot, pool) pair on the device, by leader
    value alone (ops/pk/elect.py). The pool columns are staged once and
    stay on the device; a dispatch ships one alpha row a slot and
    brings back two bitmaps. Exact: a pair inside the threshold bracket
    is proved on the host and put to `check_leader_value`, as on the
    verify side."""

    def __init__(self, params: PraosParams, pools):
        import jax

        from ..ops.host import ed25519 as he

        self.params = params
        self.pools = pools
        self.n_slots = max(1, SWEEP_LANES // len(pools))  # a dispatch
        x = np.stack([
            np.frombuffer(he.secret_expand(p.vrf_seed)[0].to_bytes(
                32, "little"), np.uint8) for p in pools])
        pk = np.stack([np.frombuffer(p.vrf_vk, np.uint8) for p in pools])
        self._x, self._pk = jax.device_put(x), jax.device_put(pk)

    def _dispatch(self, thr_dev, chunk, eta0):
        alpha = np.empty((self.n_slots, 32), np.uint8)
        for j, s in enumerate(chunk):
            alpha[j] = np.frombuffer(nonces.mk_input_vrf(s, eta0), np.uint8)
        alpha[len(chunk):] = alpha[0]  # a short last chunk: one shape
        args = (self._x, self._pk, *thr_dev, alpha)
        return _sweep_program(args)(*args)

    def rows(self, thr, slots, eta0):
        """Yield (chunk, [(slot, pool index), ...]) for `slots` in
        order, a dispatch's worth a time: each slot's FIRST winning pool
        in list order (the reference's first-credential-forges rule).
        `thr` is `pool_thresholds(params, lview, pools)`. Keeps
        SWEEP_DEPTH dispatches queued behind the one it waits for."""
        import jax

        lo_rows, hi_rows, sigmas = thr
        thr_dev = (jax.device_put(lo_rows), jax.device_put(hi_rows))
        slots = list(slots)
        chunks = [slots[i:i + self.n_slots]
                  for i in range(0, len(slots), self.n_slots)]
        queued: list = []
        nxt = 0
        while nxt < len(chunks) or queued:
            while nxt < len(chunks) and len(queued) <= SWEEP_DEPTH:
                queued.append(
                    (chunks[nxt], self._dispatch(thr_dev, chunks[nxt], eta0)))
                nxt += 1
            chunk, out = queued.pop(0)
            yield chunk, self._first_winners(chunk, out, sigmas, eta0)

    def _first_winners(self, chunk, out, sigmas, eta0):
        p = len(self.pools)
        win, amb = (np.unpackbits(np.asarray(a), axis=1)[:len(chunk), :p]
                    .astype(bool) for a in out)
        f = self.params.active_slot_coeff
        for j, i in zip(*np.nonzero(amb)):  # empty in practice
            beta = evaluate_vrf(self.pools[i], chunk[j], eta0).vrf_output
            win[j, i] = check_leader_value(
                nonces.vrf_leader_value(beta), sigmas[i], f)
        first = win.argmax(axis=1)
        return [(int(chunk[j]), int(first[j]))
                for j in np.nonzero(win.any(axis=1))[0]]


def elected_from_rows(pools, rows, eta0) -> list[Elected]:
    """The elected (slot, pool index) rows of an election made
    elsewhere, each proved here: the winners alone, ~1 pair in 1000."""
    return [Elected(int(s), int(i), evaluate_vrf(pools[i], int(s), eta0))
            for s, i in rows]


def _elect_window_leader(sweep: LeaderSweep, thr, slots,
                         eta0) -> list[Elected]:
    rows = [r for _chunk, part in sweep.rows(thr, slots, eta0)
            for r in part]
    return elected_from_rows(sweep.pools, rows, eta0)


def _elect_window_reference(params, pools, lview, slots,
                            eta0) -> list[Elected]:
    """The exact host reference: per-slot, per-pool prove + Fraction
    leader check — the recovery ladder's floor (and the legacy loop's
    election semantics, verbatim)."""
    out = []
    f = params.active_slot_coeff
    for s in slots:
        alpha = nonces.mk_input_vrf(s, eta0)
        for i, pool in enumerate(pools):
            proof = fast.ecvrf_prove(pool.vrf_seed, alpha)
            is_leader = PraosIsLeader(
                fast.ecvrf_proof_to_hash(proof), proof
            )
            lv_val = nonces.vrf_leader_value(is_leader.vrf_output)
            entry = lview.pool_distr.get(pool.pool_id)
            if entry is None:
                continue
            if not check_leader_value(lv_val, entry.stake, f):
                continue
            out.append(Elected(int(s), i, is_leader))
            break
    return out


def tpraos_delegate_credentials(lview, pools) -> list[int]:
    """Index into `pools` of each genesis delegate's credentials, in the
    ledger view's order (an overlay slot's block is its delegate's)."""
    by_cold = {p.vk_cold: i for i, p in enumerate(pools)}
    try:
        return [by_cold[d.vk_cold] for d in lview.gen_delegs]
    except KeyError:
        raise ValueError(
            "a genesis delegate of the ledger view has no credentials "
            "among the forging pools"
        ) from None


def elect_slot_tpraos(params, lview, pools, slot: int, eta0,
                      deleg_creds=None) -> Elected | None:
    """Who forges `slot` of a TPraos chain, and both its certificates:
    an active overlay slot is its genesis delegate's, an inactive one
    stays empty, any other slot is the lottery's (the first pool in list
    order whose raw 64-byte leader output wins under 2^512).
    `deleg_creds` is `tpraos_delegate_credentials(lview, pools)` where
    the caller elects many slots."""
    from . import tpraos

    assign = tpraos.overlay_slot_assignment(
        params, len(lview.gen_delegs), slot)
    if assign is not None:
        active, j = assign
        if not active:
            return None
        if deleg_creds is None:
            deleg_creds = tpraos_delegate_credentials(lview, pools)
        i = deleg_creds[j]
        return Elected(slot, i, tpraos.prove_certificates(
            pools[i].vrf_seed, slot, eta0))
    f = params.active_slot_coeff
    for i, pool in enumerate(pools):
        entry = lview.pool_distr.get(pool.pool_id)
        if entry is None:
            continue  # no stake this epoch (every delegate: none ever)
        leader = tpraos.leader_certificate(pool.vrf_seed, slot, eta0)
        if tpraos.wins_lottery(leader[0], entry.stake, f):
            return Elected(slot, i, tpraos.prove_certificates(
                pool.vrf_seed, slot, eta0, leader))
    return None


def elect_window_tpraos(params, lview, pools, slots, eta0) -> list[Elected]:
    """A window's TPraos election, on the host: `elect_slot_tpraos` a
    slot (one leader proof a lottery slot and pool; N-pool chains want
    the leader-value kernel, ops/pk/elect.py, taught this protocol's
    value: ROADMAP Reach 5)."""
    deleg_creds = tpraos_delegate_credentials(lview, pools)
    out = []
    for s in slots:
        el = elect_slot_tpraos(params, lview, pools, s, eta0, deleg_creds)
        if el is not None:
            out.append(el)
    return out


def elect_window(params, pools, stg, thr, slots, eta0,
                 engine: str) -> list[Elected]:
    """One window's election dispatch (the `forge-dispatch` chaos
    seam lives here — a window dispatch is the recovery ladder's unit
    of retry)."""
    chaos.fire("forge-dispatch")
    if engine == "device":
        return _elect_window_device(params, pools, stg, thr, slots, eta0)
    return _elect_window_host(params, pools, thr, slots, eta0)


def elect_window_recovering(params, pools, stg, thr, slots, eta0,
                            engine: str, lview, window: int,
                            tracer=None) -> list[Elected]:
    """The forge arm of the PR 12 recovery ladder: a failing election
    dispatch is retried once (chaos faults are transient by contract;
    so are real device hiccups worth one retry), then dropped to the
    exact host reference loop — the floor that cannot fail for device
    reasons. Every transition emits a RecoveryEvent so the episode is
    countable (oct_recovery_total{action=})."""
    lanes = len(slots) * len(pools)

    def emit(ev):
        if tracer is not None:
            tracer(ev)

    try:
        return elect_window(params, pools, stg, thr, slots, eta0, engine)
    except Exception as e:  # noqa: BLE001 — ladder owns classification
        emit(RecoveryEvent(
            action="retry", window=window, lanes=lanes, attempt=1,
            fault=type(e).__name__, detail=repr(e)[:200],
        ))
        try:
            out = elect_window(
                params, pools, stg, thr, slots, eta0, engine
            )
            emit(RecoveryEvent(
                action="recovered", window=window, lanes=lanes,
                attempt=2, fault=type(e).__name__,
                detail=repr(e)[:200], ok=True,
            ))
            return out
        except Exception as e2:  # noqa: BLE001
            emit(RecoveryEvent(
                action="host-reference", window=window, lanes=lanes,
                attempt=2, fault=type(e2).__name__,
                detail=repr(e2)[:200],
            ))
            out = _elect_window_reference(params, pools, lview, slots, eta0)
            emit(RecoveryEvent(
                action="recovered", window=window, lanes=lanes,
                attempt=3, fault=type(e2).__name__,
                detail=repr(e2)[:200], ok=True,
            ))
            return out


# ---------------------------------------------------------------------------
# Batched assembly (the sequential tail, with everything hoistable hoisted)
# ---------------------------------------------------------------------------

_SIGN_BUCKET = 16


def sign_ocerts_batch(pools, triples) -> dict:
    """Batch-sign the deduped OCert signables through the forge_sign
    graph: {(pool_i, counter, kes_period): OCert}. The ed25519 sign
    kernel is octrange-certified byte-identical to the host signer, so
    this swap preserves chain bytes."""
    from ..ops import ed25519_batch
    from .views import OCert

    triples = sorted(triples)
    if not triples:
        return {}
    seeds, msgs, protos = [], [], []
    for pool_i, counter, kp0 in triples:
        pool = pools[pool_i]
        oc = OCert(pool.kes_vk, counter, kp0, b"")
        seeds.append(pool.cold_seed)
        msgs.append(oc.signable())
        protos.append(oc)
    pad = (-len(seeds)) % _SIGN_BUCKET
    seeds.extend([seeds[0]] * pad)
    msgs.extend([msgs[0]] * pad)
    batch = ed25519_batch.stage_sign_np(seeds, msgs)
    sign = _jit_of("forge_sign", _SIGN_FN)
    r_enc, s = sign(*batch)
    sigs = np.concatenate(
        [np.asarray(r_enc), np.asarray(s)], axis=-1
    ).astype(np.uint8)
    return {
        key: OCert(oc.vk_hot, oc.counter, oc.kes_period, bytes(sigs[i]))
        for i, (key, oc) in enumerate(zip(triples, protos))
    }


class BlockAssembler:
    """The sequential forge tail with the message-independent work
    cached: OCert issue signatures per (pool, counter,
    evolution-window) and KES leaf seed + vk + sibling path per
    (pool, period). What remains per block — CBOR body with the
    previous hash spliced in, one leaf ed25519 sign, one Blake2b — is
    the irreducible chain dependency (COVERAGE.md §forge)."""

    def __init__(self, params: PraosParams, pools):
        self.params = params
        self.pools = pools
        self._ocerts: dict = {}
        self._leaves: dict = {}

    def ocert_window(self, slot: int) -> int:
        kp = self.params.kes_period_of(slot)
        return max(0, kp - (kp % self.params.max_kes_evolutions))

    def prime_ocerts(self, signed: dict) -> None:
        self._ocerts.update(signed)

    def _ocert(self, pool_i: int, counter: int, kp0: int):
        key = (pool_i, counter, kp0)
        oc = self._ocerts.get(key)
        if oc is None:
            oc = self.pools[pool_i].make_ocert(counter, kp0)
            self._ocerts[key] = oc
        return oc

    def _leaf(self, pool_i: int, t: int):
        key = (pool_i, t)
        leaf = self._leaves.get(key)
        if leaf is None:
            pool = self.pools[pool_i]
            leaf_seed, sibs = host_kes.leaf_path(
                pool.kes_seed, pool.kes_depth, t
            )
            leaf = (
                leaf_seed,
                fast.ed25519_public(leaf_seed) + b"".join(sibs),
            )
            self._leaves[key] = leaf
        return leaf

    def forge(self, pool_i: int, *, slot: int, block_no: int,
              prev_hash: bytes | None, txs: tuple,
              ocert_counter: int, is_leader: PraosIsLeader,
              protocol_version: tuple[int, int] = (9, 0)):
        """Byte-identical to block/forge.forge_block (the differential
        suite holds this equation), at amortized-constant signing cost."""
        from ..block.praos_block import Block, Header, HeaderBody, body_hash

        pool = self.pools[pool_i]
        kp = self.params.kes_period_of(slot)
        kp0 = self.ocert_window(slot)
        ocert = self._ocert(pool_i, ocert_counter, kp0)
        body = HeaderBody(
            block_no=block_no,
            slot=slot,
            prev_hash=prev_hash,
            issuer_vk=pool.vk_cold,
            vrf_vk=pool.vrf_vk,
            vrf_output=is_leader.vrf_output,
            vrf_proof=is_leader.vrf_proof,
            body_size=sum(len(t_) for t_ in txs),
            body_hash=body_hash(txs),
            ocert=ocert,
            protocol_version=protocol_version,
            vrf_leader_output=is_leader.vrf_leader_output,
            vrf_leader_proof=is_leader.vrf_leader_proof,
        )
        leaf_seed, tail = self._leaf(pool_i, kp - kp0)
        kes_sig = fast.ed25519_sign(leaf_seed, body.signed_bytes) + tail
        return Block(Header(body, kes_sig), tuple(txs))


# process-wide forge-window sequence (ForgeSpan.index)
_WINDOW_SEQ = [0]
_WINDOW_LOCK = threading.Lock()


def next_window_index() -> int:
    with _WINDOW_LOCK:
        n = _WINDOW_SEQ[0]
        _WINDOW_SEQ[0] = n + 1
        return n
