"""TPraos: Transitional Praos — the protocol of the Shelley, Allegra, Mary
and Alonzo eras, with the BFT overlay schedule.

Reference: `ouroboros-consensus-protocol/src/.../Protocol/TPraos.hs`
(ConsensusProtocol instance :304-392). The reference delegates header
validation to the ledger package's PRTCL / OVERLAY / UPDN / OCERT rules
(`SL.updateChainDepState`, TPraos.hs:380; cardano-protocol-tpraos
BHeader.hs `mkSeed`, `seedEta`, `seedL`, `checkLeaderValue`); this module
implements those semantics against the same batched back end the Praos
instance uses. The OCert and KES checks are Praos's. What differs:

  * a header carries TWO certified VRF results under the one registered
    VRF key (BHBody `bheaderEta`, `bheaderL`): the NONCE certificate over
    `mkSeed(seedEta, slot, eta0)` and the LEADER certificate over
    `mkSeed(seedL, slot, eta0)`, where `mkSeed(uc, slot, eta0) =
    Blake2b-256(be8(slot) ‖ eta0) XOR uc` (`HeaderView.vrf_output` /
    `.vrf_proof` hold the first, `.vrf_leader_output` / `.vrf_leader_proof`
    the second). Both proofs are verified and both declared outputs
    compared for every header, overlay or not;
  * the leader rule compares the RAW 64-byte leader output, read
    big-endian, under 2^512: nat(beta_L) / 2^512 < 1 - (1-f)^sigma (Praos
    hashes "L" ‖ beta to 32 bytes and compares under 2^256);
  * the nonce contribution is Blake2b-256(beta_eta): eta_v' =
    H(eta_v ‖ H(beta_eta)) (Praos: H(H("N" ‖ beta)));
  * a fraction `d` (decentralisation) of each epoch's slots form the
    OVERLAY schedule (Shelley `overlaySchedule`): slot i of an epoch is
    an overlay slot iff ceil((i+1)·d) > ceil(i·d), its position is
    ceil(i·d); every ascInv = ceil(1/f)-th position is ACTIVE and belongs
    to genesis delegate (position / ascInv) mod n, who must issue the
    block (cold key and VRF key both the delegate's), with both proofs,
    the OCert and the KES signature checked and NO threshold
    (`pbftVrfChecks`); a block in any other overlay slot is invalid.

Departures from the published rules, each on purpose: the OCert / KES
checks run BEFORE the VRF checks (this repo's Praos order, one error
order for both protocols; PRTCL runs OVERLAY's VRF checks first);
CompactSum KES where mainnet has Sum6KES; `d` is a parameter of the chain
where mainnet changed it by protocol-parameter update each epoch; the
header body's CBOR is this repo's (block/praos_block.HeaderBody, 11
fields).

On the device a TPraos window takes the normal path (protocol/batch:
packed columns, the stage kernels): what this module gives that path is
`TPraosRules`: the columnar overlay pass and delegate-key match, the
genesis delegates' counter default, the error taxonomy, and the native
and sharded stand-ins. `translate_state` is the TPraos→Praos
ChainDepState translation the HFC applies at the era boundary
(Protocol/Praos/Translate.hs:1-101): the nonces and operational-
certificate counters carry over unchanged.
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass, replace
from fractions import Fraction
from functools import cached_property
from typing import Sequence

import numpy as np

from ..ops.host.hashes import blake2b_256
from . import batch as pbatch
from . import nonces, praos, select
from .leader import check_leader_value
from .praos import (
    CryptoVerifier,
    HOST_VERIFIER,
    PraosParams,
    PraosState,
    PraosValidationError,
)
from .views import HeaderView, LedgerView, ViewColumns, hash_key, hash_vrf_vk

# the range of the leader value: the raw 64-byte certified output
LEADER_VALUE_MAX = 1 << 512
SEED_ETA, SEED_L = pbatch.SEED_ETA, pbatch.SEED_L


# ---------------------------------------------------------------------------
# Parameters / state / views
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class GenDeleg:
    """One genesis delegate (SL.GenDelegPair): the operational cold key
    and registered VRF key hash the overlay check matches against."""

    vk_cold: bytes
    vrf_key_hash: bytes


@dataclass(frozen=True)
class TPraosParams:
    """PraosParams + decentralization (TPraos.hs TPraosParams; `d` lives
    in the protocol parameters on-chain, here static per era)."""

    praos: PraosParams
    decentralization: Fraction  # d in [0, 1]; 0 = fully decentralized

    def __getattr__(self, name):
        return getattr(self.praos, name)

    @property
    def batch_rules(self) -> "TPraosRules":
        """What protocol/batch's window loop, staging and epilogue are
        parameterised by (`batch.rules_of`)."""
        return TPRAOS_RULES


@dataclass(frozen=True)
class TPraosLedgerView(LedgerView):
    """LedgerView + the ordered genesis delegation map (SL.LedgerView
    lvGenDelegs)."""

    gen_delegs: Sequence[GenDeleg] = ()

    @cached_property
    def deleg_index(self) -> dict:
        """cold key -> the delegate's index."""
        return {d.vk_cold: j for j, d in enumerate(self.gen_delegs)}

    @cached_property
    def counter_known(self) -> frozenset:
        """Key hashes whose OCert counter starts at 0: the pools with
        stake and the genesis delegates."""
        return frozenset(self.pool_distr) | {
            hash_key(d.vk_cold) for d in self.gen_delegs
        }


@dataclass(frozen=True)
class TPraosState(PraosState):
    """ChainDepState (TPraos c) — the PRTCL state: same nonce/counter
    content as Praos (TPraos.hs:219, SL.ChainDepState)."""


@dataclass(frozen=True)
class TickedTPraosState:
    state: TPraosState
    ledger_view: TPraosLedgerView


# ---------------------------------------------------------------------------
# Seeds (BHeader.hs mkSeed)
# ---------------------------------------------------------------------------


def mk_seed(uc: bytes, slot: int, epoch_nonce: nonces.Nonce) -> bytes:
    """mkSeed: Blake2b-256(be8(slot) ‖ eta0) XOR uc; a neutral eta0
    contributes no bytes. `uc` is `SEED_ETA` or `SEED_L`."""
    base = nonces.mk_input_vrf(slot, epoch_nonce)
    return bytes(a ^ b for a, b in zip(base, uc))


def _seed_columns(vc: ViewColumns, epoch_nonce) -> tuple[np.ndarray, np.ndarray]:
    """([B, 32] nonce-proof inputs, [B, 32] leader-proof inputs): one
    hash a lane serves both."""
    base = pbatch._alpha_column(vc, epoch_nonce)
    return (base ^ np.frombuffer(SEED_ETA, np.uint8),
            base ^ np.frombuffer(SEED_L, np.uint8))


# ---------------------------------------------------------------------------
# Overlay schedule (Shelley overlaySchedule / lookupInOverlaySchedule)
# ---------------------------------------------------------------------------


def _asc_inv(f: Fraction) -> int:
    return max(1, math.ceil(1 / f))


def overlay_position(params: TPraosParams, slot: int) -> int | None:
    """None if `slot` is not an overlay slot, else its overlay position
    within the epoch (isOverlaySlot: the ceil(i*d) step function
    advances exactly on overlay slots)."""
    d = params.decentralization
    if d == 0:
        return None
    i = slot - params.praos.first_slot_of(params.praos.epoch_of(slot))
    lo = math.ceil(i * d)
    hi = math.ceil((i + 1) * d)
    return lo if hi > lo else None


def overlay_slot_assignment(
    params: TPraosParams, n_delegs: int, slot: int
) -> tuple[bool, int | None] | None:
    """None = not an overlay slot; (False, None) = inactive overlay slot
    (must be empty); (True, j) = active, assigned to delegate j."""
    pos = overlay_position(params, slot)
    if pos is None:
        return None
    ai = _asc_inv(params.praos.active_slot_coeff)
    if pos % ai != 0 or n_delegs == 0:
        # no delegates registered: no overlay slot can ever be led
        return (False, None)
    return (True, (pos // ai) % n_delegs)


LOTTERY, ACTIVE, INACTIVE = 0, 1, 2


def overlay_columns(params: TPraosParams, n_delegs: int, slots):
    """`overlay_slot_assignment` over a column of slots, in one pass of
    exact integer arithmetic (ceil(i·p/q) = (i·p + q - 1) // q):
    -> (kind [B] int8: LOTTERY, ACTIVE or INACTIVE; delegate [B] int64,
    -1 where the slot is not an active overlay slot)."""
    slots = np.asarray(slots, np.int64)
    d = Fraction(params.decentralization)
    kind = np.zeros(slots.shape, np.int8)
    deleg = np.full(slots.shape, -1, np.int64)
    if d == 0 or not slots.size:
        return kind, deleg
    p, q = d.numerator, d.denominator
    length = params.praos.epoch_length
    i = slots - params.praos.first_slot_of(params.praos.epoch_of(slots))
    if (length + 1) * p + q >= 1 << 62:  # never, for a chain's d
        i = i.astype(object)
    lo = (i * p + (q - 1)) // q
    hi = ((i + 1) * p + (q - 1)) // q
    lo = np.asarray(lo, np.int64)
    is_overlay = np.asarray(hi, np.int64) > lo
    ai = _asc_inv(params.praos.active_slot_coeff)
    active = is_overlay & (lo % ai == 0) & (n_delegs > 0)
    kind[is_overlay] = INACTIVE
    kind[active] = ACTIVE
    if n_delegs:
        deleg[active] = (lo[active] // ai) % n_delegs
    return kind, deleg


# ---------------------------------------------------------------------------
# Errors beyond the shared Praos taxonomy
# ---------------------------------------------------------------------------


@dataclass
class WrongGenesisDelegate(PraosValidationError):
    """An overlay block issued by someone other than the scheduled
    genesis delegate (OVERLAY WrongGenesisColdKeyOVERLAY)."""

    slot: int
    expected: bytes
    got: bytes


@dataclass
class NonActiveSlot(PraosValidationError):
    """A block in an inactive overlay slot (OVERLAY NotActiveSlotOVERLAY)."""

    slot: int


@dataclass
class WrongGenesisVRFKey(PraosValidationError):
    """OVERLAY WrongGenesisVRFKeyOVERLAY."""

    slot: int
    expected: bytes
    got: bytes


@dataclass
class VRFKeyBadNonce(PraosValidationError):
    """The nonce certificate's proof or declared output is wrong (PRTCL
    vrfChecks VRFKeyBadNonce)."""

    slot: int
    epoch_nonce: nonces.Nonce


@dataclass
class VRFKeyBadLeaderValue(PraosValidationError):
    """The leader certificate's proof or declared output is wrong (PRTCL
    vrfChecks VRFKeyBadLeaderValue)."""

    slot: int
    epoch_nonce: nonces.Nonce


# ---------------------------------------------------------------------------
# tick / update / reupdate (host semantics)
# ---------------------------------------------------------------------------


def tick(
    params: TPraosParams, lview: TPraosLedgerView, slot: int, state: TPraosState
) -> TickedTPraosState:
    """TICKN at the epoch boundary, as Praos."""
    inner = praos.tick(params.praos, lview, slot, state)
    return TickedTPraosState(
        TPraosState(**vars(inner.state)), inner.ledger_view
    )


def _overlay_error(
    params: TPraosParams, lview: TPraosLedgerView, hv: HeaderView
) -> PraosValidationError | None | bool:
    """The overlay-side replacement of the Praos pool lookup + threshold
    (lookupInOverlaySchedule + pbftVrfChecks' key match). None when
    `hv.slot` is a non-overlay slot (the lottery's rules apply), False
    where it is an active overlay slot and the delegate's keys match."""
    assign = overlay_slot_assignment(params, len(lview.gen_delegs), hv.slot)
    if assign is None:
        return None
    active, j = assign
    if not active:
        return NonActiveSlot(hv.slot)
    deleg = lview.gen_delegs[j]
    if hv.vk_cold != deleg.vk_cold:
        return WrongGenesisDelegate(hv.slot, deleg.vk_cold, hv.vk_cold)
    got_hash = hash_vrf_vk(hv.vrf_vk)
    if got_hash != deleg.vrf_key_hash:
        return WrongGenesisVRFKey(hv.slot, deleg.vrf_key_hash, got_hash)
    return False


def _validate_vrf(
    params: TPraosParams,
    lview: TPraosLedgerView,
    epoch_nonce,
    hv: HeaderView,
    crypto: CryptoVerifier,
) -> None:
    """OVERLAY + vrfChecks + checkLeaderValue, in this order: who may
    issue in the slot, the nonce proof, the leader proof, the threshold
    (lottery slots only)."""
    err = _overlay_error(params, lview, hv)
    if err:
        raise err
    entry = None
    if err is None:
        hk = hash_key(hv.vk_cold)
        entry = lview.pool_distr.get(hk)
        if entry is None:
            raise praos.VRFKeyUnknown(hk)
        header_vrf_hash = hash_vrf_vk(hv.vrf_vk)
        if entry.vrf_key_hash != header_vrf_hash:
            raise praos.VRFKeyWrongVRFKey(
                hk, entry.vrf_key_hash, header_vrf_hash
            )
    if not crypto.verify_vrf(
        hv.vrf_vk, hv.vrf_proof, mk_seed(SEED_ETA, hv.slot, epoch_nonce),
        hv.vrf_output,
    ):
        raise VRFKeyBadNonce(hv.slot, epoch_nonce)
    if hv.vrf_leader_proof is None or not crypto.verify_vrf(
        hv.vrf_vk, hv.vrf_leader_proof,
        mk_seed(SEED_L, hv.slot, epoch_nonce), hv.vrf_leader_output,
    ):
        raise VRFKeyBadLeaderValue(hv.slot, epoch_nonce)
    if entry is not None:
        f = params.praos.active_slot_coeff
        lv_val = int.from_bytes(hv.vrf_leader_output, "big")
        if not check_leader_value(lv_val, entry.stake, f, LEADER_VALUE_MAX):
            raise praos.VRFLeaderValueTooBig(lv_val, entry.stake, f)


def update(
    params: TPraosParams,
    hv: HeaderView,
    slot: int,
    ticked: TickedTPraosState,
    crypto: CryptoVerifier = HOST_VERIFIER,
) -> TPraosState:
    """updateChainDepState (TPraos.hs:380 → PRTCL): the KES/OCert checks
    shared with Praos (a genesis delegate's counter starts at 0 like a
    pool's), then the overlay-aware VRF section, then `reupdate`."""
    cs = ticked.state
    lview = ticked.ledger_view
    oc = hv.ocert
    hk = hash_key(hv.vk_cold)
    try:
        praos.validate_kes_signature(
            params.praos, lview, cs.ocert_counters, hv, crypto
        )
    except praos.NoCounterForKeyHashOCERT:
        if hk not in lview.counter_known:
            raise
        m, n = 0, oc.counter
        if not m <= n:
            raise praos.CounterTooSmallOCERT(m, n)
        if not n <= m + 1:
            raise praos.CounterOverIncrementedOCERT(m, n)
    _validate_vrf(params, lview, cs.epoch_nonce, hv, crypto)
    return reupdate(params, hv, slot, ticked)


def reupdate(
    params: TPraosParams, hv: HeaderView, slot: int, ticked: TickedTPraosState
) -> TPraosState:
    """UPDN + the counter bookkeeping: eta_v' = H(eta_v ‖ H(beta_eta)),
    the candidate following until 3k/f slots before the epoch ends."""
    inner = praos.reupdate(
        params.praos, hv, slot,
        praos.TickedPraosState(ticked.state, ticked.ledger_view),
        eta=blake2b_256(hv.vrf_output),
    )
    return TPraosState(**vars(inner))


def translate_state(state: TPraosState) -> PraosState:
    """TPraos → Praos ChainDepState translation at the era boundary
    (Protocol/Praos/Translate.hs): nonces and ocert counters carry
    over unchanged; the overlay schedule simply ceases to exist."""
    return PraosState(**vars(state))


# ---------------------------------------------------------------------------
# Forging (checkIsLeader, TPraos.hs:304-355)
# ---------------------------------------------------------------------------


def prove_certificates(vrf_seed: bytes, slot: int, epoch_nonce,
                       leader=None) -> praos.PraosIsLeader:
    """Both certified VRF results of a block at `slot`: 80-byte draft-03
    proofs, whatever `OCT_VRF_BATCH` says of Praos's. `leader` is the
    slot's `leader_certificate` where the lottery has proved it."""
    from ..ops.host import fast

    eta_p = fast.ecvrf_prove_draft03(
        vrf_seed, mk_seed(SEED_ETA, slot, epoch_nonce))
    beta_l, l_p = leader or leader_certificate(vrf_seed, slot, epoch_nonce)
    return praos.PraosIsLeader(
        fast.ecvrf_proof_to_hash(eta_p), eta_p, beta_l, l_p)


def wins_lottery(beta_l: bytes, sigma: Fraction, f: Fraction) -> bool:
    """The 512-bit leader rule on a raw leader output: the cached
    integer bracket decides all but a 2^-70 band, the exact check that
    (what `finish_tp` and `_lane_error` do between them)."""
    lv = int.from_bytes(beta_l, "big")
    lo, hi = pbatch.leader_threshold_bracket(Fraction(sigma), Fraction(f), 512)
    if lv < lo:
        return True
    if lv >= hi:
        return False
    return check_leader_value(lv, sigma, f, LEADER_VALUE_MAX)


def leader_certificate(vrf_seed: bytes, slot: int, epoch_nonce):
    """(beta_L, proof) alone: all the lottery needs of a losing slot."""
    from ..ops.host import fast

    proof = fast.ecvrf_prove_draft03(
        vrf_seed, mk_seed(SEED_L, slot, epoch_nonce))
    return fast.ecvrf_proof_to_hash(proof), proof


def check_is_leader(
    params: TPraosParams,
    can_be_leader: praos.PraosCanBeLeader,
    slot: int,
    ticked: TickedTPraosState,
    deleg_index: int | None = None,
) -> praos.PraosIsLeader | None:
    """Overlay slots: lead iff we are the scheduled delegate (both
    certificates are still proved: headers always carry them);
    non-overlay: the lottery on nat(beta_L) under 2^512."""
    lview = ticked.ledger_view
    assign = overlay_slot_assignment(params, len(lview.gen_delegs), slot)
    eta0 = ticked.state.epoch_nonce
    seed = can_be_leader.vrf_sign_seed
    if assign is not None:
        active, j = assign
        if not active or deleg_index is None or j != deleg_index:
            return None
        return prove_certificates(seed, slot, eta0)
    entry = lview.pool_distr.get(hash_key(can_be_leader.vk_cold))
    sigma = entry.stake if entry is not None else Fraction(0)
    leader = leader_certificate(seed, slot, eta0)
    if not wins_lottery(leader[0], sigma, params.praos.active_slot_coeff):
        return None
    return prove_certificates(seed, slot, eta0, leader)


# ---------------------------------------------------------------------------
# What the batched path is parameterised by
# ---------------------------------------------------------------------------


def host_prechecks(
    params: TPraosParams, lview: TPraosLedgerView,
    hvs: "Sequence[HeaderView] | ViewColumns",
) -> pbatch.ColumnChecks:
    """pbatch.host_prechecks under TPraos: the KES windows as Praos, then
    per lane who may issue in its slot (the overlay schedule and the
    delegate's two keys, or the pool lookup), in one vectorised pass over
    the window's slots and key columns. A list of views is columnarised
    first: there is one implementation."""
    vc = _columns(hvs)
    n = len(vc)
    kes_errors, evol, bad_window = pbatch.kes_window_checks(params.praos, vc)
    uniq, inv, hks, entries, uerrs = pbatch.pool_pairs(lview, vc)
    t0 = time.monotonic()
    with pbatch._enclose("stage.overlay"):
        delegs = lview.gen_delegs
        kind, deleg = overlay_columns(params, len(delegs), vc.slot)
        # per unique (cold key, VRF key) pair: whose cold key it is, and
        # whether the VRF key is that delegate's
        pair_deleg = np.asarray([
            lview.deleg_index.get(uniq[j, :32].tobytes(), -1)
            for j in range(uniq.shape[0])
        ], np.int64)
        pair_dvrf = np.asarray([
            j_d >= 0 and hash_vrf_vk(uniq[j, 32:].tobytes())
            == delegs[j_d].vrf_key_hash
            for j, j_d in enumerate(pair_deleg.tolist())
        ], bool)
        pair_pool_ok = np.asarray([e is None for e in uerrs], bool)
        active = kind == ACTIVE
        ok = np.where(
            kind == LOTTERY, pair_pool_ok[inv],
            active & (pair_deleg[inv] == deleg) & pair_dvrf[inv],
        )
    overlay_s = time.monotonic() - t0
    vrf_errors: list = [None] * n
    all_ok = bool(ok.all())
    if not all_ok:
        slots = vc.slot
        for i in np.flatnonzero(~ok).tolist():
            if kind[i] == LOTTERY:
                vrf_errors[i] = uerrs[inv[i]]
            elif kind[i] == INACTIVE:
                vrf_errors[i] = NonActiveSlot(int(slots[i]))
            else:
                dg = delegs[int(deleg[i])]
                cold = vc.vk_cold[i].tobytes()
                if cold != dg.vk_cold:
                    vrf_errors[i] = WrongGenesisDelegate(
                        int(slots[i]), dg.vk_cold, cold)
                else:
                    vrf_errors[i] = WrongGenesisVRFKey(
                        int(slots[i]), dg.vrf_key_hash,
                        hash_vrf_vk(vc.vrf_vk[i].tobytes()))
    return pbatch.ColumnChecks(
        kes_errors, vrf_errors, evol,
        inv.astype(np.int32), tuple(hks), tuple(entries),
        not bad_window and all_ok,
        overlay=active.astype(np.uint8), overlay_s=overlay_s,
    )


def _columns(hvs) -> ViewColumns:
    if isinstance(hvs, ViewColumns):
        return hvs
    vc = ViewColumns.from_views(hvs)
    if vc is None or not vc.two_certs:
        raise ValueError(
            "a TPraos window must columnarise: two-certificate headers "
            "of one body and signature width"
        )
    return vc


def _lane_error(params, lview, eta0, hv, pre, v, i, counters):
    """pbatch._lane_error under TPraos: the same order (all of the
    OCert/KES section before any of the VRF section), the genesis
    delegates' counter default, which of the two proofs failed, and the
    512-bit leader rule on lottery lanes alone."""
    pp = params.praos
    if pre.kes_window_errors[i] is not None:
        return pre.kes_window_errors[i]
    if not v.ok_ocert_sig[i]:
        return praos.InvalidSignatureOCERT(hv.ocert.counter, hv.ocert.kes_period)
    if not v.ok_kes_sig[i]:
        kp = pp.kes_period_of(hv.slot)
        c0 = hv.ocert.kes_period
        return praos.InvalidKesSignatureOCERT(kp, c0, kp - c0)
    hk = hash_key(hv.vk_cold)
    m = pbatch._counter_m(hk, counters, lview.counter_known)
    if m is None:
        return praos.NoCounterForKeyHashOCERT(hk)
    n = hv.ocert.counter
    if not m <= n:
        return praos.CounterTooSmallOCERT(m, n)
    if not n <= m + 1:
        return praos.CounterOverIncrementedOCERT(m, n)
    if pre.vrf_lookup_errors[i] is not None:
        return pre.vrf_lookup_errors[i]
    if not v.ok_vrf[i]:
        if not v.ok_vrf_nonce[i]:
            return VRFKeyBadNonce(hv.slot, eta0)
        return VRFKeyBadLeaderValue(hv.slot, eta0)
    if pre.overlay[i] or (not v.leader_ambiguous[i] and v.ok_leader[i]):
        return None
    entry = lview.pool_distr.get(hk)
    sigma = entry.stake if entry is not None else Fraction(0)
    lv_val = int.from_bytes(bytes(v.leader_value[i].astype(np.uint8)), "big")
    f = pp.active_slot_coeff
    if v.leader_ambiguous[i] and check_leader_value(
        lv_val, sigma, f, LEADER_VALUE_MAX
    ):
        return None
    return praos.VRFLeaderValueTooBig(lv_val, sigma, f)


def _leader_rows(params, pre, vc: ViewColumns, live: np.ndarray):
    """(ok_leader, ambiguous) of a window on the host: the declared
    beta_L rows against the per-pool 512-bit brackets, the overlay lanes
    winning outright (what `finish_tp` does on the device)."""
    thr_lo, thr_hi = pbatch._uniq_threshold_tables(params.praos, pre, 512)
    lv = vc.vrf_leader_output
    over = pre.overlay.astype(bool)
    win = pbatch._lt_be_rows(lv, thr_lo)
    amb = ~win & pbatch._lt_be_rows(lv, thr_hi) & ~over
    return (win | over) & live, amb & live


def run_batch_native(params, lview, eta0, hvs, pre) -> pbatch.TPraosVerdicts:
    """The C++ verifier under TPraos (native/hostcrypto.cpp
    oc_validate_tpraos): OCert, KES and BOTH proofs a header, stopping at
    the first failing lane like its Praos twin."""
    from .. import native_loader as nl

    vc = _columns(hvs)
    n = len(vc)
    lb = vc.signed_bytes.shape[1]
    a_eta, a_l = _seed_columns(vc, eta0)
    rc, kind, eta = nl.native_validate_tpraos(
        vc.vk_cold, vc.ocert_sigma,
        np.concatenate(
            [vc.ocert_vk_hot, pbatch._be8_np(vc.ocert_counter),
             pbatch._be8_np(vc.ocert_kes_period)], axis=1),
        vc.ocert_vk_hot, pre.kes_evolution.astype(np.int64), vc.kes_sig,
        params.praos.kes_depth, vc.signed_bytes.tobytes(),
        np.arange(n + 1, dtype=np.int64) * lb, vc.vrf_vk,
        np.ascontiguousarray(vc.vrf_proof[:, :80]), a_eta, vc.vrf_output,
        vc.vrf_leader_proof, a_l, vc.vrf_leader_output,
    )
    ok = [np.ones(n, bool) for _ in range(4)]  # ocert, kes, nonce, leader
    if rc >= 0:
        ok[kind - 1][rc] = False
    live = np.arange(n) < (n if rc < 0 else rc)
    ok_leader, ambiguous = _leader_rows(params, pre, vc, live)
    return pbatch.TPraosVerdicts(
        ok[0], ok[1], ok[2] & ok[3], ok_leader, ambiguous, eta,
        vc.vrf_leader_output, ok[2],
    )


def run_batch_sharded(params, lview, eta0, hvs, pre,
                      mesh) -> pbatch.TPraosVerdicts:
    """The multi-chip SPMD stand-in: the window twice through Praos's
    sharded single-proof program, once a certificate (its own input and
    declared output), the OCert and KES verdicts of the first pass; the
    leader rule and the nonce hash on the host. Not the production path
    (one chip's is `finish_tp`); kept so that a sharded replay of a
    mixed-era chain checks every proof."""
    from ..parallel import spmd

    vc = _columns(hvs)
    views = vc.views()
    pp = params.praos

    def one_pass(hv_list, uc):
        batch = pbatch.stage(
            pp, lview, eta0, hv_list, pre.kes_evolution,
            alpha_of=lambda slot, nonce: mk_seed(uc, slot, nonce),
        )
        return spmd.sharded_run_batch(  # octflow: disable=FLOW304 — a
            # backend of `validate_batch` (through the rules object):
            # under recover_window's and recover_fold's ladders
            batch, mesh)[0]

    v_e = one_pass(views, SEED_ETA)
    v_l = one_pass(
        [replace(hv, vrf_output=hv.vrf_leader_output,
                 vrf_proof=hv.vrf_leader_proof) for hv in views],
        SEED_L,
    )
    ok_leader, ambiguous = _leader_rows(
        params, pre, vc, np.ones(len(vc), bool))
    eta = np.stack([
        np.frombuffer(blake2b_256(hv.vrf_output), np.uint8) for hv in views
    ])
    ok_e, ok_l = np.asarray(v_e.ok_vrf), np.asarray(v_l.ok_vrf)
    return pbatch.TPraosVerdicts(
        np.asarray(v_e.ok_ocert_sig), np.asarray(v_e.ok_kes_sig),
        ok_e & ok_l, ok_leader, ambiguous, eta, vc.vrf_leader_output, ok_e,
    )


class TPraosRules(pbatch.PraosRules):
    """protocol/batch's window loop, staging and epilogue under TPraos
    (see `batch.PraosRules`)."""

    name = "tpraos"
    protocol = "TPraos"
    packed_only = True  # a window's one device path is the packed one
    overlay = True  # the schedule gives some slots to genesis delegates

    def initial_state(self) -> TPraosState:
        return TPraosState()

    def tick(self, params, lview, slot, state):
        return tick(params, lview, slot, state)

    def reupdate(self, params, hv, slot, ticked):
        return reupdate(params, hv, slot, ticked)

    def issuers(self, lview) -> int:
        return len(lview.pool_distr) + len(lview.gen_delegs)

    def window(self, hvs):
        return _columns(hvs)

    def runs(self, hvs) -> list:
        """Lists of views are cut where a body or signature width
        steps (CBOR integer widths; the columnar stream cuts its chunks
        there too): each run columnarises."""
        if isinstance(hvs, ViewColumns):
            return [hvs]
        cuts = [0] + [
            i for i in range(1, len(hvs))
            if (len(hvs[i].signed_bytes), len(hvs[i].kes_sig))
            != (len(hvs[i - 1].signed_bytes), len(hvs[i - 1].kes_sig))
        ] + [len(hvs)]
        return [_columns(hvs[a:b]) for a, b in zip(cuts, cuts[1:])]

    def prechecks(self, params, lview, hvs):
        return host_prechecks(params, lview, hvs)

    def counter_known(self, lview):
        return lview.counter_known

    def lane_error(self, *a):
        return _lane_error(*a)

    def run_native(self, params, lview, eta0, hvs, pre):
        return run_batch_native(  # octflow: disable=FLOW304 — as
            # batch.PraosRules.run_native: `validate_batch`'s backend
            params, lview, eta0, hvs, pre)

    def run_sharded(self, params, lview, eta0, hvs, pre, mesh):
        return run_batch_sharded(params, lview, eta0, hvs, pre, mesh)

    def update(self, params, hv, slot, ticked):
        return update(params, hv, slot, ticked)


TPRAOS_RULES = TPraosRules()


class TPraosProtocol:
    """ConsensusProtocol (TPraos c) instance-as-object (TPraos.hs:304)."""

    def __init__(
        self,
        params: TPraosParams,
        crypto: CryptoVerifier = HOST_VERIFIER,
        use_device_batch: bool = True,
    ):
        self.params = params
        self.crypto = crypto
        self.security_param = params.praos.security_param
        self.use_device_batch = use_device_batch

    def initial_state(self) -> TPraosState:
        return TPraosState()

    def tick(self, ledger_view, slot, state) -> TickedTPraosState:
        return tick(self.params, ledger_view, slot, state)

    def update(self, view, slot, ticked) -> TPraosState:
        return update(self.params, view, slot, ticked, self.crypto)

    def reupdate(self, view, slot, ticked) -> TPraosState:
        return reupdate(self.params, view, slot, ticked)

    def check_is_leader(self, can_be_leader, slot, ticked, deleg_index=None):
        return check_is_leader(
            self.params, can_be_leader, slot, ticked, deleg_index
        )

    def select_view(self, header) -> select.PraosSelectView:
        # TPraos chain order == Praos chain order (Praos/Common.hs)
        return select.PraosSelectView.from_header(header)

    def compare_candidates(self, ours, theirs) -> int:
        return select.compare_select_views(ours, theirs)

    def validate_batch(self, ticked, hvs, collect_states=False, backend=None):
        """A within-epoch run of headers as one batch: the staged
        dispatch of protocol/batch (backend "device": packed columns,
        the stage programs with `vrf` twice and `finish_tp`), the C++
        verifier ("native"), the SPMD stand-in ("sharded") or the
        sequential fold ("host-fold")."""
        if not hvs:
            return pbatch.BatchResult(
                ticked.state, 0, None, [] if collect_states else None
            )
        if backend is None:
            backend = "device" if self.use_device_batch else "host-fold"
        if backend == "host-fold":
            return self._host_fold(ticked, hvs, collect_states)
        return self.recover_fold(backend, ticked, hvs, collect_states)

    def recover_fold(self, backend, ticked, hvs, collect_states):
        """The TPraos dispatch's degradation floor (FLOW304 protector):
        TPraos windows are dispatched through the hardfork combinator's
        dynamic `proto.validate_batch`, which the RecoverySupervisor's
        static ladder never sees — so the exact-host-reference rung
        lives here. Only RECOVER-classified faults (node/exit.triage:
        device/runtime errors, I/O, the chaos taxonomy) are absorbed,
        only with the supervisor enabled (OCT_RECOVERY=0 restores
        raise-through), and every fall is banked as a RecoveryEvent —
        REFUSE/REPAIR/PROPAGATE classes surface raw, same contract as
        `RecoverySupervisor.recover_window`."""
        from ..obs import recovery as _recovery

        try:
            return self._device_batch(backend, ticked, hvs, collect_states)
        except Exception as e:  # noqa: BLE001 — triaged: only RECOVER
            # (recoverable below) is absorbed onto the host fold
            if not (_recovery.enabled() and _recovery.recoverable(e)):
                raise
            lanes = len(hvs)
            _recovery.note_recovery_event("host-fold", -1, lanes, 1, e)
            res = self._host_fold(ticked, hvs, collect_states)
            _recovery.note_recovery_event("recovered", -1, lanes, 1, e,
                                          ok=True)
            return res

    def _device_batch(self, backend, ticked, hvs, collect_states):
        inner = praos.TickedPraosState(ticked.state, ticked.ledger_view)
        return pbatch.validate_batch(
            self.params, inner, hvs, collect_states, backend
        )

    def _host_fold(self, ticked, hvs, collect_states):
        """Sequential fold from an ALREADY-ticked state: the first
        header must not be ticked again (a second tick at an epoch
        boundary would rotate the nonce twice); later headers share the
        epoch, so their ticks are no-ops by construction."""
        st = ticked.state
        states = [] if collect_states else None
        t = ticked
        for i, hv in enumerate(hvs):
            if i > 0:
                t = tick(self.params, ticked.ledger_view, hv.slot, st)
            try:
                st = update(self.params, hv, hv.slot, t, self.crypto)
            except PraosValidationError as e:
                return pbatch.BatchResult(st, i, e, states)
            if states is not None:
                states.append(st)
        return pbatch.BatchResult(st, len(hvs), None, states)
