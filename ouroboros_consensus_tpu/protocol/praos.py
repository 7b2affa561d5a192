"""The Praos consensus protocol: chain-dependent state machine (host).

Semantics mirror the reference `ConsensusProtocol (Praos c)` instance
(ouroboros-consensus-protocol/.../Protocol/Praos.hs:364-606) exactly:

  * `tick`          = tickChainDepState (Praos.hs:407-432): epoch-boundary
                      nonce rotation.
  * `update`        = updateChainDepState (Praos.hs:441-466): KES checks,
                      then VRF checks, then `reupdate`.
  * `reupdate`      = reupdateChainDepState (Praos.hs:468-502): nonce and
                      ocert-counter bookkeeping, no crypto.
  * `check_is_leader` (Praos.hs:375-397): forging-side VRF evaluation +
                      leader threshold.

Crypto is routed through a `CryptoVerifier` so the host reference
implementation and the TPU batch backend (protocol/batch.py) are
interchangeable; `update` is the batch-of-1 spec the kernels are tested
against. Validation order and the error taxonomy follow
`PraosValidationErr` (Praos.hs:319-356) constructor by constructor.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from fractions import Fraction
from functools import cached_property
from typing import Mapping, Protocol as TyProtocol

from ..ops.host import ecvrf as host_ecvrf
from ..ops.host import ed25519 as host_ed25519
from ..ops.host import kes as host_kes
from . import nonces
from .leader import check_leader_value
from .nonces import Nonce
from .views import HeaderView, LedgerView, OCert, hash_key, hash_vrf_vk

# ---------------------------------------------------------------------------
# Parameters & epoch structure
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class PraosParams:
    """Node-independent Praos parameters (Praos.hs:184-209)."""

    slots_per_kes_period: int = 129600
    max_kes_evolutions: int = 62
    security_param: int = 2160  # k
    active_slot_coeff: Fraction = Fraction(1, 20)  # f
    epoch_length: int = 432000  # fixed EpochInfo (slots per epoch)
    kes_depth: int = host_kes.DEFAULT_DEPTH  # CompactSum tree depth
    # the era's origin on the hard-fork combinator's clock (EraParams /
    # the Summary's era bound): the slot its first epoch starts at and
    # that epoch's number. 0, 0: an era from genesis
    era_start_slot: int = 0
    era_start_epoch: int = 0

    @cached_property
    def stability_window(self) -> int:
        """3k/f rounded up (cardano-ledger computeStabilityWindow).
        Cached: the Fraction division costs ~12 us and the replay fold
        asks once per header (frozen dataclass — the value is stored in
        the instance __dict__, bypassing the frozen setattr guard)."""
        w = 3 * self.security_param / self.active_slot_coeff
        return int(-(-w // 1))

    def epoch_of(self, slot):
        """The epoch of `slot` (an int or a numpy column of them),
        counted from the era's origin: a slot before the era's first
        lands in the epoch before its first."""
        return (slot - self.era_start_slot) // self.epoch_length \
            + self.era_start_epoch

    def first_slot_of(self, epoch: int) -> int:
        return (self.era_start_slot
                + (epoch - self.era_start_epoch) * self.epoch_length)

    def kes_period_of(self, slot: int) -> int:
        assert self.slots_per_kes_period > 0
        return slot // self.slots_per_kes_period

    def is_new_epoch(self, last_slot: int | None, slot: int) -> bool:
        """isNewEpoch (Protocol/Ledger/Util.hs:18-40); Origin -> epoch 0."""
        old_epoch = 0 if last_slot is None else self.epoch_of(last_slot)
        first = self.first_slot_of(old_epoch)
        epochs_after = max(0, slot - first) // self.epoch_length
        return old_epoch + epochs_after > old_epoch


# ---------------------------------------------------------------------------
# Chain-dependent state
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class PraosState:
    """PraosState (Praos.hs:248-264): last slot, ocert counters, 5 nonces."""

    last_slot: int | None = None  # WithOrigin SlotNo
    ocert_counters: Mapping[bytes, int] = field(default_factory=dict)
    evolving_nonce: Nonce = None
    candidate_nonce: Nonce = None
    epoch_nonce: Nonce = None
    lab_nonce: Nonce = None  # nonce from last applied block's prev-hash
    last_epoch_block_nonce: Nonce = None


@dataclass(frozen=True)
class TickedPraosState:
    state: PraosState
    ledger_view: LedgerView


# ---------------------------------------------------------------------------
# Error taxonomy (PraosValidationErr, Praos.hs:319-356)
# ---------------------------------------------------------------------------


class PraosValidationError(Exception):
    """Base of the Praos validation error taxonomy."""


@dataclass
class VRFKeyUnknown(PraosValidationError):
    pool_key_hash: bytes


@dataclass
class VRFKeyWrongVRFKey(PraosValidationError):
    pool_key_hash: bytes
    registered_vrf_hash: bytes
    header_vrf_hash: bytes


@dataclass
class VRFKeyBadProof(PraosValidationError):
    slot: int
    epoch_nonce: Nonce


@dataclass
class VRFLeaderValueTooBig(PraosValidationError):
    leader_value: int
    sigma: Fraction
    active_slot_coeff: Fraction


@dataclass
class KESBeforeStartOCERT(PraosValidationError):
    ocert_start_period: int
    current_period: int


@dataclass
class KESAfterEndOCERT(PraosValidationError):
    current_period: int
    ocert_start_period: int
    max_kes_evolutions: int


@dataclass
class CounterTooSmallOCERT(PraosValidationError):
    last_counter: int
    current_counter: int


@dataclass
class CounterOverIncrementedOCERT(PraosValidationError):
    last_counter: int
    current_counter: int


@dataclass
class InvalidSignatureOCERT(PraosValidationError):
    counter: int
    kes_period: int


@dataclass
class InvalidKesSignatureOCERT(PraosValidationError):
    current_period: int
    start_period: int
    expected_evolutions: int


@dataclass
class NoCounterForKeyHashOCERT(PraosValidationError):
    pool_key_hash: bytes


# ---------------------------------------------------------------------------
# Crypto routing
# ---------------------------------------------------------------------------


class CryptoVerifier(TyProtocol):
    """The three verifications of the hot path, swappable host/TPU."""

    def verify_dsign(self, vk: bytes, msg: bytes, sig: bytes) -> bool: ...

    def verify_kes(
        self, vk: bytes, depth: int, period: int, msg: bytes, sig: bytes
    ) -> bool: ...

    def verify_vrf(self, vk: bytes, proof: bytes, alpha: bytes, output: bytes) -> bool: ...


class HostVerifier:
    """Pure-Python reference crypto (ops/host/*)."""

    def verify_dsign(self, vk, msg, sig):
        return host_ed25519.verify(vk, msg, sig)

    def verify_kes(self, vk, depth, period, msg, sig):
        return host_kes.verify(vk, depth, period, msg, sig)

    def verify_vrf(self, vk, proof, alpha, output):
        beta = host_ecvrf.verify(vk, proof, alpha)
        return beta is not None and beta == output


HOST_VERIFIER = HostVerifier()


class NativeVerifier:
    """C++ host crypto (native/hostcrypto.cpp via ctypes) — the same
    per-header semantics as HostVerifier at libsodium-class speed; used
    where a test/tool needs many sequential host validations."""

    def verify_dsign(self, vk, msg, sig):
        from .. import native_loader

        return native_loader.native_ed25519_verify(vk, sig, msg)

    def verify_kes(self, vk, depth, period, msg, sig):
        from .. import native_loader

        return native_loader.native_kes_verify(vk, depth, period, msg, sig)

    def verify_vrf(self, vk, proof, alpha, output):
        from .. import native_loader

        beta = native_loader.native_ecvrf_verify(vk, proof, alpha)
        return beta is not None and beta == output


def native_verifier_or_host() -> CryptoVerifier:
    """NativeVerifier when the C++ library is buildable, else the
    pure-Python fallback (import-time cheap; load is lazy per call)."""
    from .. import native_loader

    return NativeVerifier() if native_loader.load_crypto() is not None else HOST_VERIFIER


# ---------------------------------------------------------------------------
# Protocol transitions
# ---------------------------------------------------------------------------


def tick(
    params: PraosParams, ledger_view: LedgerView, slot: int, state: PraosState
) -> TickedPraosState:
    """tickChainDepState (Praos.hs:407-432): on epoch change, rotate
    epoch nonce (candidate ⭒ last-epoch-block nonce) and latch the LAB
    nonce as the new last-epoch-block nonce."""
    if params.is_new_epoch(state.last_slot, slot):
        state = replace(
            state,
            epoch_nonce=nonces.combine(
                state.candidate_nonce, state.last_epoch_block_nonce
            ),
            last_epoch_block_nonce=state.lab_nonce,
        )
    return TickedPraosState(state, ledger_view)


def validate_kes_signature(
    params: PraosParams,
    ledger_view: LedgerView,
    ocert_counters: Mapping[bytes, int],
    hv: HeaderView,
    crypto: CryptoVerifier = HOST_VERIFIER,
) -> None:
    """validateKESSignature (Praos.hs:558-606), same check order."""
    oc = hv.ocert
    c0 = oc.kes_period
    kp = params.kes_period_of(hv.slot)
    hk = hash_key(hv.vk_cold)

    if not c0 <= kp:
        raise KESBeforeStartOCERT(c0, kp)
    if not kp < c0 + params.max_kes_evolutions:
        raise KESAfterEndOCERT(kp, c0, params.max_kes_evolutions)

    t = kp - c0 if kp >= c0 else 0

    if not crypto.verify_dsign(hv.vk_cold, oc.signable(), oc.sigma):
        raise InvalidSignatureOCERT(oc.counter, c0)
    if not crypto.verify_kes(
        oc.vk_hot, params.kes_depth, t, hv.signed_bytes, hv.kes_sig
    ):
        raise InvalidKesSignatureOCERT(kp, c0, t)

    if hk in ocert_counters:
        m = ocert_counters[hk]
    elif hk in ledger_view.pool_distr:
        m = 0
    else:
        raise NoCounterForKeyHashOCERT(hk)
    n = oc.counter
    if not m <= n:
        raise CounterTooSmallOCERT(m, n)
    if not n <= m + 1:
        raise CounterOverIncrementedOCERT(m, n)


def validate_vrf_signature(
    epoch_nonce: Nonce,
    ledger_view: LedgerView,
    active_slot_coeff: Fraction,
    hv: HeaderView,
    crypto: CryptoVerifier = HOST_VERIFIER,
) -> None:
    """validateVRFSignature (Praos.hs:528-556), same check order."""
    hk = hash_key(hv.vk_cold)
    entry = ledger_view.pool_distr.get(hk)
    if entry is None:
        raise VRFKeyUnknown(hk)
    header_vrf_hash = hash_vrf_vk(hv.vrf_vk)
    if entry.vrf_key_hash != header_vrf_hash:
        raise VRFKeyWrongVRFKey(hk, entry.vrf_key_hash, header_vrf_hash)
    alpha = nonces.mk_input_vrf(hv.slot, epoch_nonce)
    if not crypto.verify_vrf(hv.vrf_vk, hv.vrf_proof, alpha, hv.vrf_output):
        raise VRFKeyBadProof(hv.slot, epoch_nonce)
    lv_val = nonces.vrf_leader_value(hv.vrf_output)
    if not check_leader_value(lv_val, entry.stake, active_slot_coeff):
        raise VRFLeaderValueTooBig(lv_val, entry.stake, active_slot_coeff)


def reupdate(
    params: PraosParams, hv: HeaderView, slot: int, ticked: TickedPraosState,
    eta: bytes | None = None,
) -> PraosState:
    """reupdateChainDepState (Praos.hs:468-502): bookkeeping, no crypto.
    `eta` is the header's nonce contribution where it is not Praos's
    (TPraos: Blake2b-256 of the nonce certificate's output)."""
    cs = ticked.state
    if eta is None:
        eta = nonces.vrf_nonce_value(hv.vrf_output)
    new_evolving = nonces.combine(cs.evolving_nonce, eta)
    first_slot_next_epoch = params.first_slot_of(params.epoch_of(slot) + 1)
    within_stability = slot + params.stability_window < first_slot_next_epoch
    counters = dict(cs.ocert_counters)
    counters[hash_key(hv.vk_cold)] = hv.ocert.counter
    return replace(
        cs,
        last_slot=slot,
        lab_nonce=nonces.prev_hash_to_nonce(hv.prev_hash),
        evolving_nonce=new_evolving,
        candidate_nonce=new_evolving if within_stability else cs.candidate_nonce,
        ocert_counters=counters,
    )


def update(
    params: PraosParams,
    hv: HeaderView,
    slot: int,
    ticked: TickedPraosState,
    crypto: CryptoVerifier = HOST_VERIFIER,
) -> PraosState:
    """updateChainDepState (Praos.hs:441-466): KES, then VRF, then reupdate."""
    cs = ticked.state
    validate_kes_signature(params, ticked.ledger_view, cs.ocert_counters, hv, crypto)
    validate_vrf_signature(
        cs.epoch_nonce, ticked.ledger_view, params.active_slot_coeff, hv, crypto
    )
    return reupdate(params, hv, slot, ticked)


# ---------------------------------------------------------------------------
# Forging side
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class PraosCanBeLeader:
    """Forging credentials (Praos/Common.hs:83-93)."""

    ocert: OCert
    vk_cold: bytes
    vrf_sign_seed: bytes  # VRF signing key seed


@dataclass(frozen=True)
class PraosIsLeader:
    """Proof of leadership: the certified VRF result (Praos.hs:212-216)."""

    vrf_output: bytes  # 64
    vrf_proof: bytes  # 80 (draft-03) or 128 (batch-compatible)
    # TPraos proves two certificates a block: the two above are then the
    # nonce certificate and these the leader certificate (tpraos.py)
    vrf_leader_output: bytes | None = None
    vrf_leader_proof: bytes | None = None


def check_is_leader(
    params: PraosParams,
    can_be_leader: PraosCanBeLeader,
    slot: int,
    ticked: TickedPraosState,
) -> PraosIsLeader | None:
    """checkIsLeader (Praos.hs:375-397): evaluate the VRF at
    InputVRF(slot, eta0) and test the leader threshold."""
    from ..ops.host import fast

    eta0 = ticked.state.epoch_nonce
    alpha = nonces.mk_input_vrf(slot, eta0)
    proof = fast.ecvrf_prove(can_be_leader.vrf_sign_seed, alpha)
    output = fast.ecvrf_proof_to_hash(proof)
    hk = hash_key(can_be_leader.vk_cold)
    entry = ticked.ledger_view.pool_distr.get(hk)
    sigma = entry.stake if entry is not None else Fraction(0)
    if check_leader_value(
        nonces.vrf_leader_value(output), sigma, params.active_slot_coeff
    ):
        return PraosIsLeader(output, proof)
    return None
