"""The plain reference of TPraos header validation that decides `correct`
in the cells of kind `replay_tpraos`.

TPraos is the protocol of the Shelley, Allegra, Mary and Alonzo eras. This
is a straightforward sequential implementation written from the published
rules (ouroboros-consensus-protocol `Protocol/TPraos.hs`; cardano-protocol-
tpraos `BHeader.hs`: `bheaderEta`, `bheaderL`, `mkSeed`, `seedEta`, `seedL`,
`checkLeaderValue`, and the STS rules PRTCL, OVERLAY, UPDN, OCERT; the
Shelley ledger's `overlaySchedule`), recalled, not copied: pure Python on
big integers, `Fraction`s and hashlib. H is Blake2b-256, `‖` concatenation.

  seeds    mkSeed(uc, slot, eta0) = H(be8(slot) ‖ eta0) XOR uc, with
           seedEta = H(be8(0)), seedL = H(be8(1)); a neutral eta0
           contributes no bytes. A header carries TWO certified VRF results
           under the one registered VRF key: the nonce certificate over
           mkSeed(seedEta, ..) and the leader certificate over
           mkSeed(seedL, ..), 80-byte draft-03 proofs, 64-byte outputs.
           Both proofs are verified and both declared outputs compared for
           every header, overlay or not.
  leader   nat(beta_L) / 2^512 < 1 - (1 - f)^sigma, beta_L the RAW 64-byte
           output of the leader proof read big-endian.
  nonce    eta_v' = H(eta_v ‖ H(beta_eta)); the candidate stops following
           3k/f slots before the epoch ends; the rotation at the boundary
           is Praos's.
  overlay  slot i of an epoch is an overlay slot iff ceil((i+1) d) >
           ceil(i d); its position is ceil(i d); every ascInv = ceil(1/f)-th
           position is ACTIVE and belongs to genesis delegate
           (position / ascInv) mod n: there the issuer's cold key and VRF
           key hash must be that delegate's, both proofs, the OCert and the
           KES signature are checked and NO threshold is. A block in an
           inactive overlay slot is invalid. A delegate with no counter yet
           starts at 0, as a pool does.

Departures from the published rules, each the program's too (the
configuration lists them under `assumed`): the OCert / KES checks come
BEFORE the overlay and VRF checks (PRTCL runs OVERLAY first; one order for
both of this repo's protocols, so that an error is named alike); CompactSum
KES where mainnet has Sum6KES; `d` is one number for the chain where
mainnet changed it each epoch; the header body's CBOR is this repo's
(11 fields: Praos's 10 with the leader certificate after the nonce
certificate), read here from the chunk files' bytes.

It imports nothing of the program. From `reference/praos.py` it takes what
is the same rule for rule (the CBOR item reader, `Params`' fields, `State`,
`tick`, the nonce combination); the signature schemes are
`reference/{ed25519,ecvrf,kes}.py`, the leader series `reference/leader.py`.
"""

from __future__ import annotations

import math
import os
from dataclasses import dataclass, replace
from fractions import Fraction

from . import ecvrf, ed25519, kes, leader
from .hashes import blake2b_224, blake2b_256
from .praos import (Params, Replayed, State, _array_head, _cbor_item,
                    _combine, tick)

SEED_ETA = blake2b_256((0).to_bytes(8, "big"))
SEED_L = blake2b_256((1).to_bytes(8, "big"))
LEADER_VALUE_MAX = 1 << 512


@dataclass(frozen=True)
class Header:
    """What validation reads of one TPraos header."""

    slot: int
    prev_hash: bytes | None
    vk_cold: bytes
    vrf_vk: bytes
    vrf_output: bytes  # the nonce certificate: beta_eta, 64
    vrf_proof: bytes  # its proof, 80
    vrf_leader_output: bytes  # the leader certificate: beta_L, 64
    vrf_leader_proof: bytes  # its proof, 80
    ocert_vk_hot: bytes
    ocert_counter: int
    ocert_kes_period: int
    ocert_sigma: bytes
    signed_bytes: bytes  # the header body's CBOR: what the KES key signed
    kes_sig: bytes


def _header_at(buf: bytes, i: int):
    """One block [[body, kes_sig], txs] at buf[i:]; -> (Header, end)."""
    j = _array_head(buf, _array_head(buf, i))  # start of the header body
    body, body_end = _cbor_item(buf, j)
    sig, k = _cbor_item(buf, body_end)
    _txs, end = _cbor_item(buf, k)
    (_bn, slot, prev, ivk, vvk, (eout, eproof), (lout, lproof), _bsz, _bh,
     oc, _pv) = body
    return Header(slot, prev, ivk, vvk, eout, eproof, lout, lproof, oc[0],
                  oc[1], oc[2], oc[3], bytes(buf[j:body_end]), sig), end


def read_chain(db_path: str) -> list[Header]:
    """Every header of the ImmutableDB at `db_path`, in chain order."""
    imm = os.path.join(db_path, "immutable")
    out: list[Header] = []
    for name in sorted(n for n in os.listdir(imm) if n.endswith(".chunk")):
        with open(os.path.join(imm, name), "rb") as f:
            buf = f.read()
        i = 0
        while i < len(buf):
            h, i = _header_at(buf, i)
            out.append(h)
    return out


@dataclass(frozen=True)
class TParams:
    """The deployment: Praos's numbers (`base`), the decentralisation
    parameter d and the genesis delegates in their order, each a
    (cold key, VRF key hash) pair."""

    base: Params
    decentralisation: Fraction
    gen_delegs: tuple

    def __getattr__(self, name):
        return getattr(self.base, name)


def mk_seed(uc: bytes, slot: int, epoch_nonce: bytes | None) -> bytes:
    base = blake2b_256(slot.to_bytes(8, "big") + (epoch_nonce or b""))
    return bytes(a ^ b for a, b in zip(base, uc))


def overlay(p: TParams, slot: int):
    """None: the lottery's slot. ("inactive",): an overlay slot nobody
    may fill. ("active", j): genesis delegate j's."""
    d = Fraction(p.decentralisation)
    i = slot % p.epoch_length  # epochs start at multiples of the length
    lo, hi = math.ceil(i * d), math.ceil((i + 1) * d)
    if hi <= lo:
        return None
    asc_inv = max(1, math.ceil(1 / Fraction(p.active_slot_coeff)))
    n = len(p.gen_delegs)
    if lo % asc_inv or not n:
        return ("inactive",)
    return ("active", (lo // asc_inv) % n)


def wins(leader_value: int, sigma: Fraction, f: Fraction) -> bool:
    """nat(beta_L) / 2^512 < 1 - (1 - f)^sigma. Exact in rationals where
    sigma is a whole number (one pool holds all the stake: 1 - (1-f));
    else 1/(1-p) < exp(-sigma ln(1-f)) by `leader.py`'s series, tightened
    until it decides, as its 256-bit check does."""
    f, sigma = Fraction(f), Fraction(sigma)
    if f == 1:
        return True
    if sigma == 0:
        return False
    if sigma.denominator == 1:
        return (Fraction(leader_value, LEADER_VALUE_MAX)
                < 1 - (1 - f) ** sigma.numerator)
    lhs = Fraction(LEADER_VALUE_MAX, LEADER_VALUE_MAX - leader_value)
    for terms in (8, 16, 32, 64, 128):
        llo, lhi = leader._neg_log1m_interval(f, terms)
        elo, ehi = leader._exp_interval(sigma * llo, sigma * lhi, terms)
        if lhs < elo:
            return True
        if lhs >= ehi:
            return False
    return lhs < (elo + ehi) / 2


def reupdate(p: TParams, h: Header, ticked: State) -> State:
    """UPDN and the counter bookkeeping of an accepted header."""
    evolving = _combine(ticked.evolving_nonce, blake2b_256(h.vrf_output))
    next_epoch_first = (h.slot // p.epoch_length + 1) * p.epoch_length
    within = h.slot + p.stability_window < next_epoch_first
    counters = dict(ticked.counters)
    counters[blake2b_224(h.vk_cold)] = h.ocert_counter
    return replace(
        ticked,
        last_slot=h.slot,
        lab_nonce=h.prev_hash,
        evolving_nonce=evolving,
        candidate_nonce=evolving if within else ticked.candidate_nonce,
        counters=tuple(sorted(counters.items())),
    )


def check(p: TParams, pool_distr: dict, h: Header, ticked: State,
          crypto: bool = True):
    """One header against the ticked state. -> None when it passes, else
    (error name, {field: value}), the program's names and fields.
    `crypto=False` leaves out the four signature checks (OCert, KES, the
    two proofs) and keeps every other rule."""
    c0 = h.ocert_kes_period
    kp = h.slot // p.slots_per_kes_period
    hk = blake2b_224(h.vk_cold)
    if not c0 <= kp:
        return "KESBeforeStartOCERT", {"ocert_start_period": c0,
                                       "current_period": kp}
    if not kp < c0 + p.max_kes_evolutions:
        return "KESAfterEndOCERT", {
            "current_period": kp, "ocert_start_period": c0,
            "max_kes_evolutions": p.max_kes_evolutions}
    t = kp - c0
    if crypto:
        signable = (h.ocert_vk_hot + h.ocert_counter.to_bytes(8, "big")
                    + c0.to_bytes(8, "big"))
        if not ed25519.verify(h.vk_cold, signable, h.ocert_sigma):
            return "InvalidSignatureOCERT", {"counter": h.ocert_counter,
                                             "kes_period": c0}
        if not kes.verify(h.ocert_vk_hot, p.kes_depth, t, h.signed_bytes,
                          h.kes_sig):
            return "InvalidKesSignatureOCERT", {
                "current_period": kp, "start_period": c0,
                "expected_evolutions": t}
    counters = dict(ticked.counters)
    if hk in counters:
        m = counters[hk]
    elif hk in pool_distr or any(hk == blake2b_224(cold)
                                 for cold, _vrf in p.gen_delegs):
        m = 0
    else:
        return "NoCounterForKeyHashOCERT", {"pool_key_hash": hk}
    n = h.ocert_counter
    if not m <= n:
        return "CounterTooSmallOCERT", {"last_counter": m,
                                        "current_counter": n}
    if not n <= m + 1:
        return "CounterOverIncrementedOCERT", {"last_counter": m,
                                               "current_counter": n}
    # who may issue in this slot
    stake = None
    over = overlay(p, h.slot)
    if over is None:
        entry = pool_distr.get(hk)
        if entry is None:
            return "VRFKeyUnknown", {"pool_key_hash": hk}
        stake, vrf_key_hash = entry
        if vrf_key_hash != blake2b_256(h.vrf_vk):
            return "VRFKeyWrongVRFKey", {
                "pool_key_hash": hk, "registered_vrf_hash": vrf_key_hash,
                "header_vrf_hash": blake2b_256(h.vrf_vk)}
    elif over[0] == "inactive":
        return "NonActiveSlot", {"slot": h.slot}
    else:
        cold, vrf_key_hash = p.gen_delegs[over[1]]
        if h.vk_cold != cold:
            return "WrongGenesisDelegate", {"slot": h.slot, "expected": cold,
                                            "got": h.vk_cold}
        if blake2b_256(h.vrf_vk) != vrf_key_hash:
            return "WrongGenesisVRFKey", {
                "slot": h.slot, "expected": vrf_key_hash,
                "got": blake2b_256(h.vrf_vk)}
    if crypto:
        eta0 = ticked.epoch_nonce
        beta = ecvrf.verify(h.vrf_vk, h.vrf_proof,
                            mk_seed(SEED_ETA, h.slot, eta0))
        if beta is None or beta != h.vrf_output:
            return "VRFKeyBadNonce", {"slot": h.slot, "epoch_nonce": eta0}
        beta = ecvrf.verify(h.vrf_vk, h.vrf_leader_proof,
                            mk_seed(SEED_L, h.slot, eta0))
        if beta is None or beta != h.vrf_leader_output:
            return "VRFKeyBadLeaderValue", {"slot": h.slot,
                                            "epoch_nonce": eta0}
    if stake is not None:
        lv = int.from_bytes(h.vrf_leader_output, "big")
        if not wins(lv, stake, p.active_slot_coeff):
            return "VRFLeaderValueTooBig", {
                "leader_value": lv, "sigma": stake,
                "active_slot_coeff": p.active_slot_coeff}
    return None


def replay(p: TParams, pool_distr: dict, headers, st: State = State(),
           crypto_at=None) -> Replayed:
    """The sequential fold: tick, check, reupdate, header by header,
    stopping at the first failure. `crypto_at` is the set of indices whose
    signatures are verified (None: all of them); every other rule, and the
    whole state, is computed for every header."""
    n_crypto = 0
    for i, h in enumerate(headers):
        ticked = tick(p, h.slot, st)
        full = crypto_at is None or i in crypto_at
        err = check(p, pool_distr, h, ticked, crypto=full)
        if err is not None:
            return Replayed(i, err, st, n_crypto)
        n_crypto += full
        st = reupdate(p, h, ticked)
    return Replayed(len(headers), None, st, n_crypto)
