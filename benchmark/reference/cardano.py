"""The plain reference of a Cardano chain's header validation across the
hard forks (Byron → Shelley → Babbage) that decides `correct` in the cells
of kind `replay_cardano`.

Written from the rules (ouroboros-consensus-cardano `CardanoBlock`,
`Cardano/CanHardFork.hs`, `protocolInfoCardano` with
`TriggerHardForkAtEpoch`; recalled, not copied): the era walk over the era
history, each era's rule, and the two translations.

  era history  Byron from genesis, in epochs of its own length; Shelley
               from epoch `shelley_epoch`, Babbage from `babbage_epoch`,
               both in epochs of the Shelley genesis's length. An era's
               epoch of a slot is (slot - era start slot) / its length +
               its first epoch, and each rule that reads an epoch (the
               nonce rotation, the stability window, the overlay
               position) reads it so.
  Byron        `reference/pbft.py`.
  Shelley      TPraos: `reference/tpraos.py`'s rule with the overlay
               position counted from the ERA's epoch start (`overlay`
               below; the copy there counts from slot 0, right for a
               chain that is Shelley from genesis).
  Babbage      Praos: `reference/praos.py`'s `check`; its tick and
               bookkeeping on the era's clock.
  Byron → Shelley  translateChainDepStateByronToShelley: evolving,
               candidate and epoch nonce the Shelley genesis nonce, the
               previous-hash and last-epoch-block nonces neutral, no OCert
               counters (a genesis delegate with none starts at 0, as in
               TPraos), the last slot the PBFT state's last signed slot.
               The first Shelley header's tick is an epoch change: the
               epoch nonce becomes candidate ⭒ neutral, the genesis nonce.
  Shelley → Babbage  the state as it is (nonces, counters, last slot);
               the first Babbage tick is an ordinary epoch rotation.

It imports nothing of the program: the chain is read from the chunk files
(era-tagged blocks [era, inner block bytes]) with `reference/praos.py`'s
CBOR reader and each era's header reader.
"""

from __future__ import annotations

import math
import os
from dataclasses import dataclass, replace
from fractions import Fraction

from . import ecvrf, ed25519, kes, pbft, praos, tpraos
from .hashes import blake2b_224, blake2b_256
from .praos import _cbor_item, _combine


# ---------------------------------------------------------------------------
# the chain
# ---------------------------------------------------------------------------


def read_chain(db_path: str) -> list:
    """Every header of the store at `db_path` as (era, header), in chain
    order."""
    imm = os.path.join(db_path, "immutable")
    out: list = []
    for name in sorted(n for n in os.listdir(imm) if n.endswith(".chunk")):
        with open(os.path.join(imm, name), "rb") as f:
            buf = f.read()
        i = 0
        while i < len(buf):
            (era, inner), i = _cbor_item(buf, i)
            reader = (pbft.header_at, tpraos._header_at,
                      praos._header_at)[era]
            out.append((era, reader(inner, 0)[0]))
    return out


# ---------------------------------------------------------------------------
# the deployment
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class CParams:
    """The three eras: Byron's PBFT numbers and epoch length; the Shelley
    and Babbage rules' numbers (`tpraos.TParams`, `praos.Params`); the
    epochs the hard forks come at; the Shelley genesis nonce."""

    byron: pbft.Params
    byron_epoch_length: int
    shelley: tpraos.TParams
    babbage: praos.Params
    shelley_epoch: int
    babbage_epoch: int
    genesis_nonce: bytes

    @property
    def shelley_start(self) -> int:
        return self.shelley_epoch * self.byron_epoch_length

    @property
    def babbage_start(self) -> int:
        return self.shelley_start + (self.babbage_epoch - self.shelley_epoch
                                     ) * self.shelley.epoch_length

    def era_of(self, slot: int) -> int:
        return 2 if slot >= self.babbage_start else (
            1 if slot >= self.shelley_start else 0)

    def epoch_of(self, slot: int) -> int:
        """The epoch of `slot` on the era history (a slot before an era's
        start counted on that era's clock falls in an earlier epoch)."""
        era = self.era_of(slot)
        if era == 0:
            return slot // self.byron_epoch_length
        return self._epoch_in(era, slot)

    def _epoch_in(self, era: int, slot: int) -> int:
        start, first = ((self.shelley_start, self.shelley_epoch) if era == 1
                        else (self.babbage_start, self.babbage_epoch))
        return (slot - start) // self.shelley.epoch_length + first

    def _first_slot(self, era: int, epoch: int) -> int:
        start, first = ((self.shelley_start, self.shelley_epoch) if era == 1
                        else (self.babbage_start, self.babbage_epoch))
        return start + (epoch - first) * self.shelley.epoch_length


# ---------------------------------------------------------------------------
# the Praos-class eras on the era's clock
# ---------------------------------------------------------------------------


def tick(cp: CParams, era: int, slot: int, st: praos.State) -> praos.State:
    """tickChainDepState on the era's clock: a change of epoch rotates the
    epoch nonce (candidate ⭒ last-epoch-block nonce) and latches the
    previous-hash nonce."""
    old = 0 if st.last_slot is None else cp._epoch_in(era, st.last_slot)
    if cp._epoch_in(era, slot) > old:
        st = replace(st, epoch_nonce=_combine(st.candidate_nonce,
                                              st.last_epoch_block_nonce),
                     last_epoch_block_nonce=st.lab_nonce)
    return st


def reupdate(cp: CParams, era: int, h, ticked: praos.State) -> praos.State:
    """The bookkeeping of an accepted header: its nonce contribution
    (Shelley: H(beta_eta); Babbage: H(H("N" ‖ beta))), the candidate
    following up to 3k/f slots before its epoch ends, the counter, the
    previous hash, the slot."""
    p = cp.shelley if era == 1 else cp.babbage
    if era == 1:
        eta = blake2b_256(h.vrf_output)
    else:
        eta = blake2b_256(blake2b_256(b"N" + h.vrf_output))
    evolving = _combine(ticked.evolving_nonce, eta)
    next_first = cp._first_slot(era, cp._epoch_in(era, h.slot) + 1)
    within = h.slot + p.stability_window < next_first
    counters = dict(ticked.counters)
    counters[blake2b_224(h.vk_cold)] = h.ocert_counter
    return replace(
        ticked, last_slot=h.slot, lab_nonce=h.prev_hash,
        evolving_nonce=evolving,
        candidate_nonce=evolving if within else ticked.candidate_nonce,
        counters=tuple(sorted(counters.items())))


def overlay(cp: CParams, slot: int):
    """The overlay schedule with the position counted from the Shelley
    era's epoch start: None (the lottery's slot), ("inactive",) or
    ("active", j), genesis delegate j's."""
    p = cp.shelley
    d = Fraction(p.decentralisation)
    i = slot - cp._first_slot(1, cp._epoch_in(1, slot))
    lo, hi = math.ceil(i * d), math.ceil((i + 1) * d)
    if hi <= lo:
        return None
    asc_inv = max(1, math.ceil(1 / Fraction(p.active_slot_coeff)))
    n = len(p.gen_delegs)
    if lo % asc_inv or not n:
        return ("inactive",)
    return ("active", (lo // asc_inv) % n)


def check_shelley(cp: CParams, pool_distr: dict, h, ticked: praos.State,
                  crypto: bool = True):
    """`reference/tpraos.check` with the era's overlay (`overlay`): the
    KES window, the OCert and KES signatures, the counter, who may issue
    in the slot, both certificates, the 512-bit leader rule."""
    p = cp.shelley
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
    if crypto:
        signable = (h.ocert_vk_hot + h.ocert_counter.to_bytes(8, "big")
                    + c0.to_bytes(8, "big"))
        if not ed25519.verify(h.vk_cold, signable, h.ocert_sigma):
            return "InvalidSignatureOCERT", {"counter": h.ocert_counter,
                                             "kes_period": c0}
        if not kes.verify(h.ocert_vk_hot, p.kes_depth, kp - c0,
                          h.signed_bytes, h.kes_sig):
            return "InvalidKesSignatureOCERT", {
                "current_period": kp, "start_period": c0,
                "expected_evolutions": kp - c0}
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
    stake = None
    over = overlay(cp, h.slot)
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
                            tpraos.mk_seed(tpraos.SEED_ETA, h.slot, eta0))
        if beta is None or beta != h.vrf_output:
            return "VRFKeyBadNonce", {"slot": h.slot, "epoch_nonce": eta0}
        beta = ecvrf.verify(h.vrf_vk, h.vrf_leader_proof,
                            tpraos.mk_seed(tpraos.SEED_L, h.slot, eta0))
        if beta is None or beta != h.vrf_leader_output:
            return "VRFKeyBadLeaderValue", {"slot": h.slot,
                                            "epoch_nonce": eta0}
    if stake is not None:
        lv = int.from_bytes(h.vrf_leader_output, "big")
        if not tpraos.wins(lv, stake, p.active_slot_coeff):
            return "VRFLeaderValueTooBig", {
                "leader_value": lv, "sigma": stake,
                "active_slot_coeff": p.active_slot_coeff}
    return None


# ---------------------------------------------------------------------------
# the walk
# ---------------------------------------------------------------------------


def translate(cp: CParams, era: int, st):
    """The state of era `era - 1` into era `era`."""
    if era == 1:
        last = st.signers[-1][0] if st.signers else None
        n = cp.genesis_nonce
        return praos.State(last_slot=last, evolving_nonce=n,
                           candidate_nonce=n, epoch_nonce=n)
    return st


def state_doc(era: int, st) -> dict:
    return {"era": era, **st.doc()}


@dataclass
class Walked:
    n_valid: int
    error: tuple | None  # (name, fields) of the first failing header
    era: int  # of `state`
    state: object  # pbft.State or praos.State after the last accepted
    crossings: list  # [(from era, to era, doc before, doc after), ...]
    n_crypto: int  # headers whose signatures were verified


def replay(cp: CParams, pool_distr: dict, headers, st=None, era: int = 0,
           crypto_at=None) -> Walked:
    """The sequential walk: at a header of a later era the state is
    translated; then tick, check, update, stopping at the first failure.
    `crypto_at` is the set of indices whose signatures are verified (None:
    all of them); every other rule, and the whole state, is computed for
    every header."""
    st = pbft.State() if st is None else st
    crossings: list = []
    n_crypto = 0
    for i, (h_era, h) in enumerate(headers):
        while era < h_era:
            before = state_doc(era, st)
            era += 1
            st = translate(cp, era, st)
            crossings.append((era - 1, era, before, state_doc(era, st)))
        full = crypto_at is None or i in crypto_at
        if era == 0:
            err = pbft.check(cp.byron, h, st, crypto=full)
            if err is not None:
                return Walked(i, err, era, st, crossings, n_crypto)
            st = pbft.update(cp.byron, h, st)
        else:
            ticked = tick(cp, era, h.slot, st)
            if era == 1:
                err = check_shelley(cp, pool_distr, h, ticked, crypto=full)
            else:
                err = praos.check(cp.babbage, pool_distr, h, ticked,
                                  crypto=full)
            if err is not None:
                return Walked(i, err, era, st, crossings, n_crypto)
            st = reupdate(cp, era, h, ticked)
        n_crypto += full and not (era == 0 and h.ebb)
    return Walked(len(headers), None, era, st, crossings, n_crypto)
