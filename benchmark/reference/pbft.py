"""The plain reference of PBFT header validation (the Byron era) that
decides `correct` in the cells of kind `replay_cardano`.

A straightforward sequential implementation written from the published
rules (ouroboros-consensus `Protocol/PBFT.hs`: `updateChainDepState` for
`PBftValidateRegular` / `PBftValidateBoundary`, `pbftWindowParams`; the
window of `PBFT/State.hs`), recalled, not copied: pure Python, `Fraction`s
and hashlib. For a main header, in order:

  signature  the delegate's Ed25519 signature over the header's signed
             bytes (its ToSign) verifies, else PBftInvalidSignature;
  slot       the slot is not before the last signed slot, else
             PBftInvalidSlot (equal is allowed: an EBB shares its slot);
  delegate   the issuer (the delegate key) is in the delegation map,
             which names its genesis key, else PBftNotGenesisDelegate;
  threshold  with the header appended, the window holds the last k signed
             blocks; its genesis key may have signed at most
             floor(threshold x k) of them, this one included, else
             PBftExceededSignThreshold (exact integers).

An epoch boundary block carries no signature and changes nothing.

Departures from the published rules, each the program's too (the
configuration lists them under `assumed`): the header's CBOR is this
repo's (`hardfork/byron_mock.py`: ToSign's integers as 8-byte strings, so
every main header signs 255 bytes; the EBB's slot where Byron has its
epoch), read here from the chunk files' bytes; the signed message is the
ToSign's CBOR as it is on the wire, without Byron's sign tag and key
prefix. The certificate is read for the genesis key; its signature is the
ledger's check, not PBFT's, and is not made here.

It imports nothing of the program: the CBOR item reader is
`reference/praos.py`'s, Ed25519 `reference/ed25519.py`.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from fractions import Fraction

from . import ed25519
from .praos import _array_head, _cbor_item


@dataclass(frozen=True)
class Header:
    """What PBFT reads of one Byron header."""

    ebb: bool
    slot: int
    block_no: int
    prev_hash: bytes | None
    delegate_vk: bytes  # b"" for an EBB
    signature: bytes  # b"" for an EBB
    signed_bytes: bytes  # the ToSign's CBOR, what the delegate signed


def header_at(buf: bytes, i: int):
    """One Byron block [kind, header, txs] at buf[i:]; -> (Header, end)."""
    j = _array_head(buf, i)
    kind, j = _cbor_item(buf, j)
    if kind == 0:  # [magic, prev, slot8, difficulty8, proof]
        (_magic, prev, slot, bn, _proof), j = _cbor_item(buf, j)
        _txs, end = _cbor_item(buf, j)
        return Header(True, int.from_bytes(slot, "big"),
                      int.from_bytes(bn, "big"), prev, b"", b"", b""), end
    j = _array_head(buf, j)  # [to_sign, vk, sig, cert]
    to_sign, k = _cbor_item(buf, j)
    signed = bytes(buf[j:k])
    vk, k = _cbor_item(buf, k)
    sig, k = _cbor_item(buf, k)
    _cert, k = _cbor_item(buf, k)
    _txs, end = _cbor_item(buf, k)
    _magic, prev, _proof, slot, bn, _bv, _sv = to_sign
    return Header(False, int.from_bytes(slot, "big"),
                  int.from_bytes(bn, "big"), prev, vk, sig, signed), end


@dataclass(frozen=True)
class Params:
    """PBftParams: the window is k signed blocks; `delegates` the
    delegation map, delegate key -> its genesis key's index."""

    window: int
    threshold: Fraction
    delegates: dict

    @property
    def allowed(self) -> int:
        return math.floor(Fraction(self.threshold) * self.window)


@dataclass(frozen=True)
class State:
    """The last k signers as ((slot, genesis key index), ...), oldest
    first."""

    signers: tuple = ()

    def doc(self) -> dict:
        return {"signers": [list(s) for s in self.signers]}


def check(p: Params, h: Header, st: State, crypto: bool = True):
    """One header against the state. -> None when it passes, else (error
    name, {field: value}), the program's names and fields. `crypto=False`
    leaves the signature out and keeps every other rule."""
    if h.ebb:
        return None
    if crypto and not ed25519.verify(h.delegate_vk, h.signed_bytes,
                                     h.signature):
        return "PBftInvalidSignature", {"slot": h.slot}
    if st.signers and h.slot < st.signers[-1][0]:
        return "PBftInvalidSlot", {"slot": h.slot,
                                   "last_signed": st.signers[-1][0]}
    gk = p.delegates.get(h.delegate_vk)
    if gk is None:
        return "PBftNotGenesisDelegate", {"slot": h.slot,
                                          "issuer_vk": h.delegate_vk}
    window = (st.signers + ((h.slot, gk),))[-p.window:]
    signed = sum(1 for _s, g in window if g == gk)
    if signed > p.allowed:
        return "PBftExceededSignThreshold", {
            "slot": h.slot, "genesis_key": gk, "signed": signed,
            "allowed": p.allowed}
    return None


def update(p: Params, h: Header, st: State) -> State:
    """The state after an accepted header."""
    if h.ebb:
        return st
    gk = p.delegates[h.delegate_vk]
    return State((st.signers + ((h.slot, gk),))[-p.window:])
