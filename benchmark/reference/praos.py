"""The plain reference of Praos header validation that decides `correct`.

A straightforward sequential implementation of what the configurations
state: per header the KES-period window, the OCert Ed25519 signature, the
CompactSum KES signature over the header body, the OCert counter rule, the
registered VRF key, the ECVRF proof (80-byte draft-03 or 128-byte
batch-compatible, by length), the leader threshold, and the nonce fold with
the epoch rotation (Praos.hs tick/update/reupdate). Pure Python on big
integers and hashlib. It imports nothing of the program: the chain is read
from the bytes on disk with the small CBOR reader below, and the pool
distribution is handed over as plain values.

Check order and error fields follow `protocol/praos.py` of the program at
PR 27 (listed in PERF.md's Open questions as the original of this copy).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, replace
from fractions import Fraction

from . import ecvrf, ed25519, kes
from .hashes import blake2b_224, blake2b_256
from .leader import check_leader_value

# ---------------------------------------------------------------------------
# reading the chain: definite-length CBOR, as the program's encoder writes it
# ---------------------------------------------------------------------------


def _cbor_item(buf: bytes, i: int):
    """Decode one CBOR item at buf[i:]; -> (value, end offset)."""
    b = buf[i]
    major, info = b >> 5, b & 31
    i += 1
    if major == 7:
        if info in (20, 21):
            return info == 21, i
        if info == 22:
            return None, i
        raise ValueError(f"CBOR simple value {info} not expected in a block")
    if info < 24:
        n = info
    elif info in (24, 25, 26, 27):
        w = 1 << (info - 24)
        n = int.from_bytes(buf[i:i + w], "big")
        i += w
    else:
        raise ValueError("indefinite-length CBOR not expected in a block")
    if major == 0:
        return n, i
    if major == 1:
        return -1 - n, i
    if major == 2:
        return bytes(buf[i:i + n]), i + n
    if major == 3:
        return bytes(buf[i:i + n]).decode(), i + n
    if major == 4:
        out = []
        for _ in range(n):
            v, i = _cbor_item(buf, i)
            out.append(v)
        return out, i
    if major == 5:
        d = {}
        for _ in range(n):
            k, i = _cbor_item(buf, i)
            v, i = _cbor_item(buf, i)
            d[k] = v
        return d, i
    raise ValueError(f"CBOR major type {major} not expected in a block")


def _array_head(buf: bytes, i: int) -> int:
    """Offset just past the head of the array that starts at buf[i]."""
    info = buf[i] & 31
    if buf[i] >> 5 != 4:
        raise ValueError("expected a CBOR array")
    return i + 1 + (0 if info < 24 else 1 << (info - 24))


@dataclass(frozen=True)
class Header:
    """What validation reads of one header (Praos/Views.hs HeaderView)."""

    slot: int
    prev_hash: bytes | None
    vk_cold: bytes
    vrf_vk: bytes
    vrf_output: bytes
    vrf_proof: bytes
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
    (_bn, slot, prev, ivk, vvk, (vout, vproof), _bsz, _bh, oc, _pv) = body
    return Header(slot, prev, ivk, vvk, vout, vproof, oc[0], oc[1], oc[2],
                  oc[3], bytes(buf[j:body_end]), sig), end


def read_chain(db_path: str) -> list[Header]:
    """Every header of the ImmutableDB at `db_path`, in chain order: the
    chunk files are the blocks' CBOR one after another."""
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


# ---------------------------------------------------------------------------
# parameters, state, errors
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Params:
    slots_per_kes_period: int
    max_kes_evolutions: int
    security_param: int
    active_slot_coeff: Fraction
    epoch_length: int
    kes_depth: int

    @property
    def stability_window(self) -> int:
        w = 3 * self.security_param / self.active_slot_coeff
        return int(-(-w // 1))


@dataclass(frozen=True)
class State:
    """PraosState (Praos.hs:248-264). A nonce is 32 bytes or None."""

    last_slot: int | None = None
    counters: tuple = ()  # ((pool key hash, counter), ...) sorted
    evolving_nonce: bytes | None = None
    candidate_nonce: bytes | None = None
    epoch_nonce: bytes | None = None
    lab_nonce: bytes | None = None
    last_epoch_block_nonce: bytes | None = None

    def doc(self) -> dict:
        return {
            "last_slot": self.last_slot,
            "counters": {k.hex(): v for k, v in self.counters},
            **{f: (getattr(self, f) or b"").hex()
               for f in ("evolving_nonce", "candidate_nonce", "epoch_nonce",
                         "lab_nonce", "last_epoch_block_nonce")},
        }


def _combine(a, b):
    if a is None:
        return b
    if b is None:
        return a
    return blake2b_256(a + b)


def tick(p: Params, slot: int, st: State) -> State:
    """tickChainDepState: on an epoch change rotate the epoch nonce."""
    old = 0 if st.last_slot is None else st.last_slot // p.epoch_length
    if slot // p.epoch_length > old:
        st = replace(
            st,
            epoch_nonce=_combine(st.candidate_nonce,
                                 st.last_epoch_block_nonce),
            last_epoch_block_nonce=st.lab_nonce,
        )
    return st


def reupdate(p: Params, h: Header, ticked: State) -> State:
    """reupdateChainDepState: the bookkeeping of an accepted header."""
    eta = blake2b_256(blake2b_256(b"N" + h.vrf_output))
    evolving = _combine(ticked.evolving_nonce, eta)
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


def check(p: Params, pool_distr: dict, h: Header, ticked: State,
          crypto: bool = True):
    """updateChainDepState's checks on one header against the ticked
    state, in the protocol's order. -> None when it passes, else
    (error name, {field: value}). `crypto=False` leaves out the three
    signature checks and keeps every other rule (used for the lanes
    outside the sample; see `replay`)."""
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
    elif hk in pool_distr:
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
    entry = pool_distr.get(hk)
    if entry is None:
        return "VRFKeyUnknown", {"pool_key_hash": hk}
    stake, vrf_key_hash = entry
    if vrf_key_hash != blake2b_256(h.vrf_vk):
        return "VRFKeyWrongVRFKey", {
            "pool_key_hash": hk, "registered_vrf_hash": vrf_key_hash,
            "header_vrf_hash": blake2b_256(h.vrf_vk)}
    if crypto:
        alpha = blake2b_256(h.slot.to_bytes(8, "big")
                            + (ticked.epoch_nonce or b""))
        beta = ecvrf.verify(h.vrf_vk, h.vrf_proof, alpha)
        if beta is None or beta != h.vrf_output:
            return "VRFKeyBadProof", {"slot": h.slot,
                                      "epoch_nonce": ticked.epoch_nonce}
    lv = int.from_bytes(blake2b_256(b"L" + h.vrf_output), "big")
    if not check_leader_value(lv, stake, p.active_slot_coeff):
        return "VRFLeaderValueTooBig", {
            "leader_value": lv, "sigma": stake,
            "active_slot_coeff": p.active_slot_coeff}
    return None


@dataclass
class Replayed:
    n_valid: int
    error: tuple | None  # (name, fields) of the first failing header
    state: State  # after the last accepted header
    n_crypto: int  # headers whose three signatures were verified


def replay(p: Params, pool_distr: dict, headers, st: State = State(),
           crypto_at=None) -> Replayed:
    """The sequential fold: tick, check, reupdate, header by header,
    stopping at the first failure. `crypto_at` is the set of indices whose
    signatures are verified (None: all of them). Every other rule, and the
    whole state, is computed for every header: pure-Python curve
    arithmetic costs ~20 ms a header, so a run verifies a sample drawn
    from its seed and a test at a tiny size verifies all."""
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
