"""The Byron era's block and header, Byron-SHAPED: PBFT over Ed25519
delegate-signed headers.

Reference shape: `cardano-ledger-byron` `Block/Header.hs` (a main
header is its `ToSign` — protocol magic, previous hash, body proof,
consensus data, extra data — plus the delegate's signature and the heavy
delegation certificate that names the genesis key) and `Block/Boundary`
(the epoch boundary block, EBB), under `Protocol/PBFT.hs` (signing
window). It keeps the fields consensus reads, at the widths they have on
mainnet, in this repository's CBOR:

  block      = [kind, header, [tx, ...]]     kind 1: main, 0: EBB
  main hdr   = [to_sign, delegate_vk(32), sig(64), cert]
  to_sign    = [magic, prev_hash(32)|null,
                [tx_count8, tx_root(32), wit(32), ssc(32), dlg(32), upd(32)],
                slot8, difficulty8, [1, 0, 0], ["cardano-sl", 1]]
  cert       = [epoch8, genesis_vk(32), delegate_vk(32), cert_sig(64)]
  EBB hdr    = [magic, prev_hash(32)|null, slot8, difficulty8,
                body_proof(32)]

`x8` is an 8-byte big-endian string: the integers a header carries are
fixed-width here where Byron's CBOR makes them minimal (assumed), so
every main header of a chain signs the same number of bytes: 255 with a
previous hash (`BYRON_SIGNED_LEN`), so R ‖ A ‖ M is 319 bytes, THREE
SHA-512 blocks for the Ed25519 check where an OCert's 112-byte message
takes one. The delegate signs `to_sign` as it is on the wire (the
span `signed_bytes`; Byron's sign tag and key prefix are left out). The
certificate is read for the genesis key; its own signature is the Byron
ledger's check (`ledger/byron.py`), not PBFT's, and is not asked here.

This is the first era of the Cardano chain (hardfork/cardano.py, the
normal path: tools/db_synthesizer forges it, tools/db_analyser replays
it) and of the toy composite (hardfork/composite.py).
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass
from functools import cached_property, lru_cache
from typing import Sequence

from ..block.abstract import Point
from ..ops.host import ed25519 as host_ed25519
from ..protocol.instances import PBFT_BOUNDARY_VIEW as BOUNDARY_VIEW
from ..protocol.instances import PBftView
from ..utils import cbor

PROTOCOL_MAGIC = 764824073  # mainnet's
BLOCK_VERSION = [1, 0, 0]
SOFTWARE_VERSION = ["cardano-sl", 1]
MAIN, EBB = 1, 0  # a block's kind


def _b2b(data: bytes) -> bytes:
    return hashlib.blake2b(data, digest_size=32).digest()


def _be8(n: int) -> bytes:
    return int(n).to_bytes(8, "big")


def _int8(b: bytes) -> int:
    return int.from_bytes(b, "big")


# the hashes of the body parts consensus does not read (witnesses, SSC,
# delegation and update payloads of an empty body): constants, carried
# at their width
_EMPTY_PROOFS = tuple(_b2b(b"byron-" + t) for t in
                      (b"witnesses", b"ssc", b"dlg", b"update"))


@dataclass(frozen=True)
class ByronMockHeader:
    """A Byron main header or, `is_ebb`, an epoch boundary header.

    An EBB (Block/EBB.hs, Byron/EBBs.hs) is unsigned and empty, shares
    its epoch's first slot and its PREDECESSOR's block number —
    validation treats it as PBftValidateBoundary (no signature, no
    window update, PBFT.hs:326)."""

    block_no: int
    slot: int
    prev_hash: bytes | None
    issuer_vk: bytes  # 32 — the delegate's key (zeros for an EBB)
    body_hash: bytes  # 32 — the tx Merkle root (an EBB: its body proof)
    sig: bytes  # 64 — Ed25519 over signed_bytes (zeros for an EBB)
    is_ebb: bool = False
    genesis_vk: bytes = bytes(32)  # the certificate's issuer
    cert_epoch: int = 0
    cert_sig: bytes = bytes(64)
    tx_count: int = 0

    @cached_property
    def to_sign(self) -> list:
        proof = [_be8(self.tx_count), self.body_hash, *_EMPTY_PROOFS]
        return [PROTOCOL_MAGIC, self.prev_hash, proof, _be8(self.slot),
                _be8(self.block_no), BLOCK_VERSION, SOFTWARE_VERSION]

    @cached_property
    def signed_bytes(self) -> bytes:
        return cbor.encode(self.to_sign)

    @cached_property
    def bytes_(self) -> bytes:
        if self.is_ebb:
            return cbor.encode([PROTOCOL_MAGIC, self.prev_hash,
                                _be8(self.slot), _be8(self.block_no),
                                self.body_hash])
        cert = [_be8(self.cert_epoch), self.genesis_vk, self.issuer_vk,
                self.cert_sig]
        return cbor.encode([self.to_sign, self.issuer_vk, self.sig, cert])

    @cached_property
    def hash_(self) -> bytes:
        return _b2b(self.bytes_)

    @property
    def point(self) -> Point:
        return Point(self.slot, self.hash_)

    def to_view(self):
        """ValidateView: PBftValidateBoundary for EBBs (a sentinel the
        protocol recognizes), PBftValidateRegular otherwise."""
        if self.is_ebb:
            return BOUNDARY_VIEW
        return PBftView(self.issuer_vk, self.signed_bytes, self.sig)

    @classmethod
    def from_item(cls, kind: int, h: list) -> "ByronMockHeader":
        if kind == EBB:
            _magic, prev, slot, bn, proof = h
            return cls(_int8(bn), _int8(slot), prev, bytes(32), proof,
                       bytes(64), is_ebb=True)
        to_sign, vk, sig, cert = h
        _magic, prev, proof, slot, bn, _bv, _sv = to_sign
        return cls(_int8(bn), _int8(slot), prev, vk, proof[1], sig,
                   genesis_vk=cert[1], cert_epoch=_int8(cert[0]),
                   cert_sig=cert[3], tx_count=_int8(proof[0]))

    @classmethod
    def from_bytes(cls, data: bytes) -> "ByronMockHeader":
        h = cbor.decode(data)
        return cls.from_item(EBB if len(h) == 5 else MAIN, h)


# what one main header signs, and the SHA-512 blocks of R ‖ A ‖ M: the
# width of the Byron `ed` program (ops/pk/kernels.pbft_unpack_fn)
BYRON_SIGNED_LEN = len(ByronMockHeader(0, 0, bytes(32), bytes(32),
                                       bytes(32), bytes(64)).signed_bytes)


def body_hash(txs: Sequence[bytes]) -> bytes:
    return _b2b(cbor.encode(list(txs)))


@dataclass(frozen=True)
class ByronMockBlock:
    header: ByronMockHeader
    txs: tuple[bytes, ...] = ()

    @cached_property
    def bytes_(self) -> bytes:
        h = self.header
        return (b"\x83" + cbor.encode(EBB if h.is_ebb else MAIN) + h.bytes_
                + cbor.encode(list(self.txs)))

    @property
    def hash_(self) -> bytes:
        return self.header.hash_

    @property
    def slot(self) -> int:
        return self.header.slot

    @property
    def block_no(self) -> int:
        return self.header.block_no

    @property
    def prev_hash(self) -> bytes | None:
        return self.header.prev_hash

    @property
    def point(self) -> Point:
        return self.header.point

    def check_integrity(self) -> bool:
        return body_hash(self.txs) == self.header.body_hash

    @classmethod
    def from_bytes(cls, data: bytes) -> "ByronMockBlock":
        kind, hdr, txs = cbor.decode(data)
        return cls(ByronMockHeader.from_item(kind, hdr), tuple(txs))


@lru_cache(maxsize=256)
def _public(seed: bytes) -> bytes:
    """A signing seed's Ed25519 key (a forge signs with a handful of
    delegates' seeds, block after block)."""
    return host_ed25519.secret_to_public(seed)


def delegation_cert(genesis_seed: bytes, delegate_vk: bytes,
                    epoch: int = 0) -> tuple[bytes, bytes]:
    """(genesis vk, certificate signature): the genesis key's heavy
    delegation of its signing right to `delegate_vk` from `epoch`."""
    gvk = _public(genesis_seed)
    return gvk, host_ed25519.sign(
        genesis_seed, cbor.encode([_be8(epoch), delegate_vk]))


def forge_block(
    seed: bytes,
    *,
    slot: int,
    block_no: int,
    prev_hash: bytes | None,
    txs: tuple[bytes, ...] = (),
    cert: tuple[bytes, bytes] | None = None,
    sign=None,
) -> ByronMockBlock:
    """Forge a delegate block (Byron forging: sign the header's ToSign
    with the delegate's Ed25519 key — Byron/Forge.hs shape). `cert` is
    the (genesis vk, certificate signature) the delegate holds
    (`delegation_cert`); without one the key is its own genesis key.
    `sign(seed, msg)` is the signer (the native one when forging at
    scale); the host reference by default."""
    vk = _public(seed)
    gvk, csig = cert if cert is not None else (vk, bytes(64))
    bh = body_hash(txs)
    unsigned = ByronMockHeader(block_no, slot, prev_hash, vk, bh,
                               bytes(64), genesis_vk=gvk, cert_sig=csig,
                               tx_count=len(txs))
    sig = (sign or host_ed25519.sign)(seed, unsigned.signed_bytes)
    return ByronMockBlock(
        ByronMockHeader(block_no, slot, prev_hash, vk, bh, sig,
                        genesis_vk=gvk, cert_sig=csig, tx_count=len(txs)),
        tuple(txs),
    )


def forge_ebb(
    *, slot: int, block_no: int, prev_hash: bytes | None
) -> ByronMockBlock:
    """Forge an epoch boundary block (Byron/EBBs.hs): unsigned, empty;
    `block_no` must equal the PREDECESSOR's (EBBs do not advance the
    block count), `slot` the new epoch's first slot."""
    hdr = ByronMockHeader(
        block_no, slot, prev_hash, bytes(32), body_hash(()),
        bytes(64), is_ebb=True,
    )
    return ByronMockBlock(hdr, ())
