"""Praos header & block model with deterministic CBOR codecs.

Reference: the standalone Praos header
(ouroboros-consensus-protocol/.../Protocol/Praos/Header.hs:62-125):
`HeaderBody` carries 10 fields (block number, slot, prev hash, issuer VK,
VRF VK, VRF certificate, body size, body hash, OCert, protocol version);
`Header = (HeaderBody, KES signature)` memoises its serialized bytes, and
the header hash is Blake2b-256 of the CBOR (Header.hs:158).

The KES signature signs the CBOR of the HeaderBody — exactly the bytes the
batched verifier consumes (`HeaderView.signed_bytes`).

The block is this framework's own: header + a list of opaque tx byte
strings (the mock ledger interprets them; Shelley-depth tx bodies are out
of hot-path scope per SURVEY.md §7.2 step 11).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import cached_property
from typing import Sequence

from ..ops.host.hashes import blake2b_256
from ..protocol.views import HeaderView, OCert
from ..utils import cbor
from .abstract import HeaderFields, Point


@dataclass(frozen=True)
class HeaderBody:
    """The KES-signed part of a Praos header (Praos/Header.hs:62-84)."""

    block_no: int
    slot: int
    prev_hash: bytes | None  # None = genesis
    issuer_vk: bytes  # 32 — cold key
    vrf_vk: bytes  # 32
    vrf_output: bytes  # 64 — certified output beta
    vrf_proof: bytes  # ECVRF proof pi: 80 (draft-03) or 128 (batch-compat)
    body_size: int
    body_hash: bytes  # 32
    ocert: OCert
    protocol_version: tuple[int, int] = (9, 0)
    # a TPraos (Shelley..Alonzo) body carries TWO VRF certificates under
    # the one key (BHBody bheaderEta, bheaderL): `vrf_output`/`vrf_proof`
    # are then the NONCE certificate and these the LEADER certificate,
    # and the body serialises as 11 fields (the repo's own CBOR: the
    # ledger's BHBody inlines the OCert and the version into 15)
    vrf_leader_output: bytes | None = None  # 64
    vrf_leader_proof: bytes | None = None  # 80 (draft-03)

    def to_cbor_obj(self):
        leader = (
            [] if self.vrf_leader_proof is None
            else [[self.vrf_leader_output, self.vrf_leader_proof]]
        )
        return [
            self.block_no,
            self.slot,
            self.prev_hash,
            self.issuer_vk,
            self.vrf_vk,
            [self.vrf_output, self.vrf_proof],
            *leader,
            self.body_size,
            self.body_hash,
            [self.ocert.vk_hot, self.ocert.counter, self.ocert.kes_period, self.ocert.sigma],
            [self.protocol_version[0], self.protocol_version[1]],
        ]

    @classmethod
    def from_cbor_obj(cls, obj) -> "HeaderBody":
        lout = lproof = None
        if len(obj) == 11:  # TPraos: the leader certificate follows
            obj = list(obj)
            lout, lproof = (bytes(x) for x in obj.pop(6))
        (bn, slot, prev, ivk, vvk, (vout, vproof), bsz, bh, oc, pv) = obj
        return cls(
            block_no=bn, slot=slot,
            prev_hash=bytes(prev) if prev is not None else None,
            issuer_vk=bytes(ivk), vrf_vk=bytes(vvk),
            vrf_output=bytes(vout), vrf_proof=bytes(vproof),
            body_size=bsz, body_hash=bytes(bh),
            ocert=OCert(bytes(oc[0]), oc[1], oc[2], bytes(oc[3])),
            protocol_version=(pv[0], pv[1]),
            vrf_leader_output=lout, vrf_leader_proof=lproof,
        )

    @cached_property
    def signed_bytes(self) -> bytes:
        """Memoised CBOR — the exact bytes the KES signature covers
        (Header.hs:120-125 `headerBodyBytes`)."""
        return cbor.encode(self.to_cbor_obj())


@dataclass(frozen=True)
class Header:
    body: HeaderBody
    kes_sig: bytes

    @cached_property
    def bytes_(self) -> bytes:
        return cbor.encode([self.body.to_cbor_obj(), self.kes_sig])

    @cached_property
    def hash_(self) -> bytes:
        """Blake2b-256 of the serialized header (Header.hs:158)."""
        return blake2b_256(self.bytes_)

    @property
    def slot(self) -> int:
        return self.body.slot

    @property
    def block_no(self) -> int:
        return self.body.block_no

    @property
    def prev_hash(self) -> bytes | None:
        return self.body.prev_hash

    @property
    def fields(self) -> HeaderFields:
        return HeaderFields(self.slot, self.block_no, self.hash_)

    @property
    def point(self) -> Point:
        return Point(self.slot, self.hash_)

    def to_view(self) -> HeaderView:
        """Project the exact validation inputs (Praos/Views.hs:22-39)."""
        b = self.body
        return HeaderView(
            prev_hash=b.prev_hash,
            vk_cold=b.issuer_vk,
            vrf_vk=b.vrf_vk,
            vrf_output=b.vrf_output,
            vrf_proof=b.vrf_proof,
            ocert=b.ocert,
            slot=b.slot,
            signed_bytes=b.signed_bytes,
            kes_sig=self.kes_sig,
            vrf_leader_output=b.vrf_leader_output,
            vrf_leader_proof=b.vrf_leader_proof,
        )

    @classmethod
    def from_bytes(cls, data: bytes) -> "Header":
        body_obj, sig = cbor.decode(data)
        return cls(HeaderBody.from_cbor_obj(body_obj), bytes(sig))


def body_hash(txs: Sequence[bytes]) -> bytes:
    """Blake2b-256 over the canonical CBOR of the tx list."""
    return blake2b_256(cbor.encode(list(txs)))


@dataclass(frozen=True)
class Block:
    """header + opaque txs; the unit ChainDB stores and the ledger applies."""

    header: Header
    txs: tuple[bytes, ...] = ()

    @cached_property
    def bytes_(self) -> bytes:
        return cbor.encode([[self.header.body.to_cbor_obj(), self.header.kes_sig], list(self.txs)])

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

    @classmethod
    def from_bytes(cls, data: bytes) -> "Block":
        (body_obj, sig), txs = cbor.decode(data)
        return cls(
            Header(HeaderBody.from_cbor_obj(body_obj), bytes(sig)),
            tuple(bytes(t) for t in txs),
        )

    def check_integrity(self) -> bool:
        """nodeCheckIntegrity analog (shelley Ledger/Integrity.hs:14-20):
        body hash matches; KES verification is the batched verifier's job
        (storage validation routes whole chunks through it)."""
        return body_hash(self.txs) == self.header.body.body_hash
