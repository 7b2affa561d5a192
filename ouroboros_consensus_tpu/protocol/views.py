"""Praos header / ledger views — the exact inputs of header validation.

Reference: Praos/Views.hs:22-51 (`HeaderView`, `LedgerView`) and
cardano-protocol-tpraos `OCert`. The views isolate validation from header
serialisation: the ChainSync client, ChainSel and db-analyser all validate
through these, and the SoA batch staging (protocol/batch.py) columnarizes
lists of them for the device kernels.
"""

from __future__ import annotations

from dataclasses import dataclass, fields
from fractions import Fraction
from functools import lru_cache
from typing import Mapping, Sequence

import numpy as np

from ..ops.host.hashes import blake2b_224, blake2b_256


@lru_cache(maxsize=65536)
def hash_key(vk_cold: bytes) -> bytes:
    """KeyHash (Blake2b-224) of an Ed25519 cold verification key.

    Cached: a chain has few distinct issuers but the replay hot path
    asks several times per header (staging, counter fold, views)."""
    return blake2b_224(vk_cold)


def hash_vrf_vk(vrf_vk: bytes) -> bytes:
    """Blake2b-256 hash of a VRF verification key (pool registration)."""
    return blake2b_256(vrf_vk)


@dataclass(frozen=True)
class OCert:
    """Operational certificate: cold key delegates to a hot KES key.

    Reference: cardano-protocol-tpraos `OCert.OCert`; the DSIGN-signable
    representation is vk_hot ‖ counter_be8 ‖ kes_period_be8
    (`ocertToSignable`).
    """

    vk_hot: bytes  # 32 — KES root verification key
    counter: int  # issue number
    kes_period: int  # start period c0
    sigma: bytes  # 64 — Ed25519 signature by the cold key

    def signable(self) -> bytes:
        return (
            self.vk_hot
            + self.counter.to_bytes(8, "big")
            + self.kes_period.to_bytes(8, "big")
        )


@dataclass(frozen=True)
class HeaderView:
    """Exactly the header fields validation consumes (Praos/Views.hs:22-39)."""

    prev_hash: bytes | None  # None = genesis
    vk_cold: bytes  # 32 — issuer cold key
    vrf_vk: bytes  # 32
    vrf_output: bytes  # 64 — certified VRF output beta
    vrf_proof: bytes  # ECVRF proof pi: 80 (draft-03) or 128 (batch-compat)
    ocert: OCert
    slot: int
    signed_bytes: bytes  # KES-signed representation (header body CBOR)
    kes_sig: bytes  # CompactSum signature (64 + 32 + 32*depth)
    # TPraos (Shelley..Alonzo) headers carry TWO certified VRF results
    # under the one VRF key (BHBody bheaderEta / bheaderL): there
    # `vrf_output`/`vrf_proof` above are the NONCE certificate and these
    # the LEADER certificate. None on a Praos header (one certificate
    # serves both).
    vrf_leader_output: bytes | None = None  # 64
    vrf_leader_proof: bytes | None = None  # 80 (draft-03)


def _no_leader_cert(n: int) -> tuple[np.ndarray, np.ndarray]:
    """The leader-certificate columns of a window of one-certificate
    (Praos) headers: zero-width, so they slice and concatenate like any
    column and cost nothing."""
    return np.zeros((n, 0), np.uint8), np.zeros((n, 0), np.uint8)


@dataclass
class ViewColumns:
    """A columnar window of header views — the SoA twin of
    `Sequence[HeaderView]` that the hot path (protocol/batch,
    tools/db_analyser) flows END-TO-END without materializing per-header
    Python objects (~20-26 µs/header of interpreter tax at the 1M bench
    scale, PERF.md round-8).

    Per-lane data lives in row-major numpy columns; windowing is array
    slicing (`vc[i:j]` -> ViewColumns sharing the underlying buffers).
    `HeaderView` objects are built LAZILY — `vc[i]` / `vc.views()` — and
    only on the paths that genuinely need per-header objects: anomaly
    lanes (exact reference-error reconstruction), the generic-fallback
    staging path, and the sequential reference fold.

    The KES-signed bodies are the one ragged column: `signed_bytes` is
    zero-padded to the widest row and `signed_len` holds each row's own
    length. A body's length steps wherever a CBOR integer it holds
    crosses a width (block number and slot from genesis, and on a chain
    with real block bodies the body size, every time a big block and
    an ordinary one alternate), so one window holds several. Every other
    column is rectangular: `from_header_columns` / `from_views` return
    None when the OCert sigma is not 64 bytes or the KES signatures of
    the range differ in width, and the caller streams plain HeaderView
    lists for that window instead.
    """

    slot: np.ndarray  # [n] int64
    prev_hash: np.ndarray  # [n, 32] uint8
    has_prev: np.ndarray  # [n] uint8 — 0 = genesis (prev_hash is None)
    vk_cold: np.ndarray  # [n, 32] uint8
    vrf_vk: np.ndarray  # [n, 32] uint8
    vrf_output: np.ndarray  # [n, 64] uint8
    vrf_proof: np.ndarray  # [n, 128] uint8, zero-padded to the widest format
    vrf_proof_len: np.ndarray  # [n] int64 — 80 (draft-03) or 128 (bc)
    ocert_vk_hot: np.ndarray  # [n, 32] uint8
    ocert_counter: np.ndarray  # [n] int64
    ocert_kes_period: np.ndarray  # [n] int64
    ocert_sigma: np.ndarray  # [n, 64] uint8
    kes_sig: np.ndarray  # [n, 96 + 32*depth] uint8
    signed_bytes: np.ndarray  # [n, widest body] uint8, zero-padded
    # the TPraos leader certificate (HeaderView.vrf_leader_*): [n, 64] and
    # [n, 80], or zero-width on a window of one-certificate headers
    vrf_leader_output: np.ndarray = None  # type: ignore[assignment]
    vrf_leader_proof: np.ndarray = None  # type: ignore[assignment]
    # [n] int64 — each row's body length (None: every row is as wide as
    # the column)
    signed_len: np.ndarray = None  # type: ignore[assignment]

    def __post_init__(self):
        n = int(self.slot.shape[0])
        if self.vrf_leader_output is None:
            self.vrf_leader_output, self.vrf_leader_proof = _no_leader_cert(n)
        if self.signed_len is None:
            self.signed_len = np.full(n, self.signed_bytes.shape[1], np.int64)

    @property
    def two_certs(self) -> bool:
        """True on a window of TPraos (two-certificate) headers."""
        return self.vrf_leader_output.shape[1] != 0

    def __len__(self) -> int:
        return int(self.slot.shape[0])

    def __getitem__(self, i):
        if isinstance(i, slice):
            return ViewColumns(*(
                getattr(self, f.name)[i] for f in fields(self)
            ))
        return self.view(int(i))

    def view(self, i: int) -> HeaderView:
        """Materialize ONE lane as a HeaderView (the lazy per-header
        path: error reconstruction, window-boundary peeks)."""
        return HeaderView(
            prev_hash=(
                self.prev_hash[i].tobytes() if self.has_prev[i] else None
            ),
            vk_cold=self.vk_cold[i].tobytes(),
            vrf_vk=self.vrf_vk[i].tobytes(),
            vrf_output=self.vrf_output[i].tobytes(),
            vrf_proof=self.vrf_proof[i, : int(self.vrf_proof_len[i])].tobytes(),
            ocert=OCert(
                self.ocert_vk_hot[i].tobytes(),
                int(self.ocert_counter[i]),
                int(self.ocert_kes_period[i]),
                self.ocert_sigma[i].tobytes(),
            ),
            slot=int(self.slot[i]),
            signed_bytes=self.signed_bytes[i, : int(self.signed_len[i])
                                            ].tobytes(),
            kes_sig=self.kes_sig[i].tobytes(),
            vrf_leader_output=(
                self.vrf_leader_output[i].tobytes() if self.two_certs
                else None
            ),
            vrf_leader_proof=(
                self.vrf_leader_proof[i].tobytes() if self.two_certs
                else None
            ),
        )

    def views(self) -> list[HeaderView]:
        """Materialize the whole window as HeaderViews (whole-column
        tobytes + bytes slicing — per-row numpy tobytes costs ~10x
        more). This IS the object tax; hot paths call it only on
        anomaly windows."""
        n = len(self)
        prev_b = np.ascontiguousarray(self.prev_hash).tobytes()
        cold_b = np.ascontiguousarray(self.vk_cold).tobytes()
        vrf_vk_b = np.ascontiguousarray(self.vrf_vk).tobytes()
        vrf_out_b = np.ascontiguousarray(self.vrf_output).tobytes()
        vrf_prf_b = np.ascontiguousarray(self.vrf_proof).tobytes()
        pw = self.vrf_proof.shape[1]  # row stride of the padded column
        vk_hot_b = np.ascontiguousarray(self.ocert_vk_hot).tobytes()
        sigma_b = np.ascontiguousarray(self.ocert_sigma).tobytes()
        kes_b = np.ascontiguousarray(self.kes_sig).tobytes()
        kw = self.kes_sig.shape[1]
        sgn_b = np.ascontiguousarray(self.signed_bytes).tobytes()
        sw = self.signed_bytes.shape[1]
        slens = self.signed_len.tolist()
        has_prev = self.has_prev.tolist()
        slots = self.slot.tolist()
        counters = self.ocert_counter.tolist()
        periods = self.ocert_kes_period.tolist()
        plens = self.vrf_proof_len.tolist()
        two = self.two_certs
        lout_b = np.ascontiguousarray(self.vrf_leader_output).tobytes()
        lprf_b = np.ascontiguousarray(self.vrf_leader_proof).tobytes()
        out = []
        for i in range(n):
            o32 = 32 * i
            out.append(HeaderView(
                prev_hash=prev_b[o32:o32 + 32] if has_prev[i] else None,
                vk_cold=cold_b[o32:o32 + 32],
                vrf_vk=vrf_vk_b[o32:o32 + 32],
                vrf_output=vrf_out_b[64 * i:64 * i + 64],
                vrf_proof=vrf_prf_b[pw * i:pw * i + plens[i]],
                ocert=OCert(
                    vk_hot_b[o32:o32 + 32],
                    counters[i],
                    periods[i],
                    sigma_b[64 * i:64 * i + 64],
                ),
                slot=slots[i],
                signed_bytes=sgn_b[sw * i:sw * i + slens[i]],
                kes_sig=kes_b[kw * i:kw * (i + 1)],
                vrf_leader_output=lout_b[64 * i:64 * i + 64] if two else None,
                vrf_leader_proof=lprf_b[80 * i:80 * i + 80] if two else None,
            ))
        return out

    def body_spans(self) -> tuple[bytes, np.ndarray]:
        """The bodies back to back and their [n + 1] int64 offsets (the
        native verifiers' message spans)."""
        lens = self.signed_len
        n, w = self.signed_bytes.shape
        off = np.zeros(n + 1, np.int64)
        np.cumsum(lens, out=off[1:])
        if int(off[-1]) == n * w:  # every row as wide as the column
            return np.ascontiguousarray(self.signed_bytes).tobytes(), off
        keep = np.arange(w) < lens[:, None]
        return self.signed_bytes[keep].tobytes(), off

    @classmethod
    def concat(cls, parts: Sequence["ViewColumns"]) -> "ViewColumns | None":
        """Concatenate windows (epoch segmentation across chunk files),
        the body column zero-padded to the widest part's, or None when
        the parts' KES signatures differ in width or their headers in
        certificate count (the caller keeps them apart)."""
        if len(parts) == 1:
            return parts[0]
        if len({p.kes_sig.shape[1] for p in parts}) > 1 or len(
            {p.two_certs for p in parts}
        ) > 1:
            return None
        w = max(p.signed_bytes.shape[1] for p in parts)

        def col(name):
            if name != "signed_bytes":
                return np.concatenate([getattr(p, name) for p in parts])
            out = np.zeros((sum(len(p) for p in parts), w), np.uint8)
            at = 0
            for p in parts:
                out[at:at + len(p), : p.signed_bytes.shape[1]] = (
                    p.signed_bytes)
                at += len(p)
            return out

        return cls(*(col(f.name) for f in fields(cls)))

    @classmethod
    def from_header_columns(cls, hc, lo: int = 0, hi: int | None = None
                            ) -> "ViewColumns | None":
        """Build from (a range of) a native_loader.HeaderColumns chunk
        scan — pure array plumbing (the span matrices gather
        vectorized; the bodies zero-padded to the widest). None when the
        OCert sigma or KES signature spans of the range are not uniform
        width, or the sigma is not 64 bytes (callers split at those
        changes via `pieces_from_header_columns`, or use the per-view
        path)."""
        from ..native_loader import _padded_span_matrix, _span_matrix

        hi = hc.n if hi is None else hi
        if lo == 0 and hi == hc.n:
            sigma, kes, body = (
                hc.ocert_sigma_mat, hc.kes_sig_mat, hc.signed_bytes_mat
            )
        else:
            buf = hc._buf_u8
            sigma = _span_matrix(buf, hc.sig_off[lo:hi], hc.sig_len[lo:hi])
            kes = _span_matrix(buf, hc.kes_off[lo:hi], hc.kes_len[lo:hi])
            body = _padded_span_matrix(buf, hc.sgn_off[lo:hi],
                                       hc.sgn_len[lo:hi])
        if sigma is None or kes is None or sigma.shape[1] != 64:
            return None
        s = slice(lo, hi)
        return cls(
            slot=hc.slot[s],
            prev_hash=hc.prev_hash[s],
            has_prev=hc.has_prev[s],
            vk_cold=hc.issuer_vk[s],
            vrf_vk=hc.vrf_vk[s],
            vrf_output=hc.vrf_output[s],
            vrf_proof=hc.vrf_proof[s],
            vrf_proof_len=hc.vrf_proof_len[s],
            ocert_vk_hot=hc.ocert_vk[s],
            ocert_counter=hc.ocert_counter[s],
            ocert_kes_period=hc.ocert_kes_period[s],
            ocert_sigma=sigma,
            kes_sig=kes,
            signed_bytes=body,
            signed_len=np.asarray(hc.sgn_len[s], np.int64),
            **cls._leader_cert_of(hc, s),
        )

    @staticmethod
    def _leader_cert_of(hc, s: slice) -> dict:
        """The leader-certificate columns of a chunk scan's rows `s`
        (HeaderColumns or SidecarColumns): present where every row of
        the range carries one (a chain is one protocol; a range that
        mixes the two header shapes reads as one-certificate and its
        TPraos rows fail their checks loudly)."""
        two = getattr(hc, "vrf_two", None)
        if two is None or not len(two[s]) or not two[s].all():
            return {}
        return dict(vrf_leader_output=hc.vrf_leader_output[s],
                    vrf_leader_proof=hc.vrf_leader_proof[s])

    @classmethod
    def pieces_from_header_columns(cls, hc) -> "list[ViewColumns] | None":
        """The chunk as a minimal list of rectangular ViewColumns
        pieces, split where any span width, the VRF proof format or the
        certificate count changes: a piece of one width is a zero-copy
        view of the chunk, and the epoch segmentation's concat (a copy
        in any case) merges bodies of several lengths into one segment
        (`db_analyser._epoch_window_segments`). None when even a
        uniform-width run cannot columnarize (malformed sigma width) —
        the caller streams per-view lists instead."""
        widths = np.stack(
            [hc.sig_len, hc.kes_len, hc.sgn_len, hc.vrf_proof_len,
             hc.vrf_two.astype(np.int64)], axis=1,
        )
        chg = np.flatnonzero((widths[1:] != widths[:-1]).any(axis=1)) + 1
        bounds = [0, *chg.tolist(), hc.n]
        out = []
        for k in range(len(bounds) - 1):
            vc = cls.from_header_columns(hc, bounds[k], bounds[k + 1])
            if vc is None:
                return None
            out.append(vc)
        return out

    @classmethod
    def from_views(cls, hvs: Sequence[HeaderView]) -> "ViewColumns | None":
        """Columnarize a HeaderView list (tests, synthetic chains), the
        bodies zero-padded to the widest. None when the views cannot
        form the other columns (mixed KES-signature widths, a sigma not
        64 bytes, mixed certificate counts)."""
        n = len(hvs)
        if n == 0:
            return None
        kw = len(hvs[0].kes_sig)
        if any(len(hv.kes_sig) != kw for hv in hvs):
            return None
        if any(len(hv.ocert.sigma) != 64 for hv in hvs):
            return None
        plen = np.asarray([len(hv.vrf_proof) for hv in hvs], np.int64)
        proof = np.zeros((n, 128), np.uint8)
        for i, hv in enumerate(hvs):
            proof[i, : plen[i]] = np.frombuffer(hv.vrf_proof, np.uint8)
        slen = np.asarray([len(hv.signed_bytes) for hv in hvs], np.int64)
        body = np.zeros((n, int(slen.max())), np.uint8)
        body[np.arange(body.shape[1]) < slen[:, None]] = np.frombuffer(
            b"".join(hv.signed_bytes for hv in hvs), np.uint8)
        two = hvs[0].vrf_leader_proof is not None
        if any((hv.vrf_leader_proof is not None) != two for hv in hvs):
            return None
        if two and any(len(hv.vrf_leader_proof) != 80
                       or len(hv.vrf_leader_output) != 64 for hv in hvs):
            return None

        def col(get, w):
            return np.frombuffer(
                b"".join(get(hv) for hv in hvs), np.uint8
            ).reshape(n, w).copy()

        return cls(
            slot=np.asarray([hv.slot for hv in hvs], np.int64),
            prev_hash=col(
                lambda hv: hv.prev_hash if hv.prev_hash is not None
                else bytes(32), 32,
            ),
            has_prev=np.asarray(
                [hv.prev_hash is not None for hv in hvs], np.uint8
            ),
            vk_cold=col(lambda hv: hv.vk_cold, 32),
            vrf_vk=col(lambda hv: hv.vrf_vk, 32),
            vrf_output=col(lambda hv: hv.vrf_output, 64),
            vrf_proof=proof,
            vrf_proof_len=plen,
            ocert_vk_hot=col(lambda hv: hv.ocert.vk_hot, 32),
            ocert_counter=np.asarray(
                [hv.ocert.counter for hv in hvs], np.int64
            ),
            ocert_kes_period=np.asarray(
                [hv.ocert.kes_period for hv in hvs], np.int64
            ),
            ocert_sigma=col(lambda hv: hv.ocert.sigma, 64),
            kes_sig=col(lambda hv: hv.kes_sig, kw),
            signed_bytes=body,
            signed_len=slen,
            **(dict(
                vrf_leader_output=col(lambda hv: hv.vrf_leader_output, 64),
                vrf_leader_proof=col(lambda hv: hv.vrf_leader_proof, 80),
            ) if two else {}),
        )


@dataclass(frozen=True)
class IndividualPoolStake:
    """Relative stake + registered VRF key hash (SL.IndividualPoolStake)."""

    stake: Fraction
    vrf_key_hash: bytes  # Blake2b-256 of the pool's VRF vk


@dataclass(frozen=True)
class LedgerView:
    """Praos ledger view (Praos/Views.hs:41-51): what the protocol needs
    from the ledger — the pool stake distribution (+ size limits used by
    envelope checks)."""

    pool_distr: Mapping[bytes, IndividualPoolStake]  # KeyHash -> stake
    max_header_size: int = 1100
    max_body_size: int = 90112
    protocol_version: tuple[int, int] = (9, 0)
