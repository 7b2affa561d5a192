"""PBFT windows on the normal path: the Byron era through protocol/batch's
window loop, packed staging, the `ed` stage and a columnar epilogue.

What `batch.PraosRules` and `tpraos.TPraosRules` are to their eras,
`PBftRules` is to Byron's (`batch.rules_of(PBftEraParams)`):

  prechecks  the delegation map as a COLUMNAR lookup: each lane's issuer
             (the delegate key) against the map's keys -> its genesis
             key's index, -1 for a key outside it (span `stage.pbft`,
             child of `stage.prechecks`).
  staging    a lane carries the `ed` operands only: the signed bytes
             (`to_sign`, one width a window), the signature R ‖ s and the
             delegate key. No KES, VRF or threshold columns cross.
  device     `unpack` (R ‖ A ‖ M into SHA-512 blocks, limb-first), `ed`
             at Byron's width (three blocks where an OCert takes one),
             `finish_pbft` (the compression and the R compare: the
             verdict row) and `reduce`, unchanged (ops/pk/kernels).
  epilogue   the PBFT rule in the reference's order (PBFT.hs:320-352 as
             `PBftProtocol.apply_checked_sig` has it): signature, slot
             not before the last signed one, issuer a delegate, then the
             signer's genesis key over at most floor(threshold x k) of
             the last k signed blocks, this one included. The signing
             window is counted COLUMNAR, per genesis key, over the
             carried window and the window's lanes, exactly; EBB lanes
             (PBftValidateBoundary) are skipped and change nothing (span
             `epilogue.pbft`).

A Byron window has no nonce: its epoch nonce is None and its tick is
the identity (PBFT's state knows no epochs).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from fractions import Fraction
from typing import NamedTuple, Sequence

import numpy as np

from .instances import (PBFT_BOUNDARY_VIEW, PBftExceededSignThreshold,
                        PBftInvalidSignature, PBftInvalidSlot,
                        PBftNotGenesisDelegate, PBftProtocol, PBftParams,
                        PBftState, PBftView, TickedPBftState)


@dataclass(frozen=True)
class PBftEraParams:
    """The Byron era as the window loop reads it: PBftParams (the
    signature threshold over a window of k signed blocks,
    pbftWindowSize = pbftSecurityParam) and its epochs on the hard-fork
    combinator's clock."""

    num_genesis_keys: int = 7
    threshold: Fraction = Fraction(22, 100)
    security_param: int = 2160
    epoch_length: int = 21600  # 10k slots
    era_start_slot: int = 0
    era_start_epoch: int = 0

    @property
    def window(self) -> int:
        return self.security_param

    @property
    def allowed(self) -> int:
        """pbftWindowParams (PBFT.hs:393-396): floor(threshold x k)."""
        return int(Fraction(self.threshold) * self.window)

    def epoch_of(self, slot):
        return (slot - self.era_start_slot) // self.epoch_length \
            + self.era_start_epoch

    def first_slot_of(self, epoch: int) -> int:
        return (self.era_start_slot
                + (epoch - self.era_start_epoch) * self.epoch_length)

    def pbft_params(self) -> PBftParams:
        return PBftParams(self.num_genesis_keys, Fraction(self.threshold),
                          self.window, self.security_param)

    @property
    def batch_rules(self) -> "PBftRules":
        return PBFT_RULES


# ---------------------------------------------------------------------------
# Columns
# ---------------------------------------------------------------------------


@dataclass
class ByronColumns:
    """A run of Byron headers as columns (the ViewColumns of this era).
    `kind` 1 is a main header, 0 an EBB; an EBB's delegate key,
    signature and signed row are zeros. Every main row of one piece
    signs the same number of bytes (`signed` [n, W])."""

    kind: np.ndarray  # [n] int64
    slot: np.ndarray  # [n] int64
    block_no: np.ndarray  # [n] int64
    prev_hash: np.ndarray  # [n, 32] uint8
    has_prev: np.ndarray  # [n] uint8
    delegate_vk: np.ndarray  # [n, 32] uint8
    sig: np.ndarray  # [n, 64] uint8
    signed: np.ndarray  # [n, W] uint8

    _FIELDS = ("kind", "slot", "block_no", "prev_hash", "has_prev",
               "delegate_vk", "sig", "signed")

    def __len__(self) -> int:
        return int(self.slot.shape[0])

    def __getitem__(self, i: slice) -> "ByronColumns":
        return ByronColumns(*(getattr(self, f)[i] for f in self._FIELDS))

    @property
    def main(self) -> np.ndarray:
        return self.kind == 1

    @classmethod
    def concat(cls, parts: Sequence["ByronColumns"]) -> "ByronColumns":
        if len(parts) == 1:
            return parts[0]
        return cls(*(np.concatenate([getattr(p, f) for p in parts])
                     for f in cls._FIELDS))

    @classmethod
    def pieces_from_native(cls, cols: dict, buf) -> "list[ByronColumns]":
        """native_loader.extract_byron's columns -> pieces of one signed
        width (a main header without a previous hash signs 33 bytes
        fewer); EBB rows join whatever piece they fall in."""
        n = len(cols["slot"])
        lens = cols["signed_len"]
        main = cols["kind"] == 1
        ml = lens[main]
        if ml.size and (ml == ml[0]).all():
            bounds = [0, n]
        else:
            # a new piece wherever a main row's width differs from the
            # last main row's before it
            idx = np.flatnonzero(main)
            steps = idx[1:][ml[1:] != ml[:-1]]
            bounds = [0, *steps.tolist(), n]
        data = np.frombuffer(buf, np.uint8)
        out = []
        for a, b in zip(bounds, bounds[1:]):
            m = main[a:b]
            w = int(lens[a:b][m][0]) if m.any() else 0
            signed = np.zeros((b - a, w), np.uint8)
            if w:
                rows = np.flatnonzero(m)
                offs = cols["signed_off"][a:b][rows]
                signed[rows] = data[offs[:, None] + np.arange(w)]
            out.append(cls(
                cols["kind"][a:b], cols["slot"][a:b], cols["block_no"][a:b],
                cols["prev_hash"][a:b], cols["has_prev"][a:b],
                cols["delegate_vk"][a:b], cols["sig"][a:b], signed))
        return out

    @classmethod
    def from_headers(cls, headers) -> "ByronColumns":
        """byron_mock headers (one signed width among the main ones)."""
        n = len(headers)
        w = max((len(h.signed_bytes) for h in headers if not h.is_ebb),
                default=0)

        def col(get, width):
            return np.frombuffer(b"".join(get(h) for h in headers) or b"",
                                 np.uint8).reshape(n, width)

        signed = np.zeros((n, w), np.uint8)
        for i, h in enumerate(headers):
            if not h.is_ebb:
                signed[i] = np.frombuffer(h.signed_bytes, np.uint8)
        return cls(
            np.array([0 if h.is_ebb else 1 for h in headers], np.int64),
            np.array([h.slot for h in headers], np.int64),
            np.array([h.block_no for h in headers], np.int64),
            col(lambda h: h.prev_hash or bytes(32), 32),
            np.array([h.prev_hash is not None for h in headers], np.uint8),
            col(lambda h: h.issuer_vk, 32), col(lambda h: h.sig, 64),
            signed)

    def views(self) -> list:
        """The per-header validate views (the sequential reference's
        input): PBftView, or the boundary sentinel for an EBB."""
        out = []
        for i in range(len(self)):
            if self.kind[i] != 1:
                out.append(PBFT_BOUNDARY_VIEW)
            else:
                out.append(PBftView(self.delegate_vk[i].tobytes(),
                                    self.signed[i].tobytes(),
                                    self.sig[i].tobytes()))
        return out


# ---------------------------------------------------------------------------
# Prechecks and staging
# ---------------------------------------------------------------------------


class PBftChecks(NamedTuple):
    """A Byron window's host prechecks: each lane's genesis key by the
    delegation map (-1: not a delegate, or an EBB) and its kind."""

    gk: np.ndarray  # [n] int64
    main: np.ndarray  # [n] bool
    pbft_s: float  # span `stage.pbft`


def delegate_index(cols: ByronColumns, delegates) -> np.ndarray:
    """The columnar membership lookup: [n] genesis key index of each
    lane's issuer under the delegation map {delegate vk: genesis key
    index}, -1 where the issuer is none of its keys (EBB lanes -1)."""
    gk = np.full(len(cols), -1, np.int64)
    vk = cols.delegate_vk
    for key, g in delegates.items():
        row = np.frombuffer(key, np.uint8)
        gk[(vk == row).all(axis=1)] = g
    gk[~cols.main] = -1
    return gk


def prechecks(params, lview, cols: ByronColumns) -> PBftChecks:
    from .batch import _enclose

    t0 = time.monotonic()
    with _enclose("stage.pbft"):
        gk = delegate_index(cols, lview.delegates)
    return PBftChecks(gk, cols.main, time.monotonic() - t0)


class PBftPackedLayout(NamedTuple):
    """The packed layout of a Byron window (hashable: part of the jit
    key): its one signed width. `proofs` 0 (no VRF work), no KES."""

    body_len: int  # bytes a main header signs
    vrf_proof_len: int = 0
    proofs: int = 0
    kes_depth: int = 0


class PBftPacked(NamedTuple):
    """A Byron window's wire: the `ed` operands and nothing else."""

    signed: np.ndarray  # [B, W] uint8 — what the delegate signed
    sig: np.ndarray  # [B, 64] uint8 — R ‖ s
    vk: np.ndarray  # [B, 32] uint8 — the delegate key


def stage_packed(cols: ByronColumns):
    """-> (PBftPackedLayout, PBftPacked): the columns as they are (an
    EBB lane's are zeros; its verdict is not read)."""
    layout = PBftPackedLayout(int(cols.signed.shape[1]))
    return layout, PBftPacked(np.ascontiguousarray(cols.signed),
                              np.ascontiguousarray(cols.sig),
                              np.ascontiguousarray(cols.delegate_vk))


def unpack_pbft(layout: PBftPackedLayout, signed, sig, vk):
    """Device side: the packed wire -> (pk, r, s, hblocks [B, NB, 16, 2],
    hnblocks [B]) batch-first, R ‖ A ‖ M padded into SHA-512 blocks."""
    from jax import numpy as jnp

    from ..ops import ed25519_batch

    sig = jnp.asarray(sig).astype(jnp.uint8)
    vk = jnp.asarray(vk).astype(jnp.uint8)
    r, s = sig[:, :32], sig[:, 32:]
    hb, hnb = ed25519_batch.build_hblocks(r, vk, jnp.asarray(signed))
    return vk, r, s, hb, hnb


def verify_xla(layout: PBftPackedLayout, signed, sig, vk):
    """The XLA twin of a Byron window's stages -> (flags [5, B] int32
    with `finish`'s rows: the signature's verdict in row 0, rows 1-3
    set, row 4 clear; eta [B, 32] int32 zeros)."""
    from jax import numpy as jnp

    from ..ops import ed25519_batch

    pk, r, s, hb, hnb = unpack_pbft(layout, signed, sig, vk)
    ok = ed25519_batch.verify(pk, r, s, hb, hnb)
    one = jnp.ones(ok.shape, jnp.int32)
    flags = jnp.stack([ok.astype(jnp.int32), one, one, one, 0 * one])
    return flags, jnp.zeros((ok.shape[0], 32), jnp.int32)


# ---------------------------------------------------------------------------
# The columnar epilogue
# ---------------------------------------------------------------------------


def _signers_arrays(st: PBftState):
    if not st.signers:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    a = np.asarray(st.signers, np.int64)
    return a[:, 0], a[:, 1]


def window_counts(carried_gk: np.ndarray, gk: np.ndarray,
                  window: int) -> np.ndarray:
    """For each lane j of `gk` (signers in order, behind the carried
    window's): how many of the last `window` signers, lane j included,
    are its genesis key. One cumulative count per key over the carried
    window and the lanes, so each lane's count is a difference of two
    (a lane of no key, -1, counts 0 and is counted by none)."""
    seq = np.concatenate([carried_gk, gk])
    m = carried_gk.shape[0]
    keys = int(seq.max(initial=-1)) + 1 or 1
    onehot = (seq[:, None] == np.arange(keys)[None, :]).astype(np.int32)
    cs = np.concatenate([np.zeros((1, keys), np.int32),
                         np.cumsum(onehot, axis=0, dtype=np.int32)])
    pos = np.arange(m, m + gk.shape[0])
    lo = np.maximum(pos + 1 - window, 0)
    g = np.clip(gk, 0, keys - 1)
    return cs[pos + 1, g] - cs[lo, g]


def epilogue(params: PBftEraParams, ticked: TickedPBftState,
             cols: ByronColumns, pre: PBftChecks, v):
    """The window's PBFT fold, columnar -> batch.BatchResult (the
    PBftState after the valid prefix, its length, the first error)."""
    from . import batch as pbatch

    t0 = time.monotonic()
    with pbatch._enclose("epilogue.pbft"):
        res = _fold(params, ticked.state, cols, pre, v)
    pbatch._PBFT_S[0] = time.monotonic() - t0  # WindowSpan.pbft_s
    return res


def _fold(params, st: PBftState, cols, pre, v):
    from . import batch as pbatch

    b = len(cols)
    if isinstance(v, pbatch.PackedVerdicts):
        sig_ok = pbatch._mask_bits(v.masks[0], b)
    else:
        sig_ok = np.asarray(v.ok_ocert_sig)[:b].astype(bool)
    main = pre.main
    idx = np.flatnonzero(main)  # the signing lanes, in order
    slots = cols.slot
    c_slot, c_gk = _signers_arrays(st)
    last0 = int(c_slot[-1]) if c_slot.size else None
    ms = slots[idx]
    prev = np.empty_like(ms)
    if ms.size:
        prev[0] = ms[0] if last0 is None else last0  # no check: no slot
        prev[1:] = ms[:-1]
    mgk = pre.gk[idx]
    counts = window_counts(c_gk, mgk, params.window)
    bad_sig = ~sig_ok[idx]
    bad_slot = ms < prev
    bad_deleg = mgk < 0
    bad_thr = counts > params.allowed
    bad = bad_sig | bad_slot | bad_deleg | bad_thr
    first = int(np.argmax(bad)) if bad.any() else None
    n_main = idx.size if first is None else first
    err = None
    n_valid = b
    if first is not None:
        i = int(idx[first])
        n_valid = i
        slot = int(slots[i])
        if bad_sig[first]:
            err = PBftInvalidSignature(slot)
        elif bad_slot[first]:
            err = PBftInvalidSlot(slot, int(prev[first]))
        elif bad_deleg[first]:
            err = PBftNotGenesisDelegate(slot,
                                         cols.delegate_vk[i].tobytes())
        else:
            err = PBftExceededSignThreshold(slot, int(mgk[first]),
                                            int(counts[first]),
                                            params.allowed)
    if n_main:
        keep = params.window
        new_s = np.concatenate([c_slot, ms[:n_main]])[-keep:]
        new_g = np.concatenate([c_gk, mgk[:n_main]])[-keep:]
        st = PBftState(tuple(zip(new_s.tolist(), new_g.tolist())))
    return pbatch.BatchResult(st, n_valid, err)


# ---------------------------------------------------------------------------
# The rules object
# ---------------------------------------------------------------------------


class PBftRules:
    """protocol/batch's window loop, staging and epilogue under PBFT (see
    `batch.PraosRules`): what a Byron window differs by."""

    name = "pbft"
    protocol = "PBft"
    nonces = False  # no epoch nonce: no lookahead, no rotation
    packed_only = True

    def initial_state(self) -> PBftState:
        return PBftState()

    def tick(self, params, lview, slot, state) -> TickedPBftState:
        return TickedPBftState(state, lview.delegates)

    def window(self, hvs):
        return hvs

    def runs(self, hvs) -> list:
        return [hvs]

    def prechecks(self, params, lview, hvs):
        return prechecks(params, lview, hvs)

    def stage_packed(self, params, lview, eta0, hvs, pre):
        return stage_packed(hvs), None

    def epilogue(self, params, ticked, hvs, pre, v):
        return epilogue(params, ticked, hvs, pre, v)

    def run_native(self, params, ticked, cols):
        """A window through the native verifier (native/hostcrypto.cpp),
        one signature a main lane, then the columnar fold:
        `db_analyser`'s backend="native"."""
        from .. import native_loader
        from .batch import Verdicts

        pre = PBftChecks(delegate_index(cols, ticked.dlg), cols.main, 0.0)
        ok = np.array([
            bool(m) and native_loader.native_ed25519_verify(
                cols.delegate_vk[i].tobytes(), cols.sig[i].tobytes(),
                cols.signed[i].tobytes())
            for i, m in enumerate(cols.main.tolist())], bool)
        one = np.ones(len(cols), bool)
        v = Verdicts(ok, one, one, one, ~one, None, None)
        return _fold(params, ticked.state, cols, pre, v)

    def protocol_of(self, params, lview) -> PBftProtocol:
        """The sequential reference (instances.PBftProtocol); a header's
        tick hands it `lview`'s delegation map."""
        return PBftProtocol(params.pbft_params(),
                            [bytes(32)] * params.num_genesis_keys)


PBFT_RULES = PBftRules()
