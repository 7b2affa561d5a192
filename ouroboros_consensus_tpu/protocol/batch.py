"""Batched Praos header validation — the TPU hot path.

This is the architectural inversion at the heart of the framework: where
the reference validates header-by-header inside a sequential fold
(`ledgerDbPushMany` = repeatedlyM, LedgerDB/Update.hs:302; crypto at
Praos.hs:441-606), we stage a columnar batch of header views (SoA) and run
ALL the expensive work as one fused device program:

  * Ed25519 verify of the OCert cold-key signature   (Praos.hs:580)
  * CompactSum KES verify of the header body          (Praos.hs:582)
  * ECVRF verify of the leader-election proof         (Praos.hs:543)
  * beta == declared certified output                 (verifyCertified)
  * leader-value range extension Blake2b("L" ‖ beta)  (Praos/VRF.hs:103)
  * leader threshold compare                          (Praos.hs:551)
  * nonce range extension Blake2b²("N" ‖ beta)        (Praos/VRF.hs:116)

Only the cheap state-threading (ocert counter monotonicity, nonce fold —
a NON-associative hash fold, so inherently sequential but ~1.5 µs/header
on host) remains outside the kernel. Verdicts come back as per-check bitmaps;
the host locates the first failing chain position and reports the exact
`PraosValidationError` the sequential reference implementation would have
raised (re-deriving it with the host verifier for the error payload).

The device boundary itself is packed (round 6, "cut the wire"): windows
stage as body-sourced u8 columns (`stage_packed` — the KES-signed header
body is the single wire copy of every field it embeds; SHA padding, the
VRF alpha and the limb relayout run on device), and results come back as
u32 verdict bitmask words plus the uint8 eta column (`verdict_pack`),
which the host folds into the evolving/candidate nonces in retire order
(`_fold_nonces`: round 6 ran that hash chain on the device, 3.3 s a
window of 8192 lanes against 12 ms here — PERF.md, PR 29), with the
per-lane columns left device-resident for the exact-error slow path. Non-qualifying windows (mixed CBOR layouts, synthetic test views)
fall back to the original staged path — verified byte-for-byte at
staging time, so both wires are semantically identical.

Leader threshold on device: the rule p < 1 − (1−f)^σ compares a 256-bit
hash against an irrational bound. Per (σ, f) — one per pool per epoch —
the host brackets T = 2²⁵⁶·(1 − (1−f)^σ) by rationals [T_lo, T_hu] tight
to ~2⁻⁴⁰ relative width (protocol/leader.py series bounds). The device
does the big-endian compare against both brackets; the measure-zero band
in between falls back to the exact host check (`leader_ambiguous` mask).

One loop, two protocols: the window loop, packed staging and the epilogue
take the protocol's rules from the params (`rules_of`: `PraosRules` here,
`protocol/tpraos.TPraosRules` for the Shelley-to-Alonzo eras' TPraos, whose
headers carry two VRF certificates: its packed layout holds both
(`PraosPackedLayout.proofs == 2`, `TPraosPacked`), its window runs the
draft-03 `vrf` stage twice and `finish_tp`, its leader rule compares the
raw 64-byte leader output against 512-bit brackets, and an overlay column
says which lanes the BFT schedule, not the lottery, gave their slot).

Epoch segmentation (SURVEY.md §5.7): the epoch nonce and pool distribution
are constant within an epoch, so a batch spans at most one epoch; the
chain driver (storage/ledgerdb, tools/db_analyser) cuts batches at epoch
boundaries and threads the tiny PraosState between them.
"""

from __future__ import annotations

import contextlib
import itertools
import os
import threading
import time
from dataclasses import dataclass, replace
from fractions import Fraction
from functools import lru_cache
from typing import Mapping, NamedTuple, Sequence

import jax
import numpy as np
from jax import numpy as jnp

from ..ops import blake2b, ecvrf_batch, ed25519_batch, kes_batch
from ..ops.host import kes as host_kes
from ..utils.trace import GcSpans
from . import leader, nonces, praos
from .praos import PraosParams, PraosState, TickedPraosState
from .views import (
    HeaderView, LedgerView, ViewColumns, hash_key, hash_vrf_vk,
)

# ---------------------------------------------------------------------------
# Leader-threshold bracketing (host, cached per (sigma, f))
# ---------------------------------------------------------------------------


# fraction bits of the bracket's fixed-point series: its rounding (2^-500
# of the threshold) is nothing beside the 64-term series' own remainder
_BRACKET_BITS = 512
_BRACKET_TERMS = 64


def _exp_fixed(x: int, up: bool) -> int:
    """A bound of exp(x / 2^_BRACKET_BITS) in the same fixed point, from
    below (every term rounded down) or from above (every term rounded up,
    plus the tail's geometric bound x^(N+1)/(N+1)!/(1-x)); 0 <= x < 1."""
    one = 1 << _BRACKET_BITS
    acc = term = one
    for n in range(1, _BRACKET_TERMS + 1):
        term = -(-term * x // (n * one)) if up else term * x // (n * one)
        acc += term
    if up:
        rem = -(-term * x // ((_BRACKET_TERMS + 1) * one))
        acc += -(-rem * one // (one - x))
    return acc


@lru_cache(maxsize=4096)
def leader_threshold_bracket(sigma: Fraction, f: Fraction,
                             bits: int = 256) -> tuple[int, int]:
    """[T_lo, T_hi] integers bracketing 2^bits * (1 - (1-f)^sigma):
    bits = 256 for Praos's hashed leader value, 512 for TPraos's raw
    64-byte VRF output (`checkLeaderValue` over 2^512).

    leader_value < T_lo  => certainly a leader;
    leader_value >= T_hi => certainly not;
    otherwise undecided (exact host check).  With 64 series terms the
    bracket is some 2^-70 of the range wide at f = 1/2, so the ambiguous
    band is empty in practice.

    The series runs in integer fixed point with directed rounding, not
    in exact rationals: a stake that is a share of many pools' weights
    has a denominator of hundreds of digits, which the exact series
    raised to its 64th power (0.2 s a pool: 100 s for the 512 of a
    mainnet-shaped ledger view, PERF.md PR 32), and the bracket need
    only BE a bracket.
    """
    vmax = 1 << bits
    if f == 1:
        return (vmax, vmax)
    if sigma == 0:
        return (0, 0)
    llo, lhi = leader._neg_log1m_interval(f, _BRACKET_TERMS)
    one = 1 << _BRACKET_BITS
    xlo, xhi = sigma * llo, sigma * lhi
    elo = _exp_fixed(xlo.numerator * one // xlo.denominator, up=False)
    ehi = _exp_fixed(-(-xhi.numerator * one // xhi.denominator), up=True)
    # lhs = 2^256/(2^256 - lv) < exp(x)  <=>  lv < 2^256 (1 - 1/exp(x))
    lo = vmax * (elo - one) // elo  # floor
    hi = -(-vmax * (ehi - one) // ehi)  # ceil
    return (lo, hi)


# ---------------------------------------------------------------------------
# SoA staging
# ---------------------------------------------------------------------------


class PraosBatch(NamedTuple):
    """Device-ready columnar batch of Praos header-validation inputs."""

    ed: ed25519_batch.Ed25519Batch  # OCert cold-key signature check
    kes: kes_batch.KesBatch  # header-body KES signature check
    # leader VRF proof check; the staged type follows the proof format
    # (EcvrfBatch = draft-03, EcvrfBcBatch = batch-compatible)
    vrf: "ecvrf_batch.EcvrfBatch | ecvrf_batch.EcvrfBcBatch"
    beta: np.ndarray  # [B, 64] uint8 — declared certified VRF output
    thr_lo: np.ndarray  # [B, 32] uint8 big-endian leader bound (certain win)
    thr_hi: np.ndarray  # [B, 32] uint8 big-endian leader bound (certain loss)


@dataclass(frozen=True)
class HostChecks:
    """Results of the cheap non-crypto checks.

    Split into KES-side and VRF-side error arrays because the reference
    interleaves them with the crypto verdicts in a strict order
    (validateKESSignature COMPLETELY before validateVRFSignature,
    Praos.hs:441-466) that `_lane_error` must reproduce.
    """

    # per-lane: None = pass, else the error the reference would raise
    kes_window_errors: list  # KESBeforeStart / KESAfterEnd (Praos.hs:560-574)
    vrf_lookup_errors: list  # VRFKeyUnknown / WrongVRFKey (Praos.hs:530-540)
    kes_evolution: np.ndarray  # [B] int32 — t = kes_period - c0 (clamped 0)

    def any_errors(self) -> bool:
        return any(e is not None for e in self.kes_window_errors) or any(
            e is not None for e in self.vrf_lookup_errors
        )


@dataclass(frozen=True)
class ColumnChecks(HostChecks):
    """HostChecks from the columnar precheck pass, carrying the
    per-window pool dedup so later stages (threshold tables, counter
    monotonicity, the native leader compare) never repeat the
    hash_key + pool_distr lookups per lane."""

    uniq_inv: np.ndarray  # [B] int32 — lane -> unique (cold, vrf) pair
    uniq_hk: tuple  # per-unique KeyHash bytes
    uniq_entry: tuple  # per-unique IndividualPoolStake | None
    clean: bool = False  # True = no precheck error in any lane
    # a protocol with a BFT overlay (`PraosRules.overlay`): [B] uint8,
    # 1 = the lane's slot is an active overlay slot; None under Praos
    overlay: np.ndarray | None = None
    overlay_s: float = 0.0  # wall of the span `stage.overlay`

    def any_errors(self) -> bool:
        return not self.clean


def host_prechecks(
    params: PraosParams,
    ledger_view: LedgerView,
    hvs: "Sequence[HeaderView] | ViewColumns",
) -> HostChecks:
    """The non-crypto parts of validateKESSignature/validateVRFSignature
    (Praos.hs:558-574 window checks, :528-540 pool lookups), batch-wide.

    OCert counter monotonicity (Praos.hs:585-590) is NOT here: it depends
    on the evolving counter map and is checked in the sequential epilogue.

    A ViewColumns window takes the vectorized path: whole-column KES
    window arithmetic, pool lookups deduplicated per unique
    (cold-key, vrf-key) pair — hash_key and the dict probe run once per
    pool per window, not once per header.
    """
    if isinstance(hvs, ViewColumns):
        return host_prechecks_columns(params, ledger_view, hvs)
    kes_errors: list = [None] * len(hvs)
    vrf_errors: list = [None] * len(hvs)
    evol = np.zeros((len(hvs),), np.int32)
    for i, hv in enumerate(hvs):
        c0 = hv.ocert.kes_period
        kp = params.kes_period_of(hv.slot)
        if not c0 <= kp:
            kes_errors[i] = praos.KESBeforeStartOCERT(c0, kp)
        elif not kp < c0 + params.max_kes_evolutions:
            kes_errors[i] = praos.KESAfterEndOCERT(kp, c0, params.max_kes_evolutions)
        else:
            evol[i] = kp - c0
        hk = hash_key(hv.vk_cold)
        entry = ledger_view.pool_distr.get(hk)
        if entry is None:
            vrf_errors[i] = praos.VRFKeyUnknown(hk)
        else:
            header_vrf_hash = hash_vrf_vk(hv.vrf_vk)
            if entry.vrf_key_hash != header_vrf_hash:
                vrf_errors[i] = praos.VRFKeyWrongVRFKey(
                    hk, entry.vrf_key_hash, header_vrf_hash
                )
    return HostChecks(kes_errors, vrf_errors, evol)


def _dedup_rows(rows: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(unique_rows [k, w], inverse [n]) over a [n, w] uint8 matrix —
    np.unique(axis=0) semantics (sorted-by-something stable grouping +
    gather indices) WITHOUT its void-dtype argsort, which comparison-
    sorts w-byte keys (~27 µs/row at w=288: slower than the rest of the
    columnar stage combined). Rows are grouped by a vectorized 64-bit
    Horner fingerprint over their u64 words and the grouping is then
    VERIFIED by one exact gather-compare; a fingerprint collision (only
    adversarially reachable) falls back to the exact np.unique."""
    n, w = rows.shape
    if n == 0:
        return rows.copy(), np.zeros(0, np.int64)
    pad = (-w) % 8
    if pad:
        padded = np.zeros((n, w + pad), np.uint8)
        padded[:, :w] = rows
    else:
        padded = np.ascontiguousarray(rows)
    words = padded.view(np.uint64)
    h = np.zeros(n, np.uint64)
    mult = np.uint64(0x9E3779B97F4A7C15)
    with np.errstate(over="ignore"):
        for c in range(words.shape[1]):
            h = h * mult + words[:, c]
    uh, inv = np.unique(h, return_inverse=True)
    first = np.full(uh.shape[0], -1, np.int64)
    # first occurrence per group (reverse scatter keeps the lowest index)
    first[inv[::-1]] = np.arange(n - 1, -1, -1)
    uniq = rows[first]
    if not np.array_equal(uniq[inv], rows):
        return np.unique(rows, axis=0, return_inverse=True)
    return uniq, inv


def kes_window_checks(params: PraosParams, vc: ViewColumns):
    """The OCert KES-window checks of a window, whole-column:
    -> (per-lane errors, evolution index [B] int32, any error)."""
    n = len(vc)
    c0 = vc.ocert_kes_period
    kp = vc.slot // params.slots_per_kes_period
    before = c0 > kp
    after = ~before & (kp >= c0 + params.max_kes_evolutions)
    bad_window = before | after
    evol = np.where(bad_window, 0, kp - c0).astype(np.int32)
    kes_errors: list = [None] * n
    bad = bool(bad_window.any())
    if bad:
        for i in np.flatnonzero(before).tolist():
            kes_errors[i] = praos.KESBeforeStartOCERT(int(c0[i]), int(kp[i]))
        for i in np.flatnonzero(after).tolist():
            kes_errors[i] = praos.KESAfterEndOCERT(
                int(kp[i]), int(c0[i]), params.max_kes_evolutions
            )
    return kes_errors, evol, bad


def pool_pairs(ledger_view: LedgerView, vc: ViewColumns):
    """The pool lookups of a window, once per unique (cold key, vrf key)
    pair: real chains have a handful of issuers per window, so the
    Blake2b-224 hash_key, the pool_distr probe and the vrf-key-hash
    equality run O(pools) times instead of O(headers).
    -> (unique pair rows [k, 64], lane -> pair [B], per-pair key hash,
    per-pair IndividualPoolStake | None, per-pair lookup error | None)"""
    pair = np.concatenate([vc.vk_cold, vc.vrf_vk], axis=1)
    uniq, inv = _dedup_rows(pair)
    hks, entries, uerrs = [], [], []
    for j in range(uniq.shape[0]):
        vk_cold = uniq[j, :32].tobytes()
        hk = hash_key(vk_cold)
        entry = ledger_view.pool_distr.get(hk)
        hks.append(hk)
        entries.append(entry)
        if entry is None:
            uerrs.append(praos.VRFKeyUnknown(hk))
        else:
            header_vrf_hash = hash_vrf_vk(uniq[j, 32:].tobytes())
            if entry.vrf_key_hash != header_vrf_hash:
                uerrs.append(praos.VRFKeyWrongVRFKey(
                    hk, entry.vrf_key_hash, header_vrf_hash
                ))
            else:
                uerrs.append(None)
    return uniq, inv, hks, entries, uerrs


def host_prechecks_columns(
    params: PraosParams,
    ledger_view: LedgerView,
    vc: ViewColumns,
) -> ColumnChecks:
    """Columnar host_prechecks: same verdicts and error objects, zero
    per-header Python on the clean path."""
    n = len(vc)
    kes_errors, evol, bad_window = kes_window_checks(params, vc)
    _uniq, inv, hks, entries, uerrs = pool_pairs(ledger_view, vc)
    if any(e is not None for e in uerrs):
        vrf_errors = [uerrs[j] for j in inv.tolist()]
    else:
        vrf_errors = [None] * n
    clean = not bad_window and all(e is None for e in uerrs)
    return ColumnChecks(
        kes_errors, vrf_errors, evol,
        inv.astype(np.int32), tuple(hks), tuple(entries), clean,
    )


@lru_cache(maxsize=4096)
def _threshold_rows(sigma: Fraction, f: Fraction, bits: int = 256):
    """Encoded (lo, hi) threshold byte rows per (sigma, f) — the
    bracket itself is lru_cached too, but the per-header Fraction wrap
    + 32-byte to_bytes/frombuffer encoding dominated staging before
    this was hoisted. Clamped to the `bits`-bit compare domain: a
    threshold of 2^bits means "every value wins", encoded as all-0xFF +
    the hi-inclusive trick."""
    lo, hi = leader_threshold_bracket(sigma, f, bits)
    top, nb = (1 << bits) - 1, bits // 8
    return (
        np.frombuffer(min(lo, top).to_bytes(nb, "big"), np.uint8),
        np.frombuffer(min(hi, top).to_bytes(nb, "big"), np.uint8),
    )


def stage(
    params: PraosParams,
    ledger_view: LedgerView,
    epoch_nonce: nonces.Nonce,
    hvs: Sequence[HeaderView],
    evolution: np.ndarray,
    alpha_of=nonces.mk_input_vrf,  # (slot, epoch nonce) -> the VRF input
) -> PraosBatch:
    """Columnarize header views for the fused device kernel."""
    b = len(hvs)
    ed = ed25519_batch.stage_np(
        [hv.vk_cold for hv in hvs],
        [hv.ocert.sigma for hv in hvs],
        [hv.ocert.signable() for hv in hvs],
    )
    kes = kes_batch.stage_np(
        [hv.ocert.vk_hot for hv in hvs],
        [int(t) for t in evolution],
        [hv.signed_bytes for hv in hvs],
        [hv.kes_sig for hv in hvs],
        depth=params.kes_depth,
    )
    vrf = ecvrf_batch.stage_np(
        [hv.vrf_vk for hv in hvs],
        [hv.vrf_proof for hv in hvs],
        [alpha_of(hv.slot, epoch_nonce) for hv in hvs],
    )
    assert all(len(hv.vrf_output) == 64 for hv in hvs)
    beta = np.frombuffer(
        b"".join(hv.vrf_output for hv in hvs), np.uint8
    ).reshape(b, 64).copy()
    thr_lo = np.zeros((b, 32), np.uint8)
    thr_hi = np.zeros((b, 32), np.uint8)
    f = Fraction(params.active_slot_coeff)
    for i, hv in enumerate(hvs):
        entry = ledger_view.pool_distr.get(hash_key(hv.vk_cold))
        sigma = entry.stake if entry is not None else Fraction(0)
        lo_row, hi_row = _threshold_rows(sigma, f)
        thr_lo[i] = lo_row
        thr_hi[i] = hi_row
    return PraosBatch(ed, kes, vrf, beta, thr_lo, thr_hi)


def _be8_np(a: np.ndarray) -> np.ndarray:
    """[n] nonnegative int64 -> [n, 8] uint8 big-endian rows (the
    vectorized int.to_bytes(8, "big"))."""
    return np.ascontiguousarray(a).astype(">u8").view(np.uint8).reshape(-1, 8)


def _uniq_threshold_rows(
    params: PraosParams, pre: ColumnChecks, bits: int = 256
) -> tuple[list[np.ndarray], list[np.ndarray]]:
    """Per-UNIQUE-pool (lo, hi) threshold byte rows from the precheck
    dedup — the one place the unknown-pool sigma-0 convention and the
    clamped bracket encoding live for the columnar paths."""
    f = Fraction(params.active_slot_coeff)
    lo_rows, hi_rows = [], []
    for entry in pre.uniq_entry:
        sigma = entry.stake if entry is not None else Fraction(0)
        lo, hi = _threshold_rows(sigma, f, bits)
        lo_rows.append(lo)
        hi_rows.append(hi)
    return lo_rows, hi_rows


def _uniq_threshold_tables(
    params: PraosParams, pre: ColumnChecks, bits: int = 256
) -> tuple[np.ndarray, np.ndarray]:
    """(thr_lo [B, bits/8], thr_hi [B, bits/8]): the per-unique rows
    gathered per lane."""
    lo_rows, hi_rows = _uniq_threshold_rows(params, pre, bits)
    inv = pre.uniq_inv
    return np.stack(lo_rows)[inv], np.stack(hi_rows)[inv]


def _alpha_column(vc: ViewColumns, epoch_nonce: nonces.Nonce) -> np.ndarray:
    """[B, 32] VRF input column (mkInputVRF per slot). The Blake2b per
    header is inherent (host staging of the generic/native paths); the
    packed device path skips it entirely via alpha_from_slots."""
    b = len(vc)
    out = np.empty((b, 32), np.uint8)
    slots = vc.slot.tolist()
    for i in range(b):
        out[i] = np.frombuffer(
            nonces.mk_input_vrf(slots[i], epoch_nonce), np.uint8
        )
    return out


def stage_columns(
    params: PraosParams,
    ledger_view: LedgerView,
    epoch_nonce: nonces.Nonce,
    vc: ViewColumns,
    evolution: np.ndarray,
    pre: ColumnChecks,
) -> PraosBatch:
    """Columnar `stage`: the generic SoA batch built straight from the
    window columns — whole-matrix slices and one vectorized SHA pad per
    hash family, no per-header bytes. Byte-identical to
    `stage(..., vc.views(), ...)` (the columnar differential suite)."""
    from ..ops import sha512

    sigma = vc.ocert_sigma
    ed_r = np.ascontiguousarray(sigma[:, :32])
    ed_s = np.ascontiguousarray(sigma[:, 32:])
    # Ed25519 challenge-hash input R ‖ A ‖ signable(vk_hot ‖ n ‖ c0)
    ed_msg = np.concatenate(
        [ed_r, vc.vk_cold, vc.ocert_vk_hot,
         _be8_np(vc.ocert_counter), _be8_np(vc.ocert_kes_period)], axis=1,
    )
    ed_hb, ed_hnb = sha512.pad_matrix_np(ed_msg)
    ed = ed25519_batch.Ed25519Batch(
        np.ascontiguousarray(vc.vk_cold), ed_r, ed_s, ed_hb, ed_hnb
    )

    ks = vc.kes_sig
    kes_r = np.ascontiguousarray(ks[:, :32])
    kes_s = np.ascontiguousarray(ks[:, 32:64])
    vk_leaf = np.ascontiguousarray(ks[:, 64:96])
    depth = params.kes_depth
    siblings = np.ascontiguousarray(ks[:, 96:].reshape(len(vc), depth, 32))
    kes_msg = np.concatenate([kes_r, vk_leaf, vc.signed_bytes], axis=1)
    kes_hb, kes_hnb = kes_batch.pad_rows_np(kes_msg, 64 + vc.signed_len)
    kes = kes_batch.KesBatch(
        np.ascontiguousarray(vc.ocert_vk_hot),
        np.asarray(evolution, np.int32),
        kes_r, kes_s, vk_leaf, siblings, kes_hb, kes_hnb,
    )

    plen = int(vc.vrf_proof_len[0])
    proof = vc.vrf_proof
    gamma = np.ascontiguousarray(proof[:, :32])
    alpha = _alpha_column(vc, epoch_nonce)
    pk = np.ascontiguousarray(vc.vrf_vk)
    if plen == 128:
        vrf = ecvrf_batch.EcvrfBcBatch(
            pk, gamma,
            np.ascontiguousarray(proof[:, 32:64]),
            np.ascontiguousarray(proof[:, 64:96]),
            np.ascontiguousarray(proof[:, 96:128]),
            alpha,
        )
    else:
        vrf = ecvrf_batch.EcvrfBatch(
            pk, gamma,
            np.ascontiguousarray(proof[:, 32:48]),
            np.ascontiguousarray(proof[:, 48:80]),
            alpha,
        )

    thr_lo, thr_hi = _uniq_threshold_tables(params, pre)
    beta = np.ascontiguousarray(vc.vrf_output)
    return PraosBatch(ed, kes, vrf, beta, thr_lo, thr_hi)


# ---------------------------------------------------------------------------
# Fused device kernel
# ---------------------------------------------------------------------------


def _lt_be(a, b):
    """Big-endian lexicographic a < b for [..., 32] int32 byte arrays.

    all_eq_before via a CUMSUM of mismatch indicators (== 0 while every
    earlier byte matched), not cumprod: an unrolled 32-long cumprod is a
    multiply chain in the top-level computation, and two of these (leader
    lo/hi compares) were the op pattern that still sent XLA's algebraic
    simplifier into its circular-simplification loop on the composed spmd
    program (round-7; same family as the PR-1 ladder-chain remediation —
    cumsum is add-class, which the simplifier's reassociation rewrites
    leave alone)."""
    ne = (a != b).astype(jnp.int32)
    mismatches_before = jnp.cumsum(
        jnp.concatenate([jnp.zeros_like(ne[..., :1]), ne[..., :-1]], axis=-1),
        axis=-1,
    )
    all_eq_before = mismatches_before == 0
    return jnp.any(all_eq_before & (a < b), axis=-1)


class Verdicts(NamedTuple):
    """Per-lane verdict bitmaps + derived values (device arrays)."""

    ok_ocert_sig: jnp.ndarray  # [B] InvalidSignatureOCERT if False
    ok_kes_sig: jnp.ndarray  # [B] InvalidKesSignatureOCERT if False
    ok_vrf: jnp.ndarray  # [B] VRFKeyBadProof if False (proof or beta mismatch)
    ok_leader: jnp.ndarray  # [B] VRFLeaderValueTooBig if False
    leader_ambiguous: jnp.ndarray  # [B] host must decide exactly
    eta: jnp.ndarray  # [B, 32] vrfNonceValue(beta) for the nonce fold
    leader_value: jnp.ndarray  # [B, 32] big-endian Blake2b("L" ‖ beta)


class TPraosVerdicts(NamedTuple):
    """`Verdicts` of a TPraos window (two proofs a header): `ok_vrf` is
    both proofs, `eta` Blake2b-256(beta_eta), `leader_value` the raw
    64-byte beta_L, and `ok_vrf_nonce` the NONCE proof alone, so that
    the error names which one failed."""

    ok_ocert_sig: jnp.ndarray
    ok_kes_sig: jnp.ndarray
    ok_vrf: jnp.ndarray
    ok_leader: jnp.ndarray
    leader_ambiguous: jnp.ndarray
    eta: jnp.ndarray  # [B, 32]
    leader_value: jnp.ndarray  # [B, 64]
    ok_vrf_nonce: jnp.ndarray  # [B]


def _leader_nonce_tail(beta_decl, thr_lo, thr_hi):
    """Shared tail of the fused verifiers: leader-value + eta range
    extensions (Praos/VRF.hs:103,116) on the DECLARED beta — ok_vrf
    guarantees it equals the proof's beta — and the two-threshold
    leader comparison. (ops/pk/aggregate.py carries the limb-first
    twin of this block.)"""
    tag_l = jnp.broadcast_to(
        jnp.asarray([ord("L")], jnp.int32), (*beta_decl.shape[:-1], 1)
    )
    lv = blake2b.blake2b_fixed(
        jnp.concatenate([tag_l, beta_decl], axis=-1), 65, 32
    )  # 32 bytes, big-endian natural (hash bytes ARE the BE encoding)
    tag_n = jnp.broadcast_to(
        jnp.asarray([ord("N")], jnp.int32), (*beta_decl.shape[:-1], 1)
    )
    eta1 = blake2b.blake2b_fixed(
        jnp.concatenate([tag_n, beta_decl], axis=-1), 65, 32
    )
    eta = blake2b.blake2b_fixed(eta1, 32, 32)

    thr_lo = jnp.asarray(thr_lo).astype(jnp.int32)
    thr_hi = jnp.asarray(thr_hi).astype(jnp.int32)
    certain_win = _lt_be(lv, thr_lo)
    certain_loss = ~_lt_be(lv, thr_hi)
    ambiguous = ~certain_win & ~certain_loss
    return certain_win, ambiguous, eta, lv


def verify_praos(
    ed_pk, ed_r, ed_s, ed_hblocks, ed_hnblocks,
    kes_vk, kes_period, kes_r, kes_s, kes_vk_leaf, kes_siblings,
    kes_hblocks, kes_hnblocks,
    vrf_pk, vrf_gamma, vrf_c, vrf_s, vrf_alpha,
    beta_decl, thr_lo, thr_hi,
) -> Verdicts:
    """The fused Praos hot-path kernel. One jit, one device program.

    XLA fuses the three verifier subgraphs and the Blake2b range
    extensions; everything is batch-uniform control flow (mask lanes).
    The seven per-lane point compressions (Ed25519 R-check, KES leaf
    R-check, ECVRF H/Γ/U/V/8Γ) share ONE Montgomery inversion chain.
    """
    from ..ops import curve

    ok_ed_pre, ed_point = ed25519_batch.verify_point(
        ed_pk, ed_s, ed_hblocks, ed_hnblocks
    )
    ok_kes_pre, kes_point = kes_batch.verify_point(
        kes_vk, kes_period, kes_s, kes_vk_leaf, kes_siblings,
        kes_hblocks, kes_hnblocks,
    )
    ok_vrf_pre, vrf_points = ecvrf_batch.verify_points(
        vrf_pk, vrf_gamma, vrf_c, vrf_s, vrf_alpha
    )
    encs = curve.compress_many([ed_point, kes_point, *vrf_points])
    ok_ed = ok_ed_pre & jnp.all(
        encs[0] == jnp.asarray(ed_r).astype(jnp.int32), axis=-1
    )
    ok_kes = ok_kes_pre & jnp.all(
        encs[1] == jnp.asarray(kes_r).astype(jnp.int32), axis=-1
    )
    ok_proof, beta = ecvrf_batch.finish(ok_vrf_pre, vrf_c, encs[2:])
    beta_decl = jnp.asarray(beta_decl).astype(jnp.int32)
    ok_vrf = ok_proof & jnp.all(beta == beta_decl, axis=-1)

    certain_win, ambiguous, eta, lv = _leader_nonce_tail(
        beta_decl, thr_lo, thr_hi
    )
    return Verdicts(ok_ed, ok_kes, ok_vrf, certain_win, ambiguous, eta, lv)


def verify_praos_bc(
    ed_pk, ed_r, ed_s, ed_hblocks, ed_hnblocks,
    kes_vk, kes_period, kes_r, kes_s, kes_vk_leaf, kes_siblings,
    kes_hblocks, kes_hnblocks,
    vrf_pk, vrf_gamma, vrf_u, vrf_v, vrf_s, vrf_alpha,
    beta_decl, thr_lo, thr_hi,
) -> Verdicts:
    """The fused hot path over BATCH-COMPATIBLE (128-byte) VRF proofs:
    identical to verify_praos except the challenge is derived on device
    from the announced U, V (ops/ecvrf_batch.verify_points_bc); the
    ed/kes subgraphs and the finish hashing are byte-identical."""
    from ..ops import curve

    ok_ed_pre, ed_point = ed25519_batch.verify_point(
        ed_pk, ed_s, ed_hblocks, ed_hnblocks
    )
    ok_kes_pre, kes_point = kes_batch.verify_point(
        kes_vk, kes_period, kes_s, kes_vk_leaf, kes_siblings,
        kes_hblocks, kes_hnblocks,
    )
    ok_vrf_pre, c16, vrf_points = ecvrf_batch.verify_points_bc(
        vrf_pk, vrf_gamma, vrf_u, vrf_v, vrf_s, vrf_alpha
    )
    encs = curve.compress_many([ed_point, kes_point, *vrf_points])
    ok_ed = ok_ed_pre & jnp.all(
        encs[0] == jnp.asarray(ed_r).astype(jnp.int32), axis=-1
    )
    ok_kes = ok_kes_pre & jnp.all(
        encs[1] == jnp.asarray(kes_r).astype(jnp.int32), axis=-1
    )
    ok_proof, beta = ecvrf_batch.finish(ok_vrf_pre, c16, encs[2:])
    beta_decl = jnp.asarray(beta_decl).astype(jnp.int32)
    ok_vrf = ok_proof & jnp.all(beta == beta_decl, axis=-1)

    certain_win, ambiguous, eta, lv = _leader_nonce_tail(
        beta_decl, thr_lo, thr_hi
    )
    return Verdicts(ok_ed, ok_kes, ok_vrf, certain_win, ambiguous, eta, lv)


def verify_tpraos(
    ed_pk, ed_r, ed_s, ed_hblocks, ed_hnblocks,
    kes_vk, kes_period, kes_r, kes_s, kes_vk_leaf, kes_siblings,
    kes_hblocks, kes_hnblocks,
    vrf_pk, eta_gamma, eta_c, eta_s, eta_alpha,
    l_gamma, l_c, l_s, l_alpha,
    beta_eta, beta_l, thr_lo, thr_hi, overlay,
) -> TPraosVerdicts:
    """The XLA twin of the TPraos window (ops/pk: ed, kes, `vrf` twice,
    `finish_tp`): both certificates' proofs and declared outputs, the
    512-bit leader rule on the RAW beta_L with the overlay bit in place
    of the threshold, eta = Blake2b-256(beta_eta). Twelve points share
    the one inversion."""
    from ..ops import curve

    ok_ed_pre, ed_point = ed25519_batch.verify_point(
        ed_pk, ed_s, ed_hblocks, ed_hnblocks
    )
    ok_kes_pre, kes_point = kes_batch.verify_point(
        kes_vk, kes_period, kes_s, kes_vk_leaf, kes_siblings,
        kes_hblocks, kes_hnblocks,
    )
    ok_e_pre, e_points = ecvrf_batch.verify_points(
        vrf_pk, eta_gamma, eta_c, eta_s, eta_alpha
    )
    ok_l_pre, l_points = ecvrf_batch.verify_points(
        vrf_pk, l_gamma, l_c, l_s, l_alpha
    )
    encs = curve.compress_many([ed_point, kes_point, *e_points, *l_points])
    ok_ed = ok_ed_pre & jnp.all(
        encs[0] == jnp.asarray(ed_r).astype(jnp.int32), axis=-1
    )
    ok_kes = ok_kes_pre & jnp.all(
        encs[1] == jnp.asarray(kes_r).astype(jnp.int32), axis=-1
    )
    ok_e, b_e = ecvrf_batch.finish(ok_e_pre, eta_c, encs[2:7])
    ok_l, b_l = ecvrf_batch.finish(ok_l_pre, l_c, encs[7:12])
    beta_eta = jnp.asarray(beta_eta).astype(jnp.int32)
    beta_l = jnp.asarray(beta_l).astype(jnp.int32)
    ok_e = ok_e & jnp.all(b_e == beta_eta, axis=-1)
    ok_l = ok_l & jnp.all(b_l == beta_l, axis=-1)
    eta = blake2b.blake2b_fixed(beta_eta, 64, 32)
    over = jnp.asarray(overlay) != 0
    thr_lo = jnp.asarray(thr_lo).astype(jnp.int32)
    thr_hi = jnp.asarray(thr_hi).astype(jnp.int32)
    certain_win = _lt_be(beta_l, thr_lo)
    ambiguous = ~certain_win & _lt_be(beta_l, thr_hi) & ~over
    return TPraosVerdicts(ok_ed, ok_kes, ok_e & ok_l, certain_win | over,
                          ambiguous, eta, beta_l, ok_e)


def verify_praos_any(*cols) -> Verdicts:
    """Arity dispatch over the two staged formats: 21 columns = draft-03
    (verify_praos), 22 = batch-compatible (verify_praos_bc). Used by the
    spmd local step, whose column list follows the staged batch."""
    if len(cols) == 22:
        return verify_praos_bc(*cols)
    return verify_praos(*cols)


_JIT: dict = {}

# warmup forensics: (stage:lanes) labels whose first execute has been
# recorded — the wrapper below costs one set lookup per call after that
_WARM_SEEN: set = set()


def _arg_lanes(a) -> int | None:
    """Leading batch axis of the first array argument."""
    return next(
        (int(x.shape[0]) for x in a
         if hasattr(x, "shape") and getattr(x, "ndim", 0) >= 1),
        None,
    )


def _store_name(label: str) -> str:
    """AOT-store stage name of an XLA-twin warmup label (the label's
    lane qualifier is carried by the store key's `b`, not the name)."""
    import re

    return re.sub(r"[^A-Za-z0-9_]+", "_", label)


def _warm_timed(stage: str, fn):
    """Wrap a jitted program so its FIRST execute (where the compile —
    or cache/store load — happens synchronously) records its wall into
    the obs warmup flight recorder. The r02-r05 ~410 s compile walls
    died without attribution; this is the per-stage black box.

    The first-execute label is qualified by the padded LANE count
    (`<stage>:<lanes>l`): one program family runs at more than one lane
    count (a window keeps its own bucket on the XLA twin), and the
    warmup report must attribute each shape's first execute separately
    (a 1024-lane first execute does not make the 8192-lane program
    warm). The first execute also consults the build-pinned AOT store
    (ops/pk/aot): a stored executable loads instead of compiling, and
    with OCT_PK_AOT_WRITEBACK=1 a fresh compile is re-serialized into
    the store so the next process on this build loads warm. The
    load/write-back executable memo is CLOSURE-local (per wrapped fn,
    sig-checked — a Compiled is shape-exact and the generic staged
    program's KES hash-block count varies per batch): the explicit
    compile path does not populate the jit's own cache, but a memo
    keyed by label alone would keep serving a stale program after the
    jit behind the label is rebuilt."""
    warm_exec: dict = {}

    def wrapper(*a, **k):
        from ..ops.pk import aot as pk_aot

        lanes = _arg_lanes(a)
        label = f"{stage}:{lanes}l" if lanes is not None else stage
        if label in _WARM_SEEN:
            stored = warm_exec.get(label)
            if stored is not None and stored[0] == pk_aot.sig_of(a):
                return stored[1](*a)
            return fn(*a, **k)
        from ..obs.warmup import WARMUP

        # breadcrumb BEFORE the call: a kill mid-compile still leaves
        # "<label> first execute starting" as the report's last note
        WARMUP.note(f"{label} first execute starting")
        t0 = time.monotonic()
        ex = None
        via = "xla-jit"
        name = _store_name(stage)
        if pk_aot.enabled():
            try:
                sig = pk_aot.sig_of(a)
                ex = pk_aot.load(name, lanes or 0, 0, 0, sig)
                if ex is not None:
                    via = "xla-aot"
            except Exception:  # noqa: BLE001 # octflow: disable=FLOW303
                # — fail-soft by contract: a failed AOT load falls
                # through to the fresh-compile dispatch just below
                ex = None
        if ex is None and pk_aot.writeback_enabled():
            ex = pk_aot.compile_and_store(name, lanes or 0, 0, 0, fn, a)
        try:
            out = ex(*a, **k) if ex is not None else fn(*a, **k)
        except Exception as e:
            if ex is None:
                raise
            # a stored executable that dies on device falls back to the
            # jit path — never worse than the pre-store behavior
            pk_aot.note_failure(e)
            pk_aot._note_aot(name, "run_failed", detail=repr(e))
            ex, via = None, "xla-jit"
            out = fn(*a, **k)
        if ex is not None:
            import jax

            jax.block_until_ready(out)
            warm_exec[label] = (pk_aot.sig_of(a), ex)
        wall = time.monotonic() - t0
        _WARM_SEEN.add(label)
        WARMUP.note_stage(label, wall, via=via)
        # device resource accounting rides the same first-execute gate:
        # one re-lower (trace only, no XLA compile) while capture is
        # enabled — lanes read off the leading batch axis. AFTER the
        # warmup note by design: a kill mid-capture must not eat the
        # already-flushed compile-wall forensics.
        from ..obs import resources as obs_resources

        obs_resources.capture_stage(label, ex if ex is not None else fn,
                                    a, lanes=lanes, via=via)
        return out

    return wrapper


# device implementation: "pk" = Pallas kernels (ops/pk, limb-first,
# ladders in VMEM — the TPU production path), "xla" = the original jnp
# graph (the cross-check twin; also the CPU default, where the pk path
# only exists as interpret-mode and compiles far slower than it runs)
DEVICE_IMPL = os.environ.get("OCT_DEVICE_IMPL", "")

# the "cut the wire" path: packed body-sourced H2D staging + on-device
# verdict-bit packing; the eta column ships back as uint8 and the host
# folds the nonces (a hash chain: 8192 serial Blake2b compressions took
# the chip 3.3 s a window and take the host 12 ms — PERF.md, PR 29).
# OCT_PACKED_STAGE=0 restores the round-5 staged-column path end to end.
PACKED_STAGE = os.environ.get("OCT_PACKED_STAGE", "1") != "0"


def _stage_thread_enabled() -> bool:
    """OCT_STAGE_THREAD (default 1): run prechecks + packed staging on
    a producer thread ahead of dispatch in validate_chain's device
    loop, double-buffering H2D staging against device compute with
    backpressure at pipeline_depth. =0 restores the inline (round-9)
    staging — the differential kill-switch; read per call so tests can
    A/B both paths in one process."""
    return os.environ.get("OCT_STAGE_THREAD", "1") != "0"


def _agg_enabled() -> bool:
    """Whether packed batch-compatible windows verify by the
    random-linear-combination aggregate + MSM (ops/pk/aggregate.py,
    per-lane fallback on any anomaly) or by the per-lane stage kernels.

    An explicit OCT_VRF_AGG (=1 aggregate, =0 per-lane) and the recovery
    overrides decide when given. With nothing asked, the answer follows
    the implementation: on `pk` — the chip — the default is the per-lane
    stage kernels (kernels.verify_praos_packed_split), the only device
    path with any evidence of a v5e compile (three attempts to compile
    the aggregate monolith for a v5e ended in the compiler's HLO passes
    with no executable); on the XLA twin the default stays the
    aggregate, so no CPU test changes what it runs. ROADMAP Speed 2
    settles whether the aggregate comes back on the chip or goes. Read
    per call so the differential tests can A/B both paths in one
    process."""
    ov = getattr(_RECOVERY_OVERRIDES, "vals", None)
    if ov is not None and ov.get("agg") is not None:
        return bool(ov["agg"])
    asked = os.environ.get("OCT_VRF_AGG", "")
    if asked:
        return asked != "0"
    return _impl() != "pk"


def _rlc_all_enabled() -> bool:
    """OCT_RLC_ALL (default 1): fold the Ed25519 and KES equations into
    the shared-bucket window MSM (`aggregate_window` — one signed-digit
    bucket pass over every stage). =0 keeps the window aggregated but
    restores the vrf-only RLC with exact per-lane ed/kes ladders
    (`aggregate_window_vrf`, the pre-fold shape on the unsigned engine)
    — the isolation kill-switch for the shared-bucket machinery. Only
    consulted when `_agg_enabled()` admits the aggregate path at all.
    Read per call like OCT_VRF_AGG so tests can A/B in one process."""
    ov = getattr(_RECOVERY_OVERRIDES, "vals", None)
    if ov is not None and ov.get("rlc_all") is not None:
        return bool(ov["rlc_all"])
    return os.environ.get("OCT_RLC_ALL", "1") != "0"


def _impl() -> str:
    ov = getattr(_RECOVERY_OVERRIDES, "vals", None)
    if ov is not None and ov.get("impl"):
        return ov["impl"]
    if DEVICE_IMPL:
        return DEVICE_IMPL
    import jax

    return "pk" if jax.devices()[0].platform == "tpu" else "xla"


# per-thread path overrides for the recovery ladder (obs/recovery.py):
# a rung re-validates ONE failing window with the aggregate fast path
# forced off (stage-split — the materialize_verdicts taxonomy path) or
# the implementation pinned to the XLA twin, without touching the env
# the rest of the process (and the staging thread) keeps reading.
_RECOVERY_OVERRIDES = threading.local()


class recovery_overrides:
    """Context manager: pin `_agg_enabled()` / `_impl()` for THIS
    thread while a recovery rung re-validates a window."""

    def __init__(self, agg=None, impl=None, rlc_all=None):
        self._vals = {"agg": agg, "impl": impl, "rlc_all": rlc_all}

    def __enter__(self):
        self._prev = getattr(_RECOVERY_OVERRIDES, "vals", None)
        _RECOVERY_OVERRIDES.vals = self._vals
        return self

    def __exit__(self, *exc):
        _RECOVERY_OVERRIDES.vals = self._prev
        return False


def flatten_batch(batch: PraosBatch) -> list:
    """PraosBatch -> flat array list in verify_praos argument order."""
    return [*batch.ed, *batch.kes, *batch.vrf, batch.beta, batch.thr_lo, batch.thr_hi]


def _words_to_byte_blocks(w: np.ndarray) -> np.ndarray:
    """SHA-512 word blocks [B, NB, 16, 2] uint32 -> [NB, 128, B] int32
    byte blocks (the ops/pk limb-first hash input layout)."""
    b_, nb = w.shape[0], w.shape[1]
    out = np.zeros((b_, nb, 16, 8), np.int32)
    for k in range(4):
        out[..., k] = ((w[..., 0] >> (24 - 8 * k)) & 0xFF).astype(np.int32)
        out[..., 4 + k] = ((w[..., 1] >> (24 - 8 * k)) & 0xFF).astype(np.int32)
    return np.ascontiguousarray(out.reshape(b_, nb, 128).transpose(1, 2, 0))


def _t(a: np.ndarray) -> np.ndarray:
    """[B, n] -> [n, B] int32, contiguous."""
    return np.ascontiguousarray(np.asarray(a).astype(np.int32).T)


def batch_is_bc(batch: PraosBatch) -> bool:
    """True when the staged vrf columns carry batch-compatible proofs."""
    return isinstance(batch.vrf, ecvrf_batch.EcvrfBcBatch)


def pk_arrays(batch: PraosBatch) -> list[np.ndarray]:
    """PraosBatch ([B, ...] staging) -> limb-first arrays in
    ops/pk/kernels.verify_praos_tiles argument order (the bc-staged
    format inserts the announced u, v columns in place of c)."""
    ed, kes, vrf = batch.ed, batch.kes, batch.vrf
    b = batch.beta.shape[0]
    if batch_is_bc(batch):
        vrf_cols = [_t(vrf.pk), _t(vrf.gamma), _t(vrf.u), _t(vrf.v),
                    _t(vrf.s), _t(vrf.alpha)]
    else:
        vrf_cols = [_t(vrf.pk), _t(vrf.gamma), _t(vrf.c), _t(vrf.s),
                    _t(vrf.alpha)]
    return [
        _t(ed.pk), _t(ed.r), _t(ed.s),
        _words_to_byte_blocks(ed.hblocks),
        np.ascontiguousarray(ed.hnblocks.astype(np.int32).reshape(1, b)),
        _t(kes.vk),
        np.ascontiguousarray(kes.period.astype(np.int32).reshape(1, b)),
        _t(kes.r), _t(kes.s), _t(kes.vk_leaf),
        np.ascontiguousarray(
            np.asarray(kes.siblings).astype(np.int32).transpose(1, 2, 0)
        ),
        _words_to_byte_blocks(kes.hblocks),
        np.ascontiguousarray(kes.hnblocks.astype(np.int32).reshape(1, b)),
        *vrf_cols,
        _t(batch.beta), _t(batch.thr_lo), _t(batch.thr_hi),
    ]


# ---------------------------------------------------------------------------
# Packed staging: body-sourced H2D columns + on-device verdict reduction
# ---------------------------------------------------------------------------


# a packed window's body layout table (`PraosPacked.body_tab`): a row a
# signed-body layout, the body's length and the byte offset of each
# field the device extracts. A chain's bodies differ in both wherever a
# CBOR integer before a field steps width (block number and slot from
# genesis, the body size on a chain with real block bodies), so a
# window holds a few. The leader columns are a TPraos body's LEADER
# certificate (64-byte output, 80-byte draft-03 proof; `vrf_out` /
# `vrf_proof` are then its NONCE certificate), 0 in a Praos body's row.
# Every entry is under 2^16 (staging checks): `unpack`'s arithmetic on
# the table is certified for that (analysis/shapes.json)
BODY_TAB_COLS = (
    "length",
    "issuer",  # vk_cold (32)
    "vrf_vk",  # vrf_vk (32)
    "vrf_out",  # declared beta (64)
    "vrf_proof",  # gamma ‖ c ‖ s (80) or gamma ‖ u ‖ v ‖ s (128)
    "vk_hot",  # OCert KES root vk (32)
    "sigma",  # OCert cold-key signature R ‖ s (64)
    "vrf_leader_out",
    "vrf_leader_proof",
)
# a window holds a few body layouts; past this many it stages generic
_MAX_BODY_LAYOUTS = 16
# a field's offset differs between a window's layouts by less than this
# (a power of two): `unpack` shifts each lane's field into place one
# select a bit. The widest CBOR steps before a field (block number,
# slot, the previous hash's null, body size, counter, period) add up to
# 73 bytes
_MAX_BODY_SHIFT = 128


class PraosPackedLayout(NamedTuple):
    """Static per-window descriptor of the packed staging format
    (hashable — part of the jit cache key). Where a lane's fields lie in
    its body rides the wire, not the descriptor: the window's body
    layouts as a table (`PraosPacked.body_tab`) and each lane's row of
    it (`.body_layout`), so ONE `unpack` program serves every window of
    a body width and proof format, whatever layouts it holds.
    `stage_packed_columns` VERIFIES every lane's fields at its own
    layout's offsets before committing to this format."""

    body_len: int  # the body column's width: the window's widest body
    kes_depth: int
    slots_per_kes: int
    has_nonce: bool  # False = neutral epoch nonce (genesis)
    vrf_proof_len: int = 80  # 80 = draft-03, 128 = batch-compatible
    two_certs: bool = False  # a TPraos body: two VRF certificates

    @property
    def proofs(self) -> int:
        """VRF proofs the device verifies a lane: the count the layout
        fixes, and with it the window's programs (`unpack`, `finish` or
        `finish_tp`; the `vrf` stage once or twice)."""
        return 2 if self.two_certs else 1


class PraosPacked(NamedTuple):
    """Packed device-ready columns — the minimal wire format.

    ~2-3x fewer H2D bytes per window than PraosBatch on real chains: the
    signed body column is the SINGLE source of every field it embeds
    (issuer/VRF keys, proof, declared beta, OCert), the KES Merkle tail
    (leaf vk ‖ siblings — period-constant per pool) is deduplicated into
    a window table, SHA-512 block padding and the 32-byte VRF alpha are
    built on device (ops/kes_batch.build_hblocks,
    ops/ecvrf_batch.alpha_from_slots), and the leader thresholds ride as
    a per-pool table + per-lane index."""

    body: np.ndarray  # [B, body_len] uint8 — KES-signed header body,
    # zero-padded past each lane's own length
    kes_rs: np.ndarray  # [B, 64] uint8 — KES leaf signature R ‖ s
    kes_tail_idx: np.ndarray  # [B] int32 into kes_tail_tab
    kes_tail_tab: np.ndarray  # [Kt, 32 + depth*32] uint8 — leaf vk ‖ siblings
    slot: np.ndarray  # [B] int32
    counter: np.ndarray  # [B] int32 — OCert issue number
    c0: np.ndarray  # [B] int32 — OCert start KES period
    thr_idx: np.ndarray  # [B] int32 into thr_tab
    thr_tab: np.ndarray  # [Kr, 64] uint8 — thr_lo ‖ thr_hi per pool
    nonce: np.ndarray  # [32] uint8 — epoch nonce bytes (zeros if neutral)
    body_layout: np.ndarray  # [B] int32 — each lane's row of body_tab
    # [_MAX_BODY_LAYOUTS, len(BODY_TAB_COLS)] int32 — the window's body
    # layouts (rows past them replicate the first)
    body_tab: np.ndarray


class TPraosPacked(NamedTuple):
    """`PraosPacked` for a two-certificate (TPraos) window: the body
    column embeds both certificates, the threshold rows are 512-bit
    (`thr_tab` [Kr, 128]: the raw 64-byte leader output is compared,
    not its hash) and each lane says whether the overlay schedule, not
    the lottery, gave it its slot."""

    body: np.ndarray
    kes_rs: np.ndarray
    kes_tail_idx: np.ndarray
    kes_tail_tab: np.ndarray
    slot: np.ndarray
    counter: np.ndarray
    c0: np.ndarray
    thr_idx: np.ndarray
    thr_tab: np.ndarray  # [Kr, 128] uint8 — thr_lo ‖ thr_hi, 64 bytes each
    nonce: np.ndarray
    body_layout: np.ndarray
    body_tab: np.ndarray
    overlay: np.ndarray  # [B] int32 — 1 = an active overlay slot's lane


# why the last packed-staging attempt declined (the PR 5 gates were
# silent about why a window fell back). Written by `_decline` on every
# early-out in stage_packed/stage_packed_columns — one module-global
# assignment, so the qualification hot path stays untaxed — and read by
# dispatch_batch into the WindowStaged/WindowSpan telemetry events.
_LAST_DECLINE: str | None = None


def _decline(reason: str) -> None:
    """Record WHICH qualification gate said no, then decline (None)."""
    global _LAST_DECLINE
    _LAST_DECLINE = reason
    return None


def _table_bucket(k: int, minimum: int = 8) -> int:
    """Power-of-two bucket for a window table's row count (bounds the
    set of compiled shapes, same rationale as bucket_size)."""
    n = minimum
    while n < k:
        n *= 2
    return n


def _table_rows(params: PraosParams, ledger_view: LedgerView, slots,
                kes_tails: int, thr_rows: int) -> tuple[int, int]:
    """Row counts of a window's two dedup tables (KES tails, thresholds):
    the bucket of what the window COULD hold, not of what it happens to.
    A window of b lanes whose slots span k KES periods under a ledger
    view of P pools holds at most min(b, P * k) tails and min(b, P)
    threshold rows (more only if a pool changes its hot key or is
    unknown; then the count itself is bucketed). One pool reads 8 and 8
    as before. With hundreds of pools the count a window happens to hold
    crosses a power of two from seed to seed (15 issuers or 17 among the
    chain's first lanes), so a replay on a warm store met an `unpack`
    shape nobody had built, built it in set-up, and paid the heap that
    leaves with a 3.9 s collection inside its window (PERF.md, PR 32)."""
    b = len(slots)
    kp = np.asarray(slots) // params.slots_per_kes_period
    pools = rules_of(params).issuers(ledger_view)
    periods = int(kp.max() - kp.min()) + 1
    return (_table_bucket(max(kes_tails, min(b, pools * periods))),
            _table_bucket(max(thr_rows, min(b, pools))))


def stage_packed(
    params: PraosParams,
    ledger_view: LedgerView,
    epoch_nonce: nonces.Nonce,
    hvs: Sequence[HeaderView],
) -> tuple[PraosPackedLayout, PraosPacked] | None:
    """Columnarize a window into the packed H2D format, or None when the
    window does not qualify (the caller falls back to `stage`): the
    views as `ViewColumns`, then `stage_packed_columns`' verified
    qualification. Real CBOR header codecs (block/praos_block.py, the
    synthesizer chains) always qualify; synthetic test views whose
    signed bytes do not embed the fields fall back."""
    if not hvs:
        return _decline("empty-window")
    if hvs[0].vrf_leader_proof is not None:
        # TPraos windows stage columnar (`stage_packed_columns`)
        return _decline("two-certificates")
    vc = ViewColumns.from_views(hvs)
    if vc is None:
        return _decline("columns")
    return stage_packed_columns(params, ledger_view, epoch_nonce, vc,
                                host_prechecks_columns(params, ledger_view,
                                                       vc))


def _body_layouts(body: np.ndarray, lens: np.ndarray, refs
                  ) -> tuple[np.ndarray, np.ndarray] | None:
    """-> (the window's body layout table, [L, len(BODY_TAB_COLS)]
    int32, and [B] int32 each lane's row of it), or None (declined). A
    layout's offsets are found in one lane (where each field of `refs`
    first occurs in its body) and VERIFIED byte for byte in every lane
    given it, which has that body length: how an offset is found does
    not matter, the equality makes extraction at a lane's own offsets
    exact. The lanes no layout has taken yet give the next one, found in
    the first lane of their most common body length: the first pass,
    over the whole window, takes most of it, and the later ones gather
    few rows."""
    n = body.shape[0]
    lane = np.zeros(n, np.int32)
    rows: list = []
    rest = np.arange(n)
    while rest.size:
        if len(rows) == _MAX_BODY_LAYOUTS:
            return _decline("body-layouts")
        widths, counts = np.unique(lens[rest], return_counts=True)
        u = int(rest[np.argmax(lens[rest] == widths[counts.argmax()])])
        row = body[u, : int(lens[u])].tobytes()
        offs = tuple(row.find(r[u].tobytes()) for r in refs)
        if min(offs) < 0:
            return _decline("field-mismatch" if rows else "field-offsets")
        whole = rest.size == n  # the first pass: no gather
        sub = body if whole else body[rest]
        ok = lens[rest] == lens[u]
        for o, r in zip(offs, refs):
            ok &= (sub[:, o : o + r.shape[1]]
                   == (r if whole else r[rest])).all(axis=1)
        lane[rest[ok]] = len(rows)
        rows.append((int(lens[u]), *offs))
        rest = rest[~ok]
    tab = np.zeros((len(rows), len(BODY_TAB_COLS)), np.int32)
    tab[:, : len(rows[0])] = rows
    spread = tab[:, 1 : len(rows[0])].max(0) - tab[:, 1 : len(rows[0])].min(0)
    if spread.max() >= _MAX_BODY_SHIFT or tab.max() > 0xFFFF:
        return _decline("body-layouts")
    return tab, lane


def stage_packed_columns(
    params: PraosParams,
    ledger_view: LedgerView,
    epoch_nonce: nonces.Nonce,
    vc: ViewColumns,
    pre: ColumnChecks,
) -> tuple[PraosPackedLayout, PraosPacked] | None:
    """The packed wire built straight from the window columns. The
    columns are already row-major uint8, so the body column IS
    `vc.signed_bytes` (cut to the window's widest body), the per-field
    verification is a few whole-matrix compares a body layout
    (`_body_layouts`), the KES-tail dedup is one np.unique, and the
    threshold table rides the precheck pool dedup — nothing slices
    per-header bytes. Whenever this returns a layout, the device
    extraction is byte-identical to the generic staged path."""
    b = len(vc)
    if not b:
        return _decline("empty-window")
    lens = vc.signed_len
    body = vc.signed_bytes[:, : int(lens.max())]
    if epoch_nonce is not None and len(epoch_nonce) != 32:
        return _decline("nonce-len")
    depth = params.kes_depth
    sig_len = 64 + 32 + 32 * depth
    if vc.kes_sig.shape[1] != sig_len:
        return _decline("kes-sig-len")
    plen = int(vc.vrf_proof_len[0])
    if plen not in (80, 128) or not (vc.vrf_proof_len == plen).all():
        return _decline("proof-format")

    proof_ref = np.ascontiguousarray(vc.vrf_proof[:, :plen])
    two = vc.two_certs
    if two and (plen != 80 or pre.overlay is None):
        return _decline("proof-format")
    refs = (
        vc.vk_cold, vc.vrf_vk, vc.vrf_output, proof_ref,
        vc.ocert_vk_hot, vc.ocert_sigma,
        *((vc.vrf_leader_output, vc.vrf_leader_proof) if two else ()),
    )
    found = _body_layouts(body, lens, refs)
    if found is None:
        return None
    rows, lane_layout = found
    body_tab = np.repeat(rows[:1], _MAX_BODY_LAYOUTS, axis=0)
    body_tab[: len(rows)] = rows

    slot, counter, c0 = vc.slot, vc.ocert_counter, vc.ocert_kes_period
    for a in (slot, counter, c0):
        if a.min() < 0 or a.max() >= 2**31:
            return _decline("int32-range")

    kes_rs = np.ascontiguousarray(vc.kes_sig[:, :64])
    kt_rows, kt_idx = _dedup_rows(vc.kes_sig[:, 64:])
    lo_rows, hi_rows = _uniq_threshold_rows(
        params, pre, 512 if two else 256)
    rows = [np.concatenate([lo, hi]) for lo, hi in zip(lo_rows, hi_rows)]
    kt_n, thr_n = _table_rows(params, ledger_view, slot, kt_rows.shape[0],
                              len(rows))
    kt_tab = np.zeros((kt_n, sig_len - 64), np.uint8)
    kt_tab[: kt_rows.shape[0]] = kt_rows
    kt_tab[kt_rows.shape[0] :] = kt_tab[0]

    thr_tab = np.zeros((thr_n, rows[0].shape[0]), np.uint8)
    thr_tab[: len(rows)] = np.stack(rows)
    thr_tab[len(rows) :] = thr_tab[0]

    layout = PraosPackedLayout(
        int(body.shape[1]), depth, params.slots_per_kes_period,
        epoch_nonce is not None, plen, two,
    )
    packed = PraosPacked(
        body=np.ascontiguousarray(body),
        kes_rs=kes_rs,
        kes_tail_idx=kt_idx.astype(np.int32),
        kes_tail_tab=kt_tab,
        slot=slot.astype(np.int32),
        counter=counter.astype(np.int32),
        c0=c0.astype(np.int32),
        thr_idx=pre.uniq_inv.astype(np.int32),
        thr_tab=thr_tab,
        nonce=np.frombuffer(epoch_nonce or bytes(32), np.uint8),
        body_layout=lane_layout,
        body_tab=body_tab,
    )
    if two:
        packed = TPraosPacked(
            *packed, overlay=np.asarray(pre.overlay).astype(np.int32))
    return layout, packed


def pad_packed_to(packed: PraosPacked, size: int) -> PraosPacked:
    """Pad the per-lane columns up to `size` by replicating lane 0
    (window tables and the nonce are shared, not padded). Same jit-cache
    rationale as pad_batch_to."""
    b = packed[0].shape[0]  # the body (a Byron window's: signed) column
    if b == size:
        return packed

    def _pad(x):
        return np.concatenate([x, np.repeat(x[:1], size - b, axis=0)], axis=0)

    return packed._replace(**{
        f: _pad(getattr(packed, f)) for f in packed._fields
        if f not in ("kes_tail_tab", "thr_tab", "nonce", "body_tab")
    })


def _be8(x):
    """[B] int32 (< 2^31) -> [B, 8] uint8 big-endian, as int.to_bytes(8)."""
    from ..ops import bigint as bi

    return bi.be8_rows(x).astype(jnp.uint8)


# mkSeed's universal constants (cardano-protocol-tpraos BHeader.hs
# `seedEta` / `seedL` = mkNonceFromNumber 0 / 1 = Blake2b-256 of the
# number's 8 big-endian bytes): a TPraos header's two VRF inputs are the
# slot-and-nonce hash XORed with one each
SEED_ETA = nonces.mk_input_vrf(0, None)
SEED_L = nonces.mk_input_vrf(1, None)


def unpack_packed(
    layout: PraosPackedLayout,
    body, kes_rs, kes_tail_idx, kes_tail_tab, slot, counter, c0,
    thr_idx, thr_tab, nonce, body_layout, body_tab, overlay=None,
):
    """The device-side unpack: packed columns -> the 21 staged columns
    in flatten_batch order, byte-identical to what `stage` builds on the
    host (the packed round-trip property, tests/test_packed_batch.py).
    Runs inside the jit — limb decomposition for the pk path continues
    through ops/pk/kernels.staged_to_limb_first on these outputs.

    Each lane's fields are cut at its own body layout's offsets (its row
    of `body_tab`): a slice at the window's least offset of the field,
    then shifted left lane by lane, one select for each bit of the
    lane's excess over it; the KES message is padded at each lane's own
    body length. The program depends on no offset.

    A two-certificate (TPraos) layout yields 27: the second proof's
    (gamma, c, s, alpha) behind the first's, both declared outputs, the
    64-byte threshold rows and the overlay column
    (`kernels.staged_to_limb_first_tp`)."""
    body = jnp.asarray(body).astype(jnp.uint8)
    bsz = body.shape[0]
    # [B, len(BODY_TAB_COLS)]: each lane's layout
    offs = jnp.take(jnp.asarray(body_tab).astype(jnp.int32),
                    jnp.asarray(body_layout), axis=0)
    spare = _MAX_BODY_SHIFT - 1
    padded = jnp.pad(body, ((0, 0), (0, spare)))

    def _slice(field, n):
        o = offs[:, BODY_TAB_COLS.index(field)]
        base = jnp.min(o)
        x = jax.lax.dynamic_slice_in_dim(padded, base, n + spare, axis=1)
        d = (o - base)[:, None]
        for bit in range(spare.bit_length()):
            w = x.shape[1] - (1 << bit)
            x = jnp.where((d >> bit) & 1 == 1, x[:, x.shape[1] - w :],
                          x[:, :w])
        return x

    issuer = _slice("issuer", 32)
    vrf_vk = _slice("vrf_vk", 32)
    beta = _slice("vrf_out", 64)
    bc = layout.vrf_proof_len == 128
    proof = _slice("vrf_proof", layout.vrf_proof_len)
    if bc:  # gamma ‖ u ‖ v ‖ s announced-points format
        gamma, vrf_u, vrf_v, vrf_s = (
            proof[:, :32], proof[:, 32:64], proof[:, 64:96], proof[:, 96:]
        )
    else:
        gamma, vrf_c, vrf_s = proof[:, :32], proof[:, 32:48], proof[:, 48:]
    vk_hot = _slice("vk_hot", 32)
    sigma = _slice("sigma", 64)
    ed_r, ed_s = sigma[:, :32], sigma[:, 32:]

    kes_rs = jnp.asarray(kes_rs).astype(jnp.uint8)
    kes_r, kes_s = kes_rs[:, :32], kes_rs[:, 32:]
    tail = jnp.take(
        jnp.asarray(kes_tail_tab).astype(jnp.uint8),
        jnp.asarray(kes_tail_idx), axis=0,
    )
    vk_leaf = tail[:, :32]
    siblings = tail[:, 32:].reshape(bsz, layout.kes_depth, 32)

    thr = jnp.take(
        jnp.asarray(thr_tab).astype(jnp.uint8), jnp.asarray(thr_idx), axis=0
    )
    tw = thr.shape[1] // 2  # 32, or 64 under the 512-bit leader rule
    thr_lo, thr_hi = thr[:, :tw], thr[:, tw:]

    slot = jnp.asarray(slot).astype(jnp.int32)
    counter = jnp.asarray(counter).astype(jnp.int32)
    c0 = jnp.asarray(c0).astype(jnp.int32)

    # OCert DSIGN message: R ‖ A ‖ (vk_hot ‖ counter_be8 ‖ period_be8)
    ed_msg = jnp.concatenate(
        [ed_r, issuer, vk_hot, _be8(counter), _be8(c0)], axis=-1
    )
    ed_hb, ed_hnb = ed25519_batch.build_hblocks(
        ed_msg[:, :32], ed_msg[:, 32:64], ed_msg[:, 64:]
    )
    kes_hb, kes_hnb = kes_batch.build_hblocks(kes_r, vk_leaf, body,
                                              offs[:, 0])

    alpha = ecvrf_batch.alpha_from_slots(
        slot, nonce if layout.has_nonce else None
    ).astype(jnp.uint8)

    # evolution index t = kes_period_of(slot) - c0; window-check-failing
    # lanes get an out-of-range t (vs the host's clamped 0) — don't-care
    # lanes, masked by the precheck error that precedes the KES verdict
    # in the reference's error order
    period = slot // layout.slots_per_kes - c0

    if layout.proofs == 2:
        # mkSeed(uc, slot, eta0) = H(be8(slot) ‖ eta0) XOR uc: one hash a
        # lane serves both inputs
        seed_e = jnp.asarray(np.frombuffer(SEED_ETA, np.uint8))
        seed_l = jnp.asarray(np.frombuffer(SEED_L, np.uint8))
        beta_l = _slice("vrf_leader_out", 64)
        proof_l = _slice("vrf_leader_proof", 80)
        return (
            issuer, ed_r, ed_s, ed_hb, ed_hnb,
            vk_hot, period, kes_r, kes_s, vk_leaf, siblings, kes_hb, kes_hnb,
            vrf_vk, gamma, vrf_c, vrf_s, alpha ^ seed_e,
            proof_l[:, :32], proof_l[:, 32:48], proof_l[:, 48:],
            alpha ^ seed_l,
            beta, beta_l, thr_lo, thr_hi,
            jnp.asarray(overlay).astype(jnp.int32),
        )
    if bc:
        return (
            issuer, ed_r, ed_s, ed_hb, ed_hnb,
            vk_hot, period, kes_r, kes_s, vk_leaf, siblings, kes_hb,
            kes_hnb,
            vrf_vk, gamma, vrf_u, vrf_v, vrf_s, alpha,
            beta, thr_lo, thr_hi,
        )
    return (
        issuer, ed_r, ed_s, ed_hb, ed_hnb,
        vk_hot, period, kes_r, kes_s, vk_leaf, siblings, kes_hb, kes_hnb,
        vrf_vk, gamma, vrf_c, vrf_s, alpha,
        beta, thr_lo, thr_hi,
    )


def _pack_bits_u32(bits):
    """[B] bool -> [ceil(B/32)] uint32; lane i -> word i//32, bit i%32
    (host unpack: protocol/batch._mask_bits)."""
    b = bits.shape[0]
    w = -(-b // 32)
    x = bits.astype(jnp.uint32)
    if w * 32 > b:
        x = jnp.concatenate([x, jnp.zeros((w * 32 - b,), jnp.uint32)])
    return (x.reshape(w, 32) << jnp.arange(32, dtype=jnp.uint32)).sum(
        axis=1, dtype=jnp.uint32
    )


def _mask_bits(words: np.ndarray, b: int) -> np.ndarray:
    """Host inverse of _pack_bits_u32: [W] uint32 -> [b] bool."""
    bits = np.unpackbits(
        np.ascontiguousarray(words).view(np.uint8), bitorder="little"
    )
    return bits[:b].astype(bool)


def _verdict_masks(flags):
    """[5, B] int32 verdict rows -> [5, W] uint32 bitmask words."""
    return jnp.stack([_pack_bits_u32(flags[i] != 0) for i in range(5)])


def verdict_pack(flags, eta_bt):
    """On-device D2H reduction of every packed dispatch: the five
    verdict bit rows packed into u32 bitmask words, and the eta column
    as uint8, so materialize transfers O(bits + 32 B a lane) instead of
    O(lanes x 40 B of int32). No loop and no carry: the evolving /
    candidate nonce fold is a hash chain, and the host folds it in the
    retire path (`_epilogue_columns_fast` / `_epilogue_packed_fast`).

      flags [5, B] int32 — rows ok_ocert_sig, ok_kes_sig, ok_vrf,
        ok_leader, leader_ambiguous; eta_bt [B, 32] int32.

    -> (masks [5, W] uint32, eta_u8 [B, 32] uint8)
    """
    return _verdict_masks(flags), eta_bt.astype(jnp.uint8)


def verdict_reduce(flags, eta_bt, within, n_real, ev0, ev0_set, cand0,
                   cand0_set):
    """REFERENCE ONLY — no dispatch path calls this. The round-6 `reduce`
    program: the verdict bitmasks plus the window's evolving/candidate
    nonce fold ON DEVICE (ops/blake2b.nonce_fold_scan), one unbatched
    Blake2b compression a lane: 3.3 s a window of 8192 lanes on a v5e
    where the host folds the same column in 12 ms (PERF.md, PR 29). Kept
    as what analysis/graphs._graph_verdict_reduce traces (goldens in
    analysis/{budgets,certified,costmodel,shapes}.json) and what
    tests/test_packed_batch.py holds equal to the host fold; ROADMAP
    queues its deletion together with those goldens.

      within [B]; n_real [] int32 (true window size before bucket pad);
      ev0/cand0 [32] int32 + ev0_set/cand0_set [] bool — the carry-in.

    -> (masks [5, W] uint32, ev, ev_set, cand, cand_set)
    """
    b = flags.shape[-1]
    masks = _verdict_masks(flags)
    is_real = jnp.arange(b, dtype=jnp.int32) < n_real
    ev, evs, cand, cands = blake2b.nonce_fold_scan(
        eta_bt.astype(jnp.int32),
        jnp.asarray(within) != 0,
        is_real,
        jnp.asarray(ev0).astype(jnp.int32),
        jnp.asarray(ev0_set).astype(bool).reshape(()),
        jnp.asarray(cand0).astype(jnp.int32),
        jnp.asarray(cand0_set).astype(bool).reshape(()),
    )
    return masks, ev, evs, cand, cands


def _packed_xla_fn(layout: PraosPackedLayout):
    """The RAW (un-jitted) XLA-twin packed program of one layout:
    unpack -> fused verify -> pack."""

    if layout.proofs == 0:  # a Byron window: the `ed` check alone
        from . import pbft

        def fn_pbft(*packed):
            flags, eta = pbft.verify_xla(layout, *packed)
            return (verdict_pack(flags, eta), flags, eta, eta)

        return fn_pbft

    def fn(*packed):
        cols = unpack_packed(layout, *packed)
        two = layout.proofs == 2
        v = verify_tpraos(*cols) if two else verify_praos_any(*cols)
        flags = jnp.stack(
            [v.ok_ocert_sig, v.ok_kes_sig, v.ok_vrf, v.ok_leader,
             v.leader_ambiguous]
        ).astype(jnp.int32)
        out = (verdict_pack(flags, v.eta), flags, v.eta, v.leader_value)
        if not two:
            return out
        # TPraos: which proof failed, for the error's name ([2, B], as
        # `finish_tp` ships it: the nonce proof, the leader proof)
        return (*out, jnp.stack([v.ok_vrf_nonce, v.ok_vrf]
                                ).astype(jnp.int32))

    return fn


def _jitted_packed_xla(layout: PraosPackedLayout):
    """The XLA-twin packed program, one jit per layout."""
    import jax

    key = ("xla-packed", layout)
    if key not in _JIT:
        _JIT[key] = _warm_timed(
            f"xla-packed:{layout.body_len}b:p{layout.vrf_proof_len}"
            + ("x2" if layout.proofs == 2 else ""),
            jax.jit(_packed_xla_fn(layout)),
        )
    return _JIT[key]


def _jitted_packed_agg(layout: PraosPackedLayout, mode: str = "all"):
    """The AGGREGATED packed program (batch-compatible layouts only):
    device unpack -> limb relayout -> the window aggregate ->
    verdict_pack. `mode` selects the aggregate:

      "all" — ops/pk/aggregate.aggregate_window, EVERY stage folded
              into one shared-bucket signed-digit MSM (the default;
              label family "agg-packed");
      "vrf" — aggregate_window_vrf, exact per-lane ed/kes ladders with
              only the VRF equations aggregated on the unsigned engine
              (the OCT_RLC_ALL=0 kill-switch; label family "agg-vrf").

    One jit per (layout, mode); identical output vocabulary to
    the per-lane packed programs, with the aggregate verdict folded
    into the ok mask rows — a window that is not clean under
    aggregation is re-dispatched through the UNCHANGED per-lane stages
    by materialize_verdicts. The `_warm_timed` wrap gives both mode
    families first-execute attribution AND build-pinned AOT store
    coverage (load / write-back) under their label-derived store
    names."""
    import jax

    key = ("agg-packed", layout, mode)
    if key not in _JIT:
        _JIT[key] = _warm_timed(
            f"{_AGG_STAGE_FAMILY[mode]}:{layout.body_len}b",
            jax.jit(_packed_agg_fn(layout, mode)),
        )
    return _JIT[key]


def _packed_agg_fn(layout: PraosPackedLayout, mode: str = "all"):
    """The RAW (un-jitted) aggregated stage program for (layout, mode)
    — the function the jit builder above wraps, exposed so
    scripts/aot_precompile.py can trace/lower/compile the SAME program
    into the build-pinned store under its `_store_name(label)` row
    (the first execute then loads instead of compiling)."""
    from ..ops.pk import aggregate as pk_aggregate
    from ..ops.pk import kernels as pk_kernels

    agg_fn = (pk_aggregate.aggregate_window if mode == "all"
              else pk_aggregate.aggregate_window_vrf)

    def fn(body, kes_rs, kt_idx, kt_tab, slot, counter, c0,
           thr_idx, thr_tab, nonce, body_layout, body_tab):
        cols = unpack_packed(
            layout, body, kes_rs, kt_idx, kt_tab, slot, counter, c0,
            thr_idx, thr_tab, nonce, body_layout, body_tab,
        )
        limb = pk_kernels.staged_to_limb_first_bc(*cols)
        av = agg_fn(*limb, kes_depth=layout.kes_depth)
        red = verdict_pack(av.flags, jnp.transpose(av.eta))
        return red, av.flags, av.eta, av.leader_value

    return fn


# warmup label families of the two aggregate modes (the family prefix
# is what analysis/costmodel.STAGE_GRAPHS keys on)
_AGG_STAGE_FAMILY = {"all": "agg-packed", "vrf": "agg-vrf"}


def _jitted_pk(kes_depth: int, bc: bool = False):
    import functools
    import os

    import jax

    key = ("pk", kes_depth, bc)
    if key not in _JIT:
        from ..ops.pk import kernels as pk_kernels

        if os.environ.get("OCT_PK_FUSED") and not bc:
            # the original single-jit composition (one cache entry for
            # the whole program) — opt-in for A/B measurement
            _JIT[key] = jax.jit(
                functools.partial(
                    pk_kernels.verify_praos_staged, kes_depth=kes_depth
                )
            )
        else:
            # default: per-stage jits (kernels.verify_praos_split) — a
            # wedged compile costs one stage and the persistent cache
            # accumulates stage entries across retries (VERDICT r3 #2)
            fn = (pk_kernels.verify_praos_split_bc if bc
                  else pk_kernels.verify_praos_split)
            _JIT[key] = functools.partial(fn, kes_depth=kes_depth)
    return _JIT[key]


def _pk_dispatch(batch: PraosBatch):
    """Dispatch the Pallas path (async); -> opaque handle. The staged
    [B, ...] uint8 columns go straight to the jit — transposes and the
    byte expansion run in XLA (pk_arrays on host cost ~20 us/header)."""
    depth = batch.kes.siblings.shape[-2]
    ed, kes, vrf = batch.ed, batch.kes, batch.vrf
    out = _jitted_pk(depth, batch_is_bc(batch))(
        ed.pk, ed.r, ed.s, ed.hblocks, ed.hnblocks,
        kes.vk, kes.period, kes.r, kes.s, kes.vk_leaf, kes.siblings,
        kes.hblocks, kes.hnblocks,
        *batch.vrf,
        batch.beta, batch.thr_lo, batch.thr_hi,
    )
    return out


def _pk_materialize(out, b: int) -> Verdicts:
    flags, eta, lv = (np.asarray(x) for x in out)
    return Verdicts(
        ok_ocert_sig=flags[0, :b] != 0,
        ok_kes_sig=flags[1, :b] != 0,
        ok_vrf=flags[2, :b] != 0,
        ok_leader=flags[3, :b] != 0,
        leader_ambiguous=flags[4, :b] != 0,
        eta=np.ascontiguousarray(eta[:, :b].T),
        leader_value=np.ascontiguousarray(lv[:, :b].T),
    )


def pad_batch_to(batch: PraosBatch, size: int) -> PraosBatch:
    """Pad every column's batch dim up to `size` by replicating lane 0
    (guaranteed-decodable inputs; callers slice verdicts back to the true
    size). Keeps the jit cache bounded: one compilation per bucket shape
    instead of one per epoch-segment length."""
    b = batch.beta.shape[0]
    if b == size:
        return batch

    def _pad(x):
        x = np.asarray(x)
        return np.concatenate([x, np.repeat(x[:1], size - b, axis=0)], axis=0)

    def _pad_tuple(t):
        return type(t)(*(_pad(c) for c in t))

    return PraosBatch(
        ed=_pad_tuple(batch.ed),
        kes=_pad_tuple(batch.kes),
        vrf=_pad_tuple(batch.vrf),
        beta=_pad(batch.beta),
        thr_lo=_pad(batch.thr_lo),
        thr_hi=_pad(batch.thr_hi),
    )


def bucket_size(b: int, minimum: int = 8) -> int:
    """Shape bucket for a batch of b lanes: next power of two up to
    2048, then next multiple of 2048. Pure powers of two waste up to
    half the lanes on the epoch-tail batch (a ~21.6k-block epoch slices
    to 8192+8192+5216, and 5216 padded to 8192 is 36% dead work —
    ~14% of ALL device lanes at the 1M bench scale); 2048-granularity
    buckets cap tail padding at <2048 lanes while keeping the set of
    compiled shapes small (the remainder is epoch-size-distributed, so
    in practice one extra shape per chain)."""
    n = minimum
    while n < b and n < 2048:
        n *= 2
    if b <= n:
        return n
    return ((b + 2047) // 2048) * 2048


def _jitted_verify(bc: bool = False):
    import jax

    key = ("fn", bc)
    if key not in _JIT:
        _JIT[key] = _warm_timed(
            f"xla-fused{'-bc' if bc else ''}",
            jax.jit(verify_praos_bc if bc else verify_praos),
        )
    return _JIT[key]


def _lt_be_rows(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Vectorized big-endian lexicographic a < b per row, [n, 32] uint8
    (the host numpy twin of the device `_lt_be`)."""
    ne = a != b
    any_ne = ne.any(axis=1)
    first = ne.argmax(axis=1)
    rows = np.arange(a.shape[0])
    return any_ne & (a[rows, first] < b[rows, first])


def run_batch_native(
    params: PraosParams,
    ledger_view: LedgerView,
    epoch_nonce,
    hvs: "Sequence[HeaderView] | ViewColumns",
    pre: HostChecks,
) -> Verdicts:
    """Native (C++) crypto backend producing the same Verdicts shape as
    the device kernel — the honest single-core comparison path and the
    fallback when no accelerator is available (native/hostcrypto.cpp
    oc_validate_praos). Short-circuits at the first failing lane; lanes
    past it carry don't-care verdicts, which the sequential epilogue
    never reads.

    A ViewColumns window passes its matrices through untouched (no
    per-header np.stack) and runs the leader bracket as one vectorized
    byte compare against the per-pool threshold tables — the same
    clamped byte rows the device kernel compares against."""
    from .. import native_loader as nl

    n = len(hvs)
    if isinstance(hvs, ViewColumns):
        vc = hvs
        cold_vk = vc.vk_cold
        ocert_sig = vc.ocert_sigma
        ocert_msg = np.concatenate(
            [vc.ocert_vk_hot, _be8_np(vc.ocert_counter),
             _be8_np(vc.ocert_kes_period)], axis=1,
        )
        kes_vk = vc.ocert_vk_hot
        kes_sig = vc.kes_sig
        body, body_off = vc.body_spans()
        vrf_vk = vc.vrf_vk
        plen = int(vc.vrf_proof_len[0])
        vrf_proof = np.ascontiguousarray(vc.vrf_proof[:, :plen])
        vrf_alpha = _alpha_column(vc, epoch_nonce)
        vrf_output = vc.vrf_output
    else:
        cold_vk = np.stack([np.frombuffer(hv.vk_cold, np.uint8) for hv in hvs])
        ocert_sig = np.stack(
            [np.frombuffer(hv.ocert.sigma, np.uint8) for hv in hvs]
        )
        ocert_msg = np.stack(
            [np.frombuffer(hv.ocert.signable(), np.uint8) for hv in hvs]
        )
        kes_vk = np.stack(
            [np.frombuffer(hv.ocert.vk_hot, np.uint8) for hv in hvs]
        )
        kes_sig = np.stack([np.frombuffer(hv.kes_sig, np.uint8) for hv in hvs])
        body = b"".join(hv.signed_bytes for hv in hvs)
        body_off = np.zeros(n + 1, np.int64)
        np.cumsum([len(hv.signed_bytes) for hv in hvs], out=body_off[1:])
        vrf_vk = np.stack([np.frombuffer(hv.vrf_vk, np.uint8) for hv in hvs])
        vrf_proof = np.stack(
            [np.frombuffer(hv.vrf_proof, np.uint8) for hv in hvs]
        )
        vrf_alpha = np.stack(
            [
                np.frombuffer(nonces.mk_input_vrf(hv.slot, epoch_nonce), np.uint8)
                for hv in hvs
            ]
        )
        vrf_output = np.stack(
            [np.frombuffer(hv.vrf_output, np.uint8) for hv in hvs]
        )

    rc, kind, lv, eta = nl.native_validate_praos(
        cold_vk, ocert_sig, ocert_msg, kes_vk,
        pre.kes_evolution.astype(np.int64), kes_sig, params.kes_depth,
        body, body_off, vrf_vk, vrf_proof, vrf_alpha, vrf_output,
    )
    ok_ocert = np.ones(n, bool)
    ok_kes = np.ones(n, bool)
    ok_vrf = np.ones(n, bool)
    if rc >= 0:
        (ok_ocert if kind == 1 else ok_kes if kind == 2 else ok_vrf)[rc] = False

    stop = n if rc < 0 else rc
    if isinstance(hvs, ViewColumns) and isinstance(pre, ColumnChecks):
        # bracket compare vectorized against the per-pool byte tables
        # (Fraction math once per unique pool; ambiguous lanes still go
        # to the exact host check in _lane_error)
        thr_lo, thr_hi = _uniq_threshold_tables(params, pre)
        win = _lt_be_rows(lv, thr_lo)
        amb = ~win & _lt_be_rows(lv, thr_hi)
        live = np.arange(n) < stop
        ok_leader = win & live
        ambiguous = amb & live
    else:
        # leader threshold: bracket compare exactly as the device kernel
        f = params.active_slot_coeff
        ok_leader = np.zeros(n, bool)
        ambiguous = np.zeros(n, bool)
        for i in range(stop):
            hv = hvs[i]
            entry = ledger_view.pool_distr.get(hash_key(hv.vk_cold))
            sigma = entry.stake if entry is not None else Fraction(0)
            lo, hi = leader_threshold_bracket(Fraction(sigma), Fraction(f))
            lv_int = int.from_bytes(lv[i].tobytes(), "big")
            ok_leader[i] = lv_int < lo
            ambiguous[i] = not ok_leader[i] and lv_int < hi
    return Verdicts(ok_ocert, ok_kes, ok_vrf, ok_leader, ambiguous, eta, lv)


def run_batch(batch: PraosBatch) -> Verdicts:
    """Stage -> device -> host verdict arrays (numpy).

    Batches are padded to power-of-two buckets so jax's per-shape trace
    cache compiles once per (bucket, kes_depth) — the crypto graph is
    large and arbitrary-length recompiles would dominate wall-clock.
    """
    b = batch.beta.shape[0]
    padded = pad_batch_to(batch, bucket_size(b))
    if _impl() == "pk":
        return _pk_materialize(_pk_dispatch(padded), b)
    out = _jitted_verify(batch_is_bc(padded))(
        *(jnp.asarray(x) for x in flatten_batch(padded))
    )
    return Verdicts(*(np.asarray(x)[:b] for x in out))


# ---------------------------------------------------------------------------
# Batched chain-position semantics (first failure + state fold)
# ---------------------------------------------------------------------------


@dataclass
class BatchResult:
    """Outcome of validating a within-epoch run of headers."""

    state: PraosState  # state after the last VALID prefix header
    n_valid: int  # length of the valid prefix
    error: praos.PraosValidationError | None  # error at position n_valid
    states: list | None = None  # per-position states (collect_states=True)
    # a stream over eras (hardfork/cardano): the era `state` is in, and
    # at each crossing (from era, to era, the state before the
    # translation, the state after it)
    era: int = 0
    crossings: list | None = None


class EraPiece:
    """A run of one era's headers in chain order (ViewColumns, a list of
    HeaderViews or pbft.ByronColumns) tagged with the era's index: what
    the stream of a chain over eras (hardfork/cardano) hands
    `validate_stream`."""

    __slots__ = ("era", "cols")

    def __init__(self, era: int, cols):
        self.era, self.cols = era, cols

    def __len__(self) -> int:
        return len(self.cols)

    def __getitem__(self, i: slice) -> "EraPiece":
        return EraPiece(self.era, self.cols[i])


def _counter_m(hk, counters, pool_distr):
    """The stateful OCert counter baseline: last seen counter, else 0
    for a pool with stake, else None (NoCounterForKeyHash)."""
    m = counters.get(hk)
    if m is None and hk in pool_distr:
        m = 0
    return m


def _counter_ok(m, n) -> bool:
    """Praos.hs:585-590: m <= n <= m + 1."""
    return m is not None and m <= n <= m + 1


def _lane_error(
    params: PraosParams,
    ledger_view: LedgerView,
    epoch_nonce: nonces.Nonce,
    hv: HeaderView,
    pre: HostChecks,
    v: Verdicts,
    i: int,
    counters: Mapping[bytes, int],
) -> praos.PraosValidationError | None:
    """Map verdict bitmaps back to the EXACT error the sequential
    reference fold would raise, in its order: the whole of
    validateKESSignature (window, OCert sig, KES sig, counters —
    Praos.hs:558-606) before any of validateVRFSignature (pool lookup,
    proof, leader threshold — Praos.hs:528-556)."""
    if pre.kes_window_errors[i] is not None:
        return pre.kes_window_errors[i]
    if not v.ok_ocert_sig[i]:
        return praos.InvalidSignatureOCERT(hv.ocert.counter, hv.ocert.kes_period)
    if not v.ok_kes_sig[i]:
        kp = params.kes_period_of(hv.slot)
        c0 = hv.ocert.kes_period
        return praos.InvalidKesSignatureOCERT(kp, c0, kp - c0)
    # ocert counter monotonicity (Praos.hs:585-590), stateful
    hk = hash_key(hv.vk_cold)
    m = _counter_m(hk, counters, ledger_view.pool_distr)
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
        return praos.VRFKeyBadProof(hv.slot, epoch_nonce)
    if not v.leader_ambiguous[i] and v.ok_leader[i]:
        return None  # the common path: no big-int reconstruction
    entry = ledger_view.pool_distr.get(hk)
    sigma = entry.stake if entry is not None else Fraction(0)
    lv_val = int.from_bytes(bytes(v.leader_value[i].astype(np.uint8)), "big")
    if v.leader_ambiguous[i] and leader.check_leader_value(
        lv_val, sigma, params.active_slot_coeff
    ):
        return None
    return praos.VRFLeaderValueTooBig(lv_val, sigma, params.active_slot_coeff)


def _proof_len_uniform(hvs) -> bool:
    if isinstance(hvs, ViewColumns):
        pl = hvs.vrf_proof_len
        return bool((pl == pl[0]).all())
    return len({len(hv.vrf_proof) for hv in hvs}) <= 1


def _proof_len_at(hvs, i: int) -> int:
    if isinstance(hvs, ViewColumns):
        return int(hvs.vrf_proof_len[i])
    return len(hvs[i].vrf_proof)


def _columnar(hvs) -> bool:
    """A window of columns (ViewColumns, pbft.ByronColumns), not a list
    of views."""
    return not isinstance(hvs, (list, tuple))


def _slot_at(hvs, i: int) -> int:
    if _columnar(hvs):
        return int(hvs.slot[i])
    return hvs[i].slot


def validate_batch(
    params: PraosParams,
    ticked: TickedPraosState,
    hvs: "Sequence[HeaderView] | ViewColumns",
    collect_states: bool = False,
    backend: str = "device",
    mesh=None,  # backend="sharded": the jax.sharding.Mesh (None = all devices)
) -> BatchResult:
    """Validate a within-epoch run of headers as one batch.

    Equivalent to folding `praos.update` over `hvs` from `ticked` — same
    resulting state, same first error — but with all crypto executed as a
    single fused device program (backend="device") or through the C++
    verifier (backend="native"). The epoch nonce must be constant across
    the run (the caller segments at epoch boundaries; `tick` between
    segments).

    `hvs` may be a ViewColumns window: prechecks, staging and the
    all-clean epilogue then run columnar (no per-header objects);
    HeaderViews materialize only for anomaly lanes.
    """
    if not len(hvs):
        return BatchResult(ticked.state, 0, None, [] if collect_states else None)
    lview = ticked.ledger_view
    eta0 = ticked.state.epoch_nonce
    rules = rules_of(params)
    runs = rules.runs(hvs)
    if len(runs) == 1 and not _proof_len_uniform(runs[0]):
        # a run mixing 80- and 128-byte proofs cannot stage as one
        # uniform proof column; segment at format boundaries — the
        # reference fold length-dispatches per header, and segmentation
        # never changes per-lane verdicts or the first error
        hvs, runs, i = runs[0], [], 0
        while i < len(hvs):
            j = _proof_break(hvs, i, len(hvs))
            runs.append(hvs[i:j])
            i = j
    if len(runs) > 1:
        states = [] if collect_states else None
        total = 0
        for k, run in enumerate(runs):
            if k:
                ticked = praos.tick(params, lview, _slot_at(run, 0),
                                    res.state)
            res = validate_batch(
                params, ticked, run, collect_states, backend, mesh
            )
            total += res.n_valid
            if collect_states:
                states.extend(res.states or [])
            if res.error is not None:
                break
        return BatchResult(res.state, total, res.error, states)
    hvs = runs[0]

    if backend == "device" and rules.packed_only:
        # no un-packed device path for this protocol: the one window
        # through the staged dispatch the replay's windows take
        pre, out, b = dispatch_batch(params, lview, eta0, hvs)
        return _epilogue(params, ticked, hvs, pre,
                         materialize_verdicts(out, b), collect_states)
    pre = rules.prechecks(params, lview, hvs)
    if backend == "native":
        v = rules.run_native(params, lview, eta0, hvs, pre)
    elif backend == "sharded":
        # multi-chip SPMD: batch axis over the device mesh, psum/pmin
        # verdict collectives (parallel/spmd.py; SURVEY.md §5.8)
        v = rules.run_sharded(params, lview, eta0, hvs, pre, mesh)
    else:
        batch = stage_any(params, lview, eta0, hvs, pre)
        v = run_batch(batch)
    return _epilogue(params, ticked, hvs, pre, v, collect_states)


def stage_any(
    params: PraosParams,
    ledger_view: LedgerView,
    epoch_nonce,
    hvs: "Sequence[HeaderView] | ViewColumns",
    pre: HostChecks,
) -> PraosBatch:
    """Stage whichever window representation arrives: ViewColumns go
    through the columnar stage; HeaderView lists through the classic
    per-view stage (also the lazy fallback for columnar windows that
    cannot stage columnar, e.g. non-int32 slots)."""
    if isinstance(hvs, ViewColumns) and isinstance(pre, ColumnChecks):
        return stage_columns(
            params, ledger_view, epoch_nonce, hvs, pre.kes_evolution, pre
        )
    if isinstance(hvs, ViewColumns):
        hvs = hvs.views()
    return stage(params, ledger_view, epoch_nonce, hvs, pre.kes_evolution)


# Enclose latency brackets (Util/Enclose.hs) around the hot-path
# phases: stage (host CBOR->SoA), dispatch (device kernel launch),
# materialize (device wait), epilogue (sequential fold). Settable so
# the embedding application (bench, node, tests) observes per-phase
# latency without touching the code path. The span vocabulary (label,
# thread, parent, fields) is obs/README.md's table.
BATCH_TRACER = None  # None = off (zero overhead on the hot path)


def set_batch_tracer(tracer) -> None:
    """Install `tracer` (None: none). While one is installed, each
    collection of the interpreter inside a replay is a span `gc`
    (`_GC_SPANS`)."""
    global BATCH_TRACER
    _GC_SPANS.flush(BATCH_TRACER)
    BATCH_TRACER = tracer
    _GC_SPANS.hook(tracer is not None)


# the collections of the interpreter inside a replay, handed to the
# tracer at its next span or change
_GC_SPANS = GcSpans(lambda: _REPLAY if BATCH_TRACER is not None else None)


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()

# identifiers the spans of one replay / one window share. A replay's is
# allotted by db_analyser.revalidate (`begin_replay`), a window's when
# the window is enqueued for staging (`next_window_id`).
_REPLAY_IDS = itertools.count()
_WINDOW_IDS = itertools.count()
_REPLAY: int | None = None  # the replay in progress (None outside one)


def begin_replay() -> int:
    global _REPLAY
    _REPLAY = next(_REPLAY_IDS)
    return _REPLAY


def end_replay() -> None:
    global _REPLAY
    _REPLAY = None


def next_window_id() -> int:
    return next(_WINDOW_IDS)


def _enclose(label, window=None, parent=None):
    """The span `label` of the replay in progress; `parent` names the
    causing span where none encloses it on the emitting thread."""
    if BATCH_TRACER is None:
        return _NULL
    if _GC_SPANS.pending:
        _GC_SPANS.flush(BATCH_TRACER)
    from ..utils.trace import Enclose

    return Enclose(BATCH_TRACER, label, _REPLAY, window, parent)


class _FailedDispatch:
    """In-flight placeholder for a window whose staging or dispatch
    raised a RECOVERABLE error (obs/recovery): the exception is
    re-raised at the window's retire slot, where the supervisor has the
    exact fold state (`ticked`) a re-validation needs — so recovery
    happens in retire order and the pipeline's windows never reorder."""

    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc

    def result(self):
        raise self.exc


class _WinMeta(NamedTuple):
    """A window's telemetry between dispatch and retire (None while no
    tracer is installed)."""

    index: int
    outcome: str
    gate: str | None
    stage_s: float
    dispatch_s: float
    lanes_padded: int
    t_dispatch: float
    t_stage_start: float
    t_stage_end: float
    t_dispatch_start: float
    stage_thread: str
    tiles_live: int  # WindowSpan.tiles_live
    stage_wait_s: float = 0.0
    census: "_StageCensus | None" = None
    proofs: int = 1  # VRF proofs the device verifies a lane
    dispatch_offcpu_s: float = 0.0  # WindowSpan.dispatch_offcpu_s
    stage_offcpu_s: float = 0.0  # WindowSpan.stage_offcpu_s


class _Dispatched(NamedTuple):
    """Opaque handle between dispatch_batch and materialize_verdicts."""

    impl: str  # "pk" | "xla"
    packed: bool
    out: tuple  # impl-specific device handles
    meta: _WinMeta | None = None


def _nbytes(arrays) -> int:
    return int(sum(np.asarray(a).nbytes for a in arrays))


def _emit_transfer(phase: str, **kw) -> None:
    if BATCH_TRACER is not None:
        from ..utils.trace import TransferEvent

        BATCH_TRACER(TransferEvent(phase=phase, **kw))


def _win_meta(outcome: str, gate: str | None, sw: "_StagedWindow",
              t_d0: float, c_d0: float, tiles_live: int = 0,
              proofs: int = 1) -> _WinMeta | None:
    """Build the per-window telemetry meta and emit the WindowStaged
    event. Returns None (zero residual cost) when no tracer is set.
    `c_d0`: the thread's CPU clock where `t_d0` was read.
    `tiles_live`: the count the window's stage kernels were bounded by
    (`_dispatch_packed_lanes`), 0 where its live lanes bounded none."""
    if BATCH_TRACER is None:
        return None
    from ..utils.trace import WindowStaged

    c2 = time.thread_time()
    t2 = time.monotonic()
    stage_s, dispatch_s = sw.t1 - sw.t0, t2 - t_d0
    BATCH_TRACER(WindowStaged(sw.window, sw.b, sw.lanes, outcome, gate,
                              stage_s, dispatch_s))
    return _WinMeta(sw.window, outcome, gate, stage_s, dispatch_s,
                    sw.lanes, t2, sw.t0, sw.t1, t_d0, sw.thread,
                    tiles_live, census=sw.census, proofs=proofs,
                    dispatch_offcpu_s=dispatch_s - (c2 - c_d0),
                    stage_offcpu_s=stage_s - sw.cpu_s)


def _emit_window_span(meta, lanes: int, n_valid: int, failed: bool,
                      t_m0: float, t_m1: float, t_e0: float,
                      t_done: float, inflight_behind: int,
                      staged_ahead: int) -> None:
    """Emit the retired-window span (dispatch_batch meta + the
    materialize/tick/epilogue walls and the pipeline's fill measured in
    the validate_chain loop)."""
    if BATCH_TRACER is None or meta is None:
        return
    from ..utils.trace import WindowSpan

    BATCH_TRACER(WindowSpan(
        index=meta.index, lanes=lanes, outcome=meta.outcome,
        gate=meta.gate, stage_s=meta.stage_s, dispatch_s=meta.dispatch_s,
        materialize_s=t_m1 - t_m0, epilogue_s=t_done - t_e0,
        t_dispatch=meta.t_dispatch, t_materialized=t_m1, t_done=t_done,
        n_valid=n_valid, failed=failed,
        stage_wait_s=meta.stage_wait_s, tick_s=t_e0 - t_m1,
        inflight_behind=inflight_behind, staged_ahead=staged_ahead,
        t_stage_start=meta.t_stage_start, t_stage_end=meta.t_stage_end,
        t_dispatch_start=meta.t_dispatch_start,
        stage_thread=meta.stage_thread, tiles_live=meta.tiles_live,
        epilogue_counters_s=_COUNTERS_S[0],
        vrf_proofs=lanes * meta.proofs,
        pbft_s=_PBFT_S[0],
        dispatch_offcpu_s=meta.dispatch_offcpu_s,
        stage_offcpu_s=meta.stage_offcpu_s,
        **(meta.census._asdict() if meta.census is not None else {}),
    ))


class _StageCensus(NamedTuple):
    """Who a window holds, counted while it is staged (only with a
    tracer installed): what grows with the number of issuers, where a
    one-pool chain reads 1, 2-3, 1 (WindowSpan fields of these names)."""

    issuers: int  # distinct cold keys
    kes_tails: int  # rows of the KES tail table before padding
    prechecks_s: float  # span `stage.prechecks`
    overlay_lanes: int = 0  # TPraos: live lanes in active overlay slots
    overlay_s: float = 0.0  # TPraos: span `stage.overlay`
    pbft_lanes: int = 0  # PBFT: live regular (signed, non-EBB) lanes
    ebbs: int = 0  # PBFT: epoch boundary blocks among the live lanes
    layouts: int = 0  # distinct signed-body layouts (0: staged generic)


def _stage_census(hvs, pre, packed, prechecks_s: float) -> _StageCensus:
    layouts = 0 if packed is None else (
        # a Byron window signs one width; a packed Praos window's rows
        # of its layout table are each some lane's
        1 if hasattr(pre, "gk") else int(packed[1].body_layout.max()) + 1)
    if hasattr(pre, "gk"):  # a Byron window (protocol/pbft.PBftChecks)
        main = np.asarray(pre.main)
        return _StageCensus(len(set(pre.gk[main].tolist())), 0,
                            prechecks_s, pbft_lanes=int(main.sum()),
                            ebbs=int(main.size - main.sum()),
                            layouts=layouts)
    if isinstance(pre, ColumnChecks):
        issuers = len(set(pre.uniq_hk))
    else:
        issuers = len({hv.vk_cold for hv in hvs})
    kes_tails = 0
    if packed is not None:
        # every row of the dedup table is some lane's, so the largest
        # index names the last row (padding replicates lane 0's)
        kes_tails = int(packed[1].kes_tail_idx.max()) + 1
    census = _StageCensus(issuers, kes_tails, prechecks_s, layouts=layouts)
    if isinstance(pre, ColumnChecks) and pre.overlay is not None:
        census = census._replace(
            overlay_lanes=int(np.count_nonzero(pre.overlay)),
            overlay_s=pre.overlay_s)
    return census


class _StagedWindow(NamedTuple):
    """Output of `prepare_window` — everything `dispatch_prepared`
    needs, so staging can run on a producer thread ahead of dispatch
    (the round-10 threaded staging pipeline; the split is also what
    keeps the kill-switched path byte-identical: dispatch_batch is the
    two halves composed inline)."""

    pre: "HostChecks"
    packed: "tuple | None"  # (layout, padded PraosPacked) when packed
    padded: "PraosBatch | None"  # generic fallback, padded
    b: int
    lanes: int
    h2d: int
    gate: str | None
    t0: float
    t1: float
    window: int  # the window's id (`next_window_id`)
    thread: str  # the thread that staged it
    census: _StageCensus | None = None
    cpu_s: float = 0.0  # the thread's CPU time in t0..t1 (with a tracer)


def window_lanes(max_batch: int) -> int | None:
    """The ONE padded lane count of a replay on the `pk` implementation
    (the chip): every window pads to the caller's `max_batch` bucket,
    not to its own, so each stage is traced, lowered and compiled once
    per replay whatever the chain's short windows (a tip, an epoch
    tail) look like — each distinct lane count costs a full set
    of stage programs (minutes of set-up on a v5e) against seconds of
    device work. Padded lanes replicate lane 0 behind the live ones and
    are sliced off at materialize, so verdicts do not change; and the
    stage kernels are told how many lane tiles hold live lanes
    (`_dispatch_packed_lanes` -> `kernels.live_tiles`), so the device's
    time follows what a window holds: a one-header tip window is one
    tile of 64, an epoch tail of 5,216 headers 41. What a short window still pays at full width is the
    `unpack` and `reduce` programs (XLA over all lanes, 0.42 ms) and
    the padded columns' transfer.
    None on the XLA twin: windows keep their own `bucket_size`, so no
    CPU test compiles a shape it did not compile before."""
    return bucket_size(max_batch) if _impl() == "pk" else None


def prepare_window(params, lview, eta0, hvs, lanes: int | None = None,
                   window: int | None = None) -> _StagedWindow:
    """The HOST half of dispatch_batch: prechecks + packed/generic
    staging + bucket padding (to `lanes` when the caller fixes the lane
    count — `window_lanes` — else to the window's own bucket). Pure with
    respect to the sequential fold (depends only on the epoch nonce and
    ledger view), so a producer thread may run it arbitrarily far ahead
    of dispatch — the round-10 staging thread overlaps this wall with
    device compute and the retire-side epilogue work on the main
    thread. `window` is the id the caller allotted when it enqueued the
    window (staging order is dispatch order); without one it is
    allotted here."""
    from ..testing import chaos

    # the staging seam (chaos: staging-thread-death@window:N) — when the
    # producer thread runs this, the raise kills THAT thread's future
    # exactly like a real mid-prepare death; disarmed it is one module
    # bool test
    chaos.fire("stage")
    rules = rules_of(params)
    hvs = rules.window(hvs)
    b = len(hvs)
    size = bucket_size(b) if lanes is None or lanes < b else lanes
    if window is None:
        window = next_window_id()
    thread = threading.current_thread().name
    traced = BATCH_TRACER is not None
    t0 = time.monotonic()
    c0 = time.thread_time() if traced else 0.0
    with _enclose("stage", window):
        with _enclose("stage.prechecks"):
            pre = rules.prechecks(params, lview, hvs)
        t_pre = time.monotonic()
        packed = None
        gate = None
        if PACKED_STAGE and not os.environ.get("OCT_PK_FUSED"):
            packed, gate = rules.stage_packed(params, lview, eta0, hvs, pre)
        else:
            gate = "packed-off"
        census = (_stage_census(hvs, pre, packed, t_pre - t0)
                  if traced else None)
        if packed is None:
            if rules.packed_only:
                raise RuntimeError(
                    f"a {rules.name} window left the packed path "
                    f"({gate}): it has no other device path"
                )
            batch = stage_any(params, lview, eta0, hvs, pre)
            padded = pad_batch_to(batch, size)
            h2d = _nbytes(flatten_batch(padded))
            lanes = padded.beta.shape[0]
        else:
            layout, parr = packed
            packed = (layout, pad_packed_to(parr, size))
            padded = None
            h2d = _nbytes(packed[1])
            lanes = packed[1][0].shape[0]
    cpu_s = time.thread_time() - c0 if traced else 0.0
    return _StagedWindow(pre, packed, padded, b, lanes, h2d, gate, t0,
                         time.monotonic(), window, thread, census, cpu_s)


def dispatch_prepared(sw: _StagedWindow):
    """The DEVICE half of dispatch_batch: launch the fused kernel for a
    prepared window WITHOUT waiting (jax dispatch is asynchronous).
    Nothing chains from one dispatch to the next: a packed window
    returns its verdict bitmasks and its eta column, and the nonce fold
    is the host's, in retire order (`_epilogue`).

    Returns (pre, dispatched, b).
    """
    from ..testing import chaos

    # the dispatch seam (chaos: device-error@dispatch:N — a fake
    # XlaRuntimeError-class failure at window launch — and
    # compile-stall@window:N, a simulated compile wall)
    chaos.fire("dispatch")
    pre, b, lanes, gate = sw.pre, sw.b, sw.lanes, sw.gate
    t_d0 = time.monotonic()
    c_d0 = time.thread_time() if BATCH_TRACER is not None else 0.0
    with _enclose("dispatch", sw.window):
        _emit_transfer(
            "dispatch", lanes=lanes, h2d_bytes=sw.h2d,
            packed=sw.packed is not None, window=sw.window,
        )
        if sw.packed is None:
            padded = sw.padded
            if _impl() == "pk":
                out = _pk_dispatch(padded)
                impl = "pk"
            else:
                out = _jitted_verify(batch_is_bc(padded))(
                    *(jnp.asarray(x) for x in flatten_batch(padded))
                )
                impl = "xla"
            meta = _win_meta("generic", gate, sw, t_d0, c_d0)
            return pre, _Dispatched(impl, False, out, meta), b
        layout, parr = sw.packed
        if layout.vrf_proof_len == 128 and _agg_enabled():
            # the aggregated fast path: ONE RLC/MSM program instead of
            # the per-lane ladder stages (materialize_verdicts
            # re-dispatches per-lane on any anomaly)
            agg_mode = "all" if _rlc_all_enabled() else "vrf"
            out = _jitted_packed_agg(layout, agg_mode)(*parr)
            meta = _win_meta("packed-agg", None, sw, t_d0, c_d0)
            return pre, _Dispatched("agg", True, (layout, parr, out),
                                    meta), b
        impl, out, tiles_live = _dispatch_packed_lanes(layout, parr, b)
        meta = _win_meta("packed", None, sw, t_d0, c_d0, tiles_live,
                         layout.proofs)
        return pre, _Dispatched(impl, True, out, meta), b


def _dispatch_packed_lanes(layout, parr, live: int):
    """One packed window through the per-lane programs of the current
    implementation -> (impl, device handles, live tiles). Padding put
    the `live` lanes first (`pad_packed_to`), so the stage kernels run
    the tiles that hold them and no other; the XLA twin has no tiles
    (0)."""
    if _impl() == "pk":
        from ..ops.pk import kernels as pk_kernels

        tiles_live = pk_kernels.live_tiles(live)
        return "pk", pk_kernels.verify_praos_packed_split(
            layout, *parr, tiles_live=tiles_live
        ), tiles_live
    return "xla", _jitted_packed_xla(layout)(*parr), 0


def dispatch_batch(params, lview, eta0, hvs):
    """Stage a within-epoch window and dispatch the fused kernel WITHOUT
    waiting (the §7.3.6 host/device overlap; the reference's analog is
    the decoupled add-block queue, ChainSel.hs:217-246) — the inline
    composition of `prepare_window` + `dispatch_prepared`; the
    pipelined validate_chain loop calls the halves separately so a
    producer thread can stage ahead of dispatch."""
    return dispatch_prepared(prepare_window(params, lview, eta0, hvs))


class PackedVerdicts:
    """Materialized packed window result: the u32 verdict bitmasks and
    the uint8 eta column on host; the per-lane flags/eta/leader-value
    stay DEVICE-RESIDENT handles, transferred only by `full()` when the
    epilogue needs the exact per-lane slow path (a failing or ambiguous
    lane)."""

    def __init__(self, masks, b, impl, eta_u8, handles):
        self.masks = masks  # [5, W] uint32
        self.b = b
        self.impl = impl
        self.eta_u8 = eta_u8  # [b, 32] uint8
        self._handles = handles  # (flags, eta, lv) device arrays
        self._full = None

    def _row_all_set(self, row: int) -> bool:
        full, rem = divmod(self.b, 32)
        w = self.masks[row]
        if full and not bool((w[:full] == np.uint32(0xFFFFFFFF)).all()):
            return False
        if rem:
            m = np.uint32((1 << rem) - 1)
            if np.uint32(w[full] & m) != m:
                return False
        return True

    def _row_none_set(self, row: int) -> bool:
        full, rem = divmod(self.b, 32)
        w = self.masks[row]
        if full and bool(w[:full].any()):
            return False
        if rem and np.uint32(w[full] & np.uint32((1 << rem) - 1)):
            return False
        return True

    def clean(self) -> bool:
        """True iff every real lane passed every check outright: rows
        ok_ocert/ok_kes/ok_vrf/ok_leader all set, leader_ambiguous clear."""
        return all(self._row_all_set(r) for r in range(4)) and (
            self._row_none_set(4)
        )

    def full(self) -> Verdicts:
        """Transfer the per-lane arrays and rebuild the classic Verdicts
        (the slow-path contract of `_epilogue`/`_lane_error`)."""
        if self._full is None:
            flags, eta, lv, *aux = self._handles
            f = np.asarray(flags)
            b = self.b
            if self.impl == "pk":
                eta_np = np.ascontiguousarray(np.asarray(eta)[:, :b].T)
                lv_np = np.ascontiguousarray(np.asarray(lv)[:, :b].T)
            else:
                eta_np = np.asarray(eta)[:b]
                lv_np = np.asarray(lv)[:b]
            rows = [f[r, :b] != 0 for r in range(5)]
            if aux:  # a TPraos window: which proof held
                self._full = TPraosVerdicts(
                    *rows, eta_np, lv_np, np.asarray(aux[0])[0, :b] != 0)
            else:
                self._full = Verdicts(*rows, eta_np, lv_np)
        return self._full


def materialize_verdicts(tagged, b):
    """Block on a dispatched window's device computation.

    Generic windows transfer the full Verdicts (the round-5 contract);
    packed windows transfer the verdict bitmasks plus the uint8 eta
    column — O(bits + 32 B a lane) instead of O(lanes x 40 B of int32)
    — and keep the per-lane arrays device-resident for the slow path.

    Aggregated windows ("agg"): when the bitmasks show the window clean
    (every lane passed its cheap checks AND the RLC aggregate was the
    identity), the result is used as-is. On ANY anomaly the aggregate's
    per-lane flags are meaningless (a single bad lane zeroes the ok rows
    of EVERY lane), so the window is re-dispatched through the unchanged
    per-lane stage kernels here — exact reference error taxonomy and
    lane isolation, at the cost of one extra round trip on the rare
    dirty window."""
    window = tagged.meta.index if tagged.meta is not None else None
    if not tagged.packed:
        out = tagged.out
        d2h = int(sum(x.nbytes for x in out))
        if tagged.impl == "pk":
            v = _pk_materialize(out, b)
        else:
            v = Verdicts(*(np.asarray(x)[:b] for x in out))
        _emit_transfer("materialize", lanes=b, d2h_bytes=d2h, packed=False,
                       window=window)
        return v
    if tagged.impl == "agg":
        layout, parr, out = tagged.out
        pv = _materialize_packed(out, b, "pk", window)
        if pv.clean():
            return pv
        if BATCH_TRACER is not None:
            from ..utils.trace import AggRedispatch

            BATCH_TRACER(AggRedispatch(b))
        impl2, out2, _ = _dispatch_packed_lanes(layout, parr, b)
        return _materialize_packed(out2, b, impl2, window)
    return _materialize_packed(tagged.out, b, tagged.impl, window)


def _materialize_packed(out, b, impl, window=None):
    (masks_d, eta_d), *handles = out  # flags, eta, lv (+ TPraos's vrf_ok)
    # the wait for the device and the D2H copies as two spans: a device
    # still busy reads as `wait`, a transfer that holds the next window
    # back as `copy` (the copies below would block on them anyway)
    with _enclose("materialize.wait", window):
        jax.block_until_ready((masks_d, eta_d))
    with _enclose("materialize.copy", window):
        masks = np.asarray(masks_d)
        eta_all = np.asarray(eta_d)  # the padded column: what crosses
    pv = PackedVerdicts(masks, b, impl, eta_all[:b], tuple(handles))
    _emit_transfer("materialize", lanes=b, packed=True, window=window,
                   d2h_bytes=masks.nbytes + eta_all.nbytes)
    return pv


# wall of the last `epilogue.counters` span: the retire path clears it
# before `_epilogue` and reads it into the window's WindowSpan after
_COUNTERS_S = [0.0]
# and of the last `epilogue.pbft` span (protocol/pbft.epilogue writes it)
_PBFT_S = [0.0]


@contextlib.contextmanager
def _counters_span():
    """Span `epilogue.counters`: the OCert counter gate of a clean
    window's epilogue."""
    t0 = time.monotonic()
    try:
        with _enclose("epilogue.counters"):
            yield
    finally:
        _COUNTERS_S[0] = time.monotonic() - t0


def _fold_nonces(params: PraosParams, st: PraosState, slots, etas):
    """The window's evolving/candidate nonce fold over its eta column
    ([b, 32] uint8), lane by lane in chain order -> (evolving,
    candidate). eta' = Blake2b-256(eta ‖ v) is a hash chain, so it is
    per-header wherever it runs (COVERAGE.md §5.11): ~1.5 us a lane
    here. The candidate follows the evolving nonce up to the window's
    last lane inside the stability window (praos.update)."""
    slots = np.asarray(slots)
    first_next = params.first_slot_of(params.epoch_of(slots) + 1)
    w_idx = np.flatnonzero(slots + params.stability_window < first_next)
    k = int(w_idx[-1]) if w_idx.size else -1
    evolving = st.evolving_nonce
    candidate = st.candidate_nonce
    with _enclose("epilogue.fold"):
        data = np.ascontiguousarray(etas).tobytes()
        for i in range(k + 1):
            evolving = nonces.combine(evolving, data[32 * i : 32 * i + 32])
        if k >= 0:
            candidate = evolving
        for i in range(k + 1, len(slots)):
            evolving = nonces.combine(evolving, data[32 * i : 32 * i + 32])
    return evolving, candidate


def _epilogue_packed_fast(
    params: PraosParams,
    ticked: TickedPraosState,
    hvs: Sequence[HeaderView],
    pre: HostChecks,
    v: PackedVerdicts,
) -> BatchResult | None:
    """The packed-verdict fast path: when the bitmask shows every lane
    clean, no precheck error exists, and the stateful OCert
    counter-monotonicity gate passes, assemble the final state straight
    from the host fold of the packed eta bytes — no per-lane error
    reconstruction, no per-lane device columns transferred. Returns
    None when ANY gate trips; the
    caller then runs the exact sequential slow path on the full
    Verdicts, so failure semantics are byte-identical to the reference
    fold by construction."""
    if not v.clean():
        return None
    if any(e is not None for e in pre.kes_window_errors):
        return None
    if any(e is not None for e in pre.vrf_lookup_errors):
        return None
    st = ticked.state
    known = rules_of(params).counter_known(ticked.ledger_view)
    counters = dict(st.ocert_counters)
    with _counters_span():
        for hv in hvs:
            hk = hash_key(hv.vk_cold)
            if not _counter_ok(
                _counter_m(hk, counters, known), hv.ocert.counter
            ):
                return None  # slow path reconstructs the exact error
            counters[hk] = hv.ocert.counter
    evolving, candidate = _fold_nonces(
        params, st, [hv.slot for hv in hvs], v.eta_u8
    )
    state = replace(
        st,
        last_slot=hvs[-1].slot,
        ocert_counters=counters,
        evolving_nonce=evolving,
        candidate_nonce=candidate,
        lab_nonce=nonces.prev_hash_to_nonce(hvs[-1].prev_hash),
    )
    return BatchResult(state, len(hvs), None, None)


def _verdicts_clean(v, b: int) -> bool:
    """Every real lane passed every check outright (no ambiguity)."""
    if isinstance(v, PackedVerdicts):
        return v.clean()
    return bool(
        np.asarray(v.ok_ocert_sig)[:b].all()
        and np.asarray(v.ok_kes_sig)[:b].all()
        and np.asarray(v.ok_vrf)[:b].all()
        and np.asarray(v.ok_leader)[:b].all()
        and not np.asarray(v.leader_ambiguous)[:b].any()
    )


def _counters_gate(cnt, inv, uniq_hk, counters, pool_distr) -> dict | None:
    """The OCert counter gate of a clean window, by issuer: each
    issuer's counters (`cnt[inv == j]`, in chain order) start at its
    current one or one above and step by 0 or 1. -> the counters after
    the window, or None where any issuer's do not (the caller's exact
    fold then names the error). One stable sort groups the lanes by
    issuer: a pass an ISSUER over the window was 8 ms of the retire path
    at 500 issuers (PERF.md, PR 32)."""
    counters = dict(counters)
    order = np.argsort(inv, kind="stable")
    cs, grp = cnt[order], inv[order]
    new_grp = grp[1:] != grp[:-1]
    d = np.diff(cs)
    if ((d < 0) | (d > 1))[~new_grp].any():
        return None
    first = np.flatnonzero(np.concatenate([[True], new_grp]))
    last = np.concatenate([first[1:], [len(cs)]]) - 1
    for j, hk in enumerate(uniq_hk):
        m = _counter_m(hk, counters, pool_distr)
        if m is None or not m <= cs[first[j]] <= m + 1:
            return None
        counters[hk] = int(cs[last[j]])
    return counters


def _epilogue_columns_fast(
    params: PraosParams,
    ticked: TickedPraosState,
    vc: ViewColumns,
    pre: HostChecks,
    v,
) -> BatchResult | None:
    """The columnar all-clean epilogue: counter monotonicity checked per
    unique pool over whole column slices, the candidate-nonce gate
    computed as one vectorized window compare, and the final state
    assembled without materializing a single HeaderView. Returns None
    when ANY gate trips (verdict anomaly, precheck error, counter
    violation, no pool dedup available) — the caller falls back to the
    exact per-header reference fold, so failure semantics are untouched.

    The evolving/candidate nonce fold over the eta column runs here
    (`_fold_nonces`, span `epilogue.fold`)."""
    b = len(vc)
    if not isinstance(pre, ColumnChecks) or pre.any_errors():
        return None
    if not _verdicts_clean(v, b):
        return None
    st = ticked.state
    with _counters_span():
        counters = _counters_gate(
            vc.ocert_counter, pre.uniq_inv, pre.uniq_hk,
            st.ocert_counters,
            rules_of(params).counter_known(ticked.ledger_view),
        )
    if counters is None:
        return None

    etas = (
        v.eta_u8 if isinstance(v, PackedVerdicts)
        else np.asarray(v.eta).astype(np.uint8)
    )
    evolving, candidate = _fold_nonces(params, st, vc.slot, etas)

    last = b - 1
    prev = vc.prev_hash[last].tobytes() if vc.has_prev[last] else None
    state = replace(
        st,
        last_slot=int(vc.slot[last]),
        ocert_counters=counters,
        evolving_nonce=evolving,
        candidate_nonce=candidate,
        lab_nonce=nonces.prev_hash_to_nonce(prev),
    )
    return BatchResult(state, b, None, None)


def _epilogue(
    params: PraosParams,
    ticked: TickedPraosState,
    hvs: "Sequence[HeaderView] | ViewColumns",
    pre: HostChecks,
    v: Verdicts,
    collect_states: bool = False,
    lane_error=None,
) -> BatchResult:
    """Sequential epilogue: counters + nonce fold, stop at first failure.

    `lane_error` defaults to the protocol's own (`rules_of(params)`:
    the Praos `_lane_error`, or TPraos's overlay-aware one with the
    genesis delegates' counter default). A PackedVerdicts `v`
    first tries the bitmask fast path (_epilogue_packed_fast) and only
    materializes the per-lane columns when a gate trips. A ViewColumns
    window first tries the fully-columnar fast path; HeaderViews
    materialize only when a gate trips (anomaly windows — the exact
    per-header reference fold)."""
    columns_declined = False
    if isinstance(hvs, ViewColumns):
        if lane_error is None and not collect_states and len(hvs):
            res = _epilogue_columns_fast(params, ticked, hvs, pre, v)
            if res is not None:
                return res
            columns_declined = True
        hvs = hvs.views()
    if isinstance(v, PackedVerdicts):
        # a declined columnar fast path already proved a gate trips —
        # the packed fast path checks the equivalent gates and would
        # burn O(lanes) re-proving it before the slow path
        if (lane_error is None and not collect_states and hvs
                and not columns_declined):
            res = _epilogue_packed_fast(params, ticked, hvs, pre, v)
            if res is not None:
                return res
        v = v.full()
    rules = rules_of(params)
    default_errors = lane_error is None
    if default_errors:
        lane_error = rules.lane_error
    known = rules.counter_known(ticked.ledger_view)
    lview = ticked.ledger_view
    eta0 = ticked.state.epoch_nonce
    st = ticked.state
    counters = dict(st.ocert_counters)
    evolving = st.evolving_nonce
    candidate = st.candidate_nonce
    lab = st.lab_nonce
    last_slot = st.last_slot
    states_out: list | None = [] if collect_states else None
    # one array conversion for the whole batch (a per-row astype cost
    # ~2us/header in the fold)
    etas = np.ascontiguousarray(np.asarray(v.eta).astype(np.uint8))
    # vectorized all-clear gate for the DEFAULT lane semantics: lanes
    # where every verdict bit is set and no precomputed error exists
    # only need the stateful counter-monotonicity check — `lane_error`
    # is the slow path that reconstructs the exact reference error.
    if default_errors:
        fast_ok = (
            np.asarray(v.ok_ocert_sig) & np.asarray(v.ok_kes_sig)
            & np.asarray(v.ok_vrf) & np.asarray(v.ok_leader)
            & ~np.asarray(v.leader_ambiguous)
        ).tolist()
    else:
        fast_ok = None
    for i, hv in enumerate(hvs):
        if (
            fast_ok is not None
            and fast_ok[i]
            and pre.kes_window_errors[i] is None
            and pre.vrf_lookup_errors[i] is None
        ):
            hk = hash_key(hv.vk_cold)
            m = _counter_m(hk, counters, known)
            if _counter_ok(m, hv.ocert.counter):
                err = None
            else:
                err = lane_error(params, lview, eta0, hv, pre, v, i, counters)
        else:
            err = lane_error(params, lview, eta0, hv, pre, v, i, counters)
        if err is not None:
            state = replace(
                st,
                last_slot=last_slot,
                ocert_counters=counters,
                evolving_nonce=evolving,
                candidate_nonce=candidate,
                lab_nonce=lab,
            )
            return BatchResult(state, i, err, states_out)
        # reupdate bookkeeping (Praos.hs:468-502) with the device-computed
        # eta (Blake2b² range extension)
        eta = etas[i].tobytes()
        evolving = nonces.combine(evolving, eta)
        slot = hv.slot
        first_next = params.first_slot_of(params.epoch_of(slot) + 1)
        if slot + params.stability_window < first_next:
            candidate = evolving
        lab = nonces.prev_hash_to_nonce(hv.prev_hash)
        counters[hash_key(hv.vk_cold)] = hv.ocert.counter
        last_slot = slot
        if states_out is not None:
            states_out.append(
                replace(
                    st,
                    last_slot=last_slot,
                    ocert_counters=dict(counters),
                    evolving_nonce=evolving,
                    candidate_nonce=candidate,
                    lab_nonce=lab,
                )
            )

    state = replace(
        st,
        last_slot=last_slot,
        ocert_counters=counters,
        evolving_nonce=evolving,
        candidate_nonce=candidate,
        lab_nonce=lab,
    )
    return BatchResult(state, len(hvs), None, states_out)


class PraosRules:
    """What the window loop, the staging and the epilogue are
    parameterised by: a protocol's host prechecks, whether its windows
    have another device path than the packed one, who has an OCert
    counter before they have issued, its error taxonomy, its host-side
    backends and its sequential reference. One loop, one staging and one
    epilogue serve every protocol that gives these (`rules_of(params)`;
    protocol/tpraos.TPraosRules is the other one), and it is the ONE
    place a caller learns the protocol of a chain from: the tools' forge
    and config ask it too (`tick`, `reupdate`, `overlay`, `protocol`)."""

    name = "praos"
    protocol = "Praos"  # the name in a chain DB's config (tools/config)
    packed_only = False  # windows may fall back to the staged columns
    overlay = False  # no BFT overlay schedule: every slot is the lottery's
    nonces = True  # epoch nonces: the loop looks the next one up ahead

    def initial_state(self) -> PraosState:
        return PraosState()

    def tick(self, params, lview, slot, state):
        return praos.tick(params, lview, slot, state)

    def reupdate(self, params, hv, slot, ticked):
        """The crypto-free fold of a trusted header (the forge's)."""
        return praos.reupdate(params, hv, slot, ticked)

    def issuers(self, lview) -> int:
        """How many credentials may issue a block under `lview`."""
        return len(lview.pool_distr)

    def window(self, hvs):
        """The representation a window is staged from."""
        return hvs

    def runs(self, hvs) -> list:
        """A within-epoch run of headers as the runs `validate_batch`
        can each take whole."""
        return [hvs]

    def prechecks(self, params, lview, hvs):
        return host_prechecks(params, lview, hvs)

    def counter_known(self, lview):
        """Issuers whose OCert counter starts at 0 (Praos.hs:585-590)."""
        return lview.pool_distr

    def stage_packed(self, params, lview, eta0, hvs, pre):
        """-> ((layout, packed) or None, the gate that declined)."""
        if isinstance(hvs, ViewColumns):
            if not isinstance(pre, ColumnChecks):
                return None, "no-column-prechecks"
            packed = stage_packed_columns(params, lview, eta0, hvs, pre)
        else:
            packed = stage_packed(params, lview, eta0, hvs)
        return packed, (_LAST_DECLINE if packed is None else None)

    def epilogue(self, params, ticked, hvs, pre, v):
        return _epilogue(params, ticked, hvs, pre, v)

    def lane_error(self, *a):
        return _lane_error(*a)

    # the two host-side backends are `validate_batch`'s, reached through
    # `rules_of(params)`: under the supervisor's ladder (recover_window
    # -> validate_batch) like the direct calls they replaced, which a
    # static call graph cannot see through the rules object
    def run_native(self, params, lview, eta0, hvs, pre):
        return run_batch_native(  # octflow: disable=FLOW304
            params, lview, eta0, hvs, pre)

    def run_sharded(self, params, lview, eta0, hvs, pre, mesh):
        from ..parallel import spmd

        return spmd.sharded_stage_run(  # octflow: disable=FLOW304
            params, lview, eta0, hvs, pre, mesh)[0]

    def update(self, params, hv, slot, ticked):
        """The sequential reference (the recovery ladder's last rung)."""
        return praos.update(params, hv, slot, ticked)


PRAOS_RULES = PraosRules()


def rules_of(params) -> PraosRules:
    """The protocol a run of headers is validated under: the params say
    (a `TPraosParams` carries `batch_rules`)."""
    return getattr(params, "batch_rules", PRAOS_RULES)


def validate_chain(
    params: PraosParams,
    ledger_view_for_epoch,
    state: PraosState,
    hvs: Sequence[HeaderView],
    max_batch: int = 8192,
    backend: str = "device",
    pipeline_depth: int = 3,  # 2 windows hide staging behind the device;
    # the third absorbs the shorter epoch-tail batches (6144-lane
    # buckets) without a bubble. ~4 MB staged (packed; ~14 MB on the
    # generic fallback) + ~26 MB on-device per window — far under HBM
    # at depth 3.
    mesh=None,  # backend="sharded": the jax.sharding.Mesh (None = all devices)
) -> BatchResult:
    """Validate an arbitrary run of headers, segmenting at epoch
    boundaries (and at `max_batch` within an epoch) per SURVEY.md §5.7.

    `ledger_view_for_epoch(epoch) -> LedgerView` supplies the forecastable
    per-epoch pool distribution (constant within an epoch).

    Device backend: the run is the stream of ONE piece through
    `validate_stream`'s pipeline — the same loop a whole replay's
    stream of segments goes through (tools/db_analyser).
    """
    if backend == "device":
        return validate_stream(
            params, ledger_view_for_epoch, state, iter((hvs,)),
            max_batch, pipeline_depth,
        )
    with _enclose("validate-chain"):
        return _validate_chain_loop(
            params, ledger_view_for_epoch, state, hvs, max_batch, backend,
            mesh,
        )


def validate_stream(
    params: PraosParams,
    ledger_view_for_epoch,
    state: PraosState,
    stream,
    max_batch: int = 8192,
    pipeline_depth: int = 3,  # see validate_chain
) -> BatchResult:
    """Device backend: ONE window pipeline over a STREAM of chain
    pieces (an iterator of ViewColumns / header lists in chain order:
    what `db_analyser._epoch_window_segments` yields, or validate_chain's
    single run). A piece is cut at epoch boundaries, at `max_batch` and
    at a proof-format change; windows never span pieces.

    Up to `pipeline_depth` windows are staged (host CBOR→SoA) ahead of
    the dispatch and up to `pipeline_depth` are in flight on the device,
    across pieces and across epochs: staging a window needs only its
    epoch's nonce, which is known before the epoch before it has
    drained (`_device_loop`). Retire order is dispatch order is chain
    order. On the first invalid header the result is that header's
    error and everything staged or in flight behind it, in whatever
    piece, is discarded (like queued blocks after a failed chain
    selection in the reference's add-block queue); the stream is closed
    and both thread pools are shut down.

    The next piece is pulled when the staging side has room and the
    piece being cut is exhausted. A stream with a `poll()` method (next
    piece, None while none is ready, StopIteration at the end:
    `db_analyser._prefetch_iter`) is never waited for while a window is
    staged or in flight; only an empty pipeline waits, under the span
    `segment-wait`. Any other iterator is pulled with `next()`, on this
    thread.

    Memory is bounded by design: at most `pipeline_depth` windows
    staged and `pipeline_depth` in flight, so the loop holds at most
    2 x `pipeline_depth` pieces (that many only where every piece is a
    single window), the one being cut among them; what the stream
    itself buffers beyond that is the stream's (the prefetch queue: two
    pieces and the one in the pump's hand).
    """
    from concurrent.futures import ThreadPoolExecutor

    with _enclose("validate-chain"):
        # one worker thread owns the BLOCKING device reads: the main
        # thread keeps staging/dispatching while the worker waits, so
        # host staging hides behind device execution even when the
        # backend only makes progress under a blocking read
        pool = ThreadPoolExecutor(max_workers=1, thread_name_prefix="oct-read")
        # producer thread: prechecks + packed staging + padding run
        # ahead of dispatch (prepare_window is fold-independent),
        # overlapping the staging wall with device compute and the
        # retire-side epilogue
        stage_pool = None
        if _stage_thread_enabled():
            stage_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="oct-stage"
            )
        try:
            return _device_loop(
                params, ledger_view_for_epoch, state, stream, max_batch,
                pipeline_depth, pool, stage_pool,
            )
        finally:
            # cancel_futures: on an early error return the queued
            # materialize / staging futures belong to DISCARDED windows
            # — without it the reader keeps issuing blocking device
            # reads for results nobody wants and the atexit join stalls
            # exit
            pool.shutdown(wait=False, cancel_futures=True)
            if stage_pool is not None:
                stage_pool.shutdown(wait=False, cancel_futures=True)
            close = getattr(stream, "close", None)
            if close is not None:
                close()  # stops the prefetch pump; closes a generator


def _epoch_segments_idx(params, hvs) -> list[tuple[int, int, int]]:
    """[(epoch, start, end)] index segmentation at epoch boundaries —
    one vectorized pass for ViewColumns, the per-header walk for lists."""
    n = len(hvs)
    if n == 0:
        return []
    if _columnar(hvs):
        epochs = params.epoch_of(hvs.slot)
        cuts = np.flatnonzero(np.diff(epochs)) + 1
        bounds = [0, *cuts.tolist(), n]
        return [
            (int(epochs[bounds[k]]), bounds[k], bounds[k + 1])
            for k in range(len(bounds) - 1)
        ]
    segments = []
    i = 0
    while i < n:
        epoch = params.epoch_of(hvs[i].slot)
        j = i
        while j < n and params.epoch_of(hvs[j].slot) == epoch:
            j += 1
        segments.append((epoch, i, j))
        i = j
    return segments


def _proof_break(hvs, w: int, j: int) -> int:
    """First index in (w, j) where the VRF proof format changes (a
    window must stage one uniform proof column), else j."""
    if not isinstance(hvs, ViewColumns) and _columnar(hvs):
        return j  # a Byron piece: one width, no proof
    if isinstance(hvs, ViewColumns):
        pl = hvs.vrf_proof_len
        diff = np.flatnonzero(pl[w + 1 : j] != pl[w])
        return w + 1 + int(diff[0]) if diff.size else j
    plen = len(hvs[w].vrf_proof)
    for k in range(w + 1, j):
        if len(hvs[k].vrf_proof) != plen:
            return k
    return j


def _validate_chain_loop(
    params, ledger_view_for_epoch, state, hvs, max_batch, backend, mesh,
):
    """The host-side backends (native / sharded / host): one window at
    a time, no pipeline."""
    from ..obs import recovery as _recovery
    from ..testing import chaos as _chaos

    total_valid = 0
    win_idx = 0  # retire-order window index (RecoveryEvent / checkpoints)
    rules = rules_of(params)
    for epoch, i, seg_end in _epoch_segments_idx(params, hvs):
        lview = ledger_view_for_epoch(epoch)
        while i < seg_end:
            j = min(i + max_batch, seg_end)
            if not rules.nonces:
                # a Byron window: the native verifier and the PBFT fold
                res = rules.run_native(params, rules.tick(
                    params, lview, _slot_at(hvs, i), state), hvs[i:j])
                state = res.state
                total_valid += res.n_valid
                if res.error is not None:
                    return BatchResult(state, total_valid, res.error)
                i = j
                continue
            ticked = praos.tick(params, lview, _slot_at(hvs, i), state)
            try:
                res = validate_batch(
                    params, ticked, hvs[i:j], backend=backend, mesh=mesh
                )
            except Exception as e:  # noqa: BLE001 — supervisor gates
                # the degradation ladder (obs/recovery.py): re-raises
                # unrecoverable classes / OCT_RECOVERY=0 unchanged
                res = _recovery.supervisor().recover_window(
                    params, ticked, hvs[i:j], e, backend=backend,
                    mesh=mesh, window=win_idx,
                )
            state = res.state
            total_valid += res.n_valid
            if res.error is not None:
                return BatchResult(state, total_valid, res.error)
            # crash-consistent progress record per retired window
            # (one None check when OCT_CHECKPOINT is unset), THEN
            # the sigkill seam — a chaos kill lands AFTER the
            # checkpoint, the exactly-once window boundary
            _recovery.note_window(state, res.n_valid)
            _chaos.fire("retire")
            win_idx += 1
            i = j
    return BatchResult(state, total_valid, None)


def _device_loop(
    params, ledger_view_for_epoch, state, stream, max_batch,
    pipeline_depth, pool, stage_pool,
):
    """ONE pipeline across pieces and epoch boundaries. Staging a
    window needs only (epoch nonce, ledger view). A row-width step
    inside an epoch keeps the nonce; the next epoch's is tick's rotation
    combine(candidate, last_epoch_block_nonce) (Praos.hs:407-432), whose
    inputs are final well before the current epoch drains:
    candidate_nonce freezes at the stability window (last update from a
    header with slot < first_slot(e+1) - 3k/f, Praos.hs:497) and
    last_epoch_block_nonce was latched at the PREVIOUS boundary. So
    once the fold retires past the freeze slot, the next epoch's first
    windows dispatch while this epoch's tail is still on device — no
    drain bubble per boundary. The retire-time tick asserts every
    window's staged nonce byte-for-byte."""
    from collections import deque

    from ..obs import recovery as _recovery
    from ..testing import chaos as _chaos

    # one lane shape per replay on the chip (window_lanes). Resolved
    # HERE, on the dispatching thread: the staging thread does not see
    # this thread's recovery overrides
    lanes = window_lanes(max_batch)
    # the protocol's prechecks, wire format and epilogue: what this loop
    # is parameterised by (`prepare_window` and `_epilogue` ask again)
    rules = rules_of(params)
    # a chain over eras (hardfork/cardano.CardanoParams): its pieces come
    # tagged (EraPiece) and each era has its own params and rules; the
    # loop carries ONE state, in era `era_cur`, translated at a crossing
    eras = getattr(params, "eras", None)

    def era_of(item):
        """-> (era, its params, its rules, the bare run of headers)."""
        if isinstance(item, EraPiece):
            p = eras[item.era]
            return item.era, p, rules_of(p), item.cols
        return 0, params, rules, item

    era_cur = 0  # the era `state` is in
    crossings: list = []

    # Backpressure at pipeline_depth on EACH side of the double buffer:
    # up to pipeline_depth windows staged-but-undispatched AND up to
    # pipeline_depth dispatched-but-unretired (without the staging
    # thread the staged deque never exceeds one window; with it, at
    # most 2 x pipeline_depth windows are alive — ~8 MB packed each at
    # 8192 lanes, still far under HBM).
    # (epoch, eta, window_hvs, staged-or-future, id, era)
    staged: deque = deque()
    # (epoch, eta, window_hvs, pre, meta, future, era)
    inflight: deque = deque()
    total_valid = 0

    stream_done = False
    pending: deque = deque()  # runs of a pulled piece still to be cut
    piece = None  # the piece being cut (None: exhausted, pull the next)
    p_era, pp, pr = 0, params, rules  # its era, the era's params, rules
    cuts: list = []  # its [(epoch, start, end)], and where the cut stands
    k = w = 0
    cut_epoch = cut_eta = None  # of the segment cut last
    cut_era = None
    # (epoch, nonce of the epoch after it), published by the retire
    # path once `epoch`'s candidate nonce is frozen: the lookahead
    rotation = None
    unknown = object()  # no nonce yet (None is one: the neutral nonce)
    progress = 0  # pieces pulled + windows cut (the fixpoint's witness)
    lviews: dict[int, object] = {}

    def lview_for(epoch: int):
        if epoch not in lviews:
            live = {epoch, *(x[0] for x in staged), *(x[0] for x in inflight)}
            for e in [e for e in lviews if e not in live]:
                del lviews[e]
            lviews[epoch] = ledger_view_for_epoch(epoch)
        return lviews[epoch]

    def segment_eta(epoch: int, slot: int):
        """The epoch nonce the windows of a new segment stage with;
        `unknown` while it is not derivable (its predecessors must
        retire further: past the freeze slot, or all of them). None in
        an era without nonces (PBFT)."""
        if not pr.nonces:
            return None
        if not staged and not inflight:
            # everything cut so far has retired: the fold's own rotation
            st = params.cross(state, era_cur, p_era) if eras else state
            return praos.tick(
                pp, lview_for(epoch), slot, st
            ).state.epoch_nonce
        if epoch == cut_epoch:
            return cut_eta  # a row-width step: no rotation inside an epoch
        if rotation is not None and rotation[0] == cut_epoch < epoch:
            return rotation[1]
        if cut_era is not None and cut_era != p_era \
                and not eras[cut_era].batch_rules.nonces:
            # the first epoch after an era without nonces: its nonce is
            # the translation's (the Shelley genesis nonce) whatever the
            # last era's state, so it is known now
            st = params.cross(rules_of(eras[cut_era]).initial_state(),
                              cut_era, p_era)
            return praos.tick(
                pp, lview_for(epoch), slot, st
            ).state.epoch_nonce
        return unknown

    def enqueue_staging():
        """Cut windows and submit them for staging while the staging
        side has room, one span `enqueue` a window. The stream's next
        piece is taken before that span: polled, or waited for in
        `segment-wait` where the pipeline is empty."""
        nonlocal stream_done, piece, cuts, k, w, cut_epoch, cut_eta, progress
        nonlocal p_era, pp, pr, cut_era
        while (
            # producer thread: stage ahead up to pipeline_depth
            # regardless of the in-flight side (double buffer)
            len(staged) < pipeline_depth
            if stage_pool is not None
            # inline (OCT_STAGE_THREAD=0): stage only what can
            # dispatch immediately — the round-9 loop exactly
            else not staged and len(inflight) < pipeline_depth
        ):
            nxt = None
            if piece is None and not pending:
                if stream_done:
                    return
                # never wait for the stream behind a busy pipeline: a
                # piece that is not there yet is asked for again after
                # the next retire
                poll = (
                    getattr(stream, "poll", None)
                    if staged or inflight else None
                )
                try:
                    if poll is not None:
                        nxt = poll()
                        if nxt is None:
                            return
                    else:
                        with _enclose("segment-wait"):
                            nxt = next(stream)
                except StopIteration:
                    stream_done = True
                    return
            with _enclose("enqueue"):
                if piece is None and pending:
                    p_era, pp, pr, nxt = era_of(pending.popleft())
                    cuts = _epoch_segments_idx(pp, nxt)
                    piece, k, w = nxt, 0, cuts[0][1]
                if piece is None:
                    progress += 1
                    if not len(nxt):
                        continue
                    n_era, n_p, n_r, nxt = era_of(nxt)
                    # the runs the protocol's windows can be cut from (a
                    # piece whole, but for a TPraos list of ragged views)
                    nxt, *more = n_r.runs(nxt)
                    pending.extend(EraPiece(n_era, m) if eras else m
                                   for m in more)
                    cuts = _epoch_segments_idx(n_p, nxt)
                    if not cuts:
                        continue
                    p_era, pp, pr = n_era, n_p, n_r
                    piece, k, w = nxt, 0, cuts[0][1]
                epoch, start, seg_end = cuts[k]
                if w == start:
                    eta = segment_eta(epoch, _slot_at(piece, start))
                    if eta is unknown:
                        return
                    cut_epoch, cut_eta, cut_era = epoch, eta, p_era
                # a window must stage a uniform proof column: break at the
                # first 80/128-byte format change (the reference fold
                # length-dispatches per header, so mixed chains stay valid;
                # segmentation never changes verdicts or the first error)
                j = _proof_break(piece, w, min(w + max_batch, seg_end))
                whvs = piece[w:j]
                # the window's id: staging order is dispatch order
                win = next_window_id()
                if stage_pool is not None:
                    item = stage_pool.submit(
                        prepare_window, pp, lview_for(epoch), cut_eta,
                        whvs, lanes, win,
                    )
                else:
                    item = prepare_window(
                        pp, lview_for(epoch), cut_eta, whvs, lanes, win,
                    )
                staged.append((epoch, cut_eta, whvs, item, win, p_era))
                progress += 1
                w = j
                if w >= seg_end:
                    k += 1
                    if k < len(cuts):
                        w = cuts[k][1]
                    else:
                        piece = None

    def _queue_failure(exc: BaseException) -> bool:
        """True when the supervisor may absorb `exc`: the window rides
        the pipeline as a _FailedDispatch and recovers at its retire
        slot. False (disabled / unrecoverable class) -> raise-through,
        the pre-PR-12 behavior."""
        return _recovery.enabled() and _recovery.recoverable(exc)

    def drain_dispatch():
        # dispatch the HEAD staged window (retire order is dispatch
        # order) if the in-flight side of the double buffer has room.
        # ONE a call: the caller refills the staging side between two
        # dispatches (7 ms of this thread each), or the producer idles
        # through a run of them. When nothing is in flight, block on
        # the staging head — otherwise let a materialize retire while
        # the producer keeps staging
        if not staged or len(inflight) >= pipeline_depth:
            return
        epoch_w, eta_w, whvs_w, item, win, era_w = staged[0]
        stage_wait_s = 0.0
        if stage_pool is not None and hasattr(item, "result"):
            late = not item.done()
            if late and inflight:
                return
            try:
                if late:
                    # staging-thread lateness: nothing is in flight
                    # and the head window is not staged yet
                    t_w0 = time.monotonic()
                    with _enclose("stage-wait", win):
                        item = item.result()
                    stage_wait_s = time.monotonic() - t_w0
                else:
                    item = item.result()
            except Exception as e:  # noqa: BLE001 — gated below
                # the staging producer died mid-prepare: the window
                # recovers at its retire slot (full re-validation)
                staged.popleft()
                if not _queue_failure(e):
                    raise
                inflight.append((epoch_w, eta_w, whvs_w, None, None,
                                 _FailedDispatch(e), era_w))
                return
        staged.popleft()
        try:
            pre, out, b = dispatch_prepared(item)
        except Exception as e:  # noqa: BLE001 — gated below
            if not _queue_failure(e):
                raise
            inflight.append((epoch_w, eta_w, whvs_w, None, None,
                             _FailedDispatch(e), era_w))
            return
        meta = out.meta
        if meta is not None and stage_wait_s:
            meta = meta._replace(stage_wait_s=stage_wait_s)
        inflight.append(
            (epoch_w, eta_w, whvs_w, pre, meta,
             pool.submit(materialize_verdicts, out, b), era_w)
        )

    win_retired = 0  # retire-order window index (recovery/checkpoints)
    while True:
        # alternate stage/dispatch to a FIXPOINT, a window at a time:
        # every ready staged window is dispatched while the in-flight
        # side has room, and the staging side is refilled before each
        # (the inline, OCT_STAGE_THREAD=0, mode stages one window and
        # dispatches it immediately, so the in-flight side still fills
        # to pipeline_depth exactly as the round-9 loop did)
        while True:
            before = (len(staged), len(inflight), progress)
            enqueue_staging()
            drain_dispatch()
            if (len(staged), len(inflight), progress) == before:
                break

        if not inflight:
            # an empty pipeline waits for the stream and ticks a new
            # segment's nonce from the fully-folded state
            # (enqueue_staging), and a staged head is dispatched
            # whenever nothing is in flight (drain_dispatch): only the
            # end of the stream leaves nothing in flight here
            assert (stream_done and piece is None and not staged
                    and not pending)
            return BatchResult(state, total_valid, None, era=era_cur,
                               crossings=crossings or None)

        # refill the staging side BEFORE blocking on the retire below:
        # dispatching just freed buffer room, and the producer must be
        # working through the device wait — without this the staging
        # thread idled during every retire block (the whole overlap)
        enqueue_staging()

        epoch, eta, whvs, pre, meta, fut, era_w = inflight.popleft()
        wp = eras[era_w] if eras else params
        wr = rules_of(wp)
        win = meta.index if meta is not None else None
        # the pipeline's fill as this window's retire wait begins
        inflight_behind, staged_ahead = len(inflight), len(staged)
        t_m0 = time.monotonic()
        fail: BaseException | None = None
        v = None
        try:
            with _enclose("materialize", win):
                v = fut.result()
        except Exception as e:  # noqa: BLE001 — gated by _queue_failure
            if not _queue_failure(e):
                raise
            fail = e
        t_m1 = time.monotonic()
        if era_w != era_cur:
            # a hard fork: the state into the window's era (span
            # `era-cross`; the era's first windows were staged and
            # dispatched behind the last era's, as at an epoch boundary)
            with _enclose("era-cross"):
                before = state
                state = params.cross(state, era_cur, era_w)
                crossings.append((era_cur, era_w, before, state))
                era_cur = era_w
        with _enclose("tick", win):
            if wr.nonces:
                ticked = praos.tick(
                    wp, lview_for(epoch), _slot_at(whvs, 0), state
                )
            else:
                ticked = wr.tick(wp, lview_for(epoch), _slot_at(whvs, 0),
                                 state)
        # the window staged with a nonce carried over or looked AHEAD:
        # the real rotation must agree (internal invariant)
        assert not wr.nonces or ticked.state.epoch_nonce == eta, (
            "lookahead epoch nonce mismatch"
        )
        t_e0 = time.monotonic()
        _COUNTERS_S[0] = _PBFT_S[0] = 0.0  # a window off the span reads 0
        if fail is None:
            try:
                with _enclose("epilogue", win):
                    res = wr.epilogue(wp, ticked, whvs, pre, v)
            except Exception as e:  # noqa: BLE001 — gated below
                if not _queue_failure(e):
                    raise
                fail = e
        if fail is not None and not wr.nonces:
            raise fail  # a Byron window has no ladder to fall down
        if fail is not None:
            # the supervisor re-validates JUST this window down the
            # degradation ladder (retry -> stage-split -> xla-twin ->
            # host reference); any rung's result IS the window's
            # verdict
            res = _recovery.supervisor().recover_window(
                wp, ticked, whvs, fail, backend="device",
                window=win_retired,
            )
        state = res.state
        total_valid += res.n_valid
        _emit_window_span(
            meta, len(whvs), res.n_valid, res.error is not None,
            t_m0, t_m1, t_e0, time.monotonic(), inflight_behind,
            staged_ahead,
        )
        if res.error is not None:
            return BatchResult(state, total_valid, res.error, era=era_cur,
                               crossings=crossings or None)
        # progress record BEFORE the sigkill seam: a chaos (or real)
        # kill after this point loses nothing — the resume re-seeds
        # from exactly this retired window (obs/recovery.py)
        _recovery.note_window(state, res.n_valid)
        _chaos.fire("retire")
        win_retired += 1

        if wr.nonces and (rotation is None or rotation[0] != epoch) and (
            state.last_slot + 1
            >= wp.first_slot_of(epoch + 1) - wp.stability_window
        ):
            # every later header of the epoch has a larger slot, so the
            # candidate is frozen, and the LAB component was latched a
            # boundary ago: the next epoch's rotation is decided
            rotation = (epoch, nonces.combine(
                state.candidate_nonce, state.last_epoch_block_nonce
            ))
