"""Praos leader-threshold check (exact interval arithmetic).

The rule (cardano-ledger `checkLeaderNatValue`, called from the reference
hot path at Praos.hs:505 `meetsLeaderThreshold` and Praos.hs:551 VRF
validation): a pool with relative stake sigma leads the slot iff

    p < 1 - (1 - f)^sigma        with p = leaderValue / 2^256

evaluated as  1/(1-p) < exp(-sigma * ln(1-f)).

The reference computes this in 34-decimal-digit fixed point with a
Taylor-series comparison (`taylorExpCmp`). We instead use exact rational
interval arithmetic: ln(1-f) and exp are bracketed by partial sums with
rigorous remainder bounds, tightened until the comparison is decided.
This is deterministic and, because the quantities are continuous in the
inputs, agrees with the fixed-point reference except on a measure-zero
boundary band narrower than the reference's own rounding error.
"""

from __future__ import annotations

from fractions import Fraction
from functools import lru_cache

LEADER_VALUE_MAX = 1 << 256


@lru_cache(maxsize=64)
def _neg_log1m_interval(f: Fraction, terms: int) -> tuple[Fraction, Fraction]:
    """[lo, hi] bracketing -ln(1 - f) for 0 < f < 1 via the Mercator series
    -ln(1-f) = sum_{n>=1} f^n / n, remainder < f^(N+1)/((N+1)(1-f))."""
    acc = Fraction(0)
    fp = Fraction(1)
    for n in range(1, terms + 1):
        fp *= f
        acc += fp / n
    rem = fp * f / ((terms + 1) * (1 - f))
    return acc, acc + rem


def _exp_interval(lo: Fraction, hi: Fraction, terms: int) -> tuple[Fraction, Fraction]:
    """[exp_lo, exp_hi] for x in [lo, hi], 0 <= x < 1: partial sums plus a
    geometric remainder bound x^(N+1)/(N+1)! * 1/(1-x)."""
    def partial(x: Fraction) -> tuple[Fraction, Fraction]:
        acc = Fraction(1)
        term = Fraction(1)
        for n in range(1, terms + 1):
            term = term * x / n
            acc += term
        rem = term * x / (terms + 1) / (1 - x)
        return acc, rem

    lo_sum, _ = partial(lo)
    hi_sum, hi_rem = partial(hi)
    return lo_sum, hi_sum + hi_rem


def check_leader_value(leader_value: int, sigma: Fraction,
                       active_slot_coeff: Fraction,
                       value_max: int = LEADER_VALUE_MAX) -> bool:
    """True iff `leader_value` wins the slot for relative stake `sigma`.

    active_slot_coeff is f in (0, 1]; f == 1 means every slot is active for
    everyone (reference: activeSlotVal == maxBound short-circuit).
    `value_max` is the range of the leader value: 2^256 for Praos's
    hashed value, 2^512 for TPraos, whose value is the raw 64-byte VRF
    output (cardano-protocol-tpraos `checkLeaderValue`).
    """
    f = Fraction(active_slot_coeff)
    sigma = Fraction(sigma)
    if f == 1:
        return True
    if sigma == 0:
        # exp(0) = 1 and 1/(1-p) >= 1 always: never a leader
        return False
    lhs = Fraction(value_max, value_max - leader_value)
    for terms in (8, 16, 32, 64, 128):
        llo, lhi = _neg_log1m_interval(f, terms)
        xlo, xhi = sigma * llo, sigma * lhi
        elo, ehi = _exp_interval(xlo, xhi, terms)
        if lhs < elo:
            return True
        if lhs >= ehi:
            return False
    # interval still undecided after 128 terms: the value sits within an
    # astronomically thin band; break the tie on the midpoint, determinism
    # preserved (same computation on every node)
    return lhs < (elo + ehi) / 2
