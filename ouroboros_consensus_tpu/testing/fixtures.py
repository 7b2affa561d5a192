"""Praos credential + header-forging fixtures (host, sign-side).

Used by the test suite and by tools/db_synthesizer to forge valid chains.
Mirrors the data the reference's `db-synthesizer` loads from credential
files (Tools/DBSynthesizer/Run.hs) — cold Ed25519 key, VRF key, KES tree —
but generated deterministically from integer seeds.
"""

from __future__ import annotations

from dataclasses import dataclass
from fractions import Fraction
from functools import cached_property

from ..ops.host import ecvrf as hv
from ..ops.host import ed25519 as he
from ..ops.host import fast
from ..ops.host import kes as hk
from ..protocol import nonces
from ..protocol.praos import PraosCanBeLeader, PraosParams
from ..protocol.views import (
    HeaderView,
    IndividualPoolStake,
    LedgerView,
    OCert,
    hash_key,
    hash_vrf_vk,
)


def _seed(tag: bytes, n: int) -> bytes:
    from ..ops.host.hashes import blake2b_256

    return blake2b_256(tag + n.to_bytes(8, "big"))


@dataclass(frozen=True)
class PoolCredentials:
    """One pool's full signing identity."""

    cold_seed: bytes
    vrf_seed: bytes
    kes_seed: bytes
    kes_depth: int

    # cached: the seeds are frozen, and each derivation is a scalar
    # multiplication — forging consults these every slot
    @cached_property
    def vk_cold(self) -> bytes:
        return fast.ed25519_public(self.cold_seed)

    @cached_property
    def vrf_vk(self) -> bytes:
        return fast.ed25519_public(self.vrf_seed)  # VRF uses Ed25519 keys

    @cached_property
    def kes_vk(self) -> bytes:
        return hk.derive_vk(self.kes_seed, self.kes_depth)

    @cached_property
    def pool_id(self) -> bytes:
        return hash_key(self.vk_cold)

    def make_ocert(self, counter: int, kes_period: int) -> OCert:
        oc = OCert(self.kes_vk, counter, kes_period, b"")
        sig = fast.ed25519_sign(self.cold_seed, oc.signable())
        return OCert(self.kes_vk, counter, kes_period, sig)


def make_pool(n: int, kes_depth: int = hk.DEFAULT_DEPTH) -> PoolCredentials:
    return PoolCredentials(
        _seed(b"cold", n), _seed(b"vrf", n), _seed(b"kes", n), kes_depth
    )


def capped_zipf_stakes(n: int, exponent: int = 1,
                       cap_weight: Fraction = Fraction(1, 18)):
    """Mainnet-shaped stake for `n` pools in rank order: the pool of
    rank r weighs min(1 / r**exponent, cap_weight), a Zipf tail under a
    saturation cap (mainnet: ~3,000 pools, saturated at 1/k of the stake
    with k = 500); the stakes are the weights over their sum, exact."""
    w = [min(Fraction(1, r ** exponent), Fraction(cap_weight))
         for r in range(1, n + 1)]
    total = sum(w)
    return [x / total for x in w]


def make_ledger_view(pools: list[PoolCredentials], stakes=None) -> LedgerView:
    if stakes is None:
        stakes = [Fraction(1, len(pools))] * len(pools)
    return LedgerView(
        pool_distr={
            p.pool_id: IndividualPoolStake(s, hash_vrf_vk(p.vrf_vk))
            for p, s in zip(pools, stakes)
        }
    )


def can_be_leader(pool: PoolCredentials, counter: int = 0, kes_period: int = 0) -> PraosCanBeLeader:
    return PraosCanBeLeader(
        ocert=pool.make_ocert(counter, kes_period),
        vk_cold=pool.vk_cold,
        vrf_sign_seed=pool.vrf_seed,
    )


def find_leader(
    params: PraosParams,
    pools: list[PoolCredentials],
    lview: LedgerView,
    slot: int,
    epoch_nonce: nonces.Nonce,
) -> PoolCredentials | None:
    """First pool (by list order) winning the leader check for `slot`,
    decided by the protocol's own check_is_leader (no re-implementation)."""
    from ..protocol import praos as praos_mod

    ticked = praos_mod.TickedPraosState(
        praos_mod.PraosState(epoch_nonce=epoch_nonce), lview
    )
    for pool in pools:
        if (
            praos_mod.check_is_leader(params, can_be_leader(pool), slot, ticked)
            is not None
        ):
            return pool
    return None


def forge_header_view(
    params: PraosParams,
    pool: PoolCredentials,
    slot: int,
    epoch_nonce: nonces.Nonce,
    prev_hash: bytes | None,
    body_bytes: bytes = b"",
    ocert_counter: int = 0,
) -> HeaderView:
    """Forge a protocol-valid HeaderView for `slot` (ignores leader check —
    callers wanting realistic chains should first consult check_is_leader).

    `body_bytes` stands in for the KES-signed header-body serialisation
    until the real codec (block/) is wired; validation only sees bytes.
    """
    alpha = nonces.mk_input_vrf(slot, epoch_nonce)
    proof = fast.ecvrf_prove(pool.vrf_seed, alpha)
    output = fast.ecvrf_proof_to_hash(proof)
    kp = params.kes_period_of(slot)
    ocert = pool.make_ocert(ocert_counter, kp)
    t = 0  # ocert issued for the current period: evolution index 0
    kes_sig = hk.sign(pool.kes_seed, pool.kes_depth, t, body_bytes)
    return HeaderView(
        prev_hash=prev_hash,
        vk_cold=pool.vk_cold,
        vrf_vk=pool.vrf_vk,
        vrf_output=output,
        vrf_proof=proof,
        ocert=ocert,
        slot=slot,
        signed_bytes=body_bytes,
        kes_sig=kes_sig,
    )


def forge_tpraos_header_view(
    params: PraosParams,
    pool: PoolCredentials,
    slot: int,
    epoch_nonce: nonces.Nonce,
    prev_hash: bytes | None,
    body_bytes: bytes = b"",
    ocert_counter: int = 0,
) -> HeaderView:
    """`forge_header_view` for a TPraos (Shelley-era) header: the nonce
    and the leader certificate, both 80-byte draft-03 proofs under the
    pool's one VRF key (protocol/tpraos.prove_certificates). The leader
    check and the overlay schedule are the caller's to consult."""
    from dataclasses import replace

    from ..protocol import tpraos

    hv = forge_header_view(params, pool, slot, epoch_nonce, prev_hash,
                           body_bytes, ocert_counter)
    il = tpraos.prove_certificates(pool.vrf_seed, slot, epoch_nonce)
    return replace(
        hv, vrf_output=il.vrf_output, vrf_proof=il.vrf_proof,
        vrf_leader_output=il.vrf_leader_output,
        vrf_leader_proof=il.vrf_leader_proof,
    )
