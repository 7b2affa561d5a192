"""Block forging: assemble and KES-sign a Praos block.

Reference: `forgeBlock`/`mkHeader` — Block/Forging.hs:143 and the Praos
`mkHeader` instance (ouroboros-consensus-cardano shelley
Protocol/Praos.hs:102): build the header body, KES-sign its serialisation
with the hot key at the current evolution, attach the signature.

Used by the forging loop (node/), db_synthesizer (tools/) and tests.
"""

from __future__ import annotations

from ..ops.host import ecvrf as host_ecvrf
from ..ops.host import fast
from ..ops.host import kes as host_kes
from ..protocol import nonces
from ..protocol.praos import PraosIsLeader, PraosParams
from ..testing.fixtures import PoolCredentials
from .praos_block import Block, Header, HeaderBody, body_hash


def evaluate_vrf(pool: PoolCredentials, slot: int, epoch_nonce: nonces.Nonce):
    """VRF.evalCertified at InputVRF(slot, eta0) (Praos.hs:397)."""
    alpha = nonces.mk_input_vrf(slot, epoch_nonce)
    proof = fast.ecvrf_prove(pool.vrf_seed, alpha)
    return PraosIsLeader(fast.ecvrf_proof_to_hash(proof), proof)


def forge_block(
    params: PraosParams,
    pool: PoolCredentials,
    *,
    slot: int,
    block_no: int,
    prev_hash: bytes | None,
    epoch_nonce: nonces.Nonce,
    txs: tuple[bytes, ...] = (),
    ocert_counter: int = 0,
    is_leader: PraosIsLeader | None = None,
    protocol_version: tuple[int, int] = (9, 0),
    hotkey=None,  # protocol.hotkey.HotKey: evolve-and-sign in place
    ocert=None,  # the issued OCert accompanying `hotkey`
) -> Block:
    """Forge a protocol-valid block for `slot` (the caller is responsible
    for having won the slot; db_synthesizer checks check_is_leader first).

    With `hotkey`/`ocert` (the node path, NodeKernel), the evolving key
    signs at its own evolution and the certificate is used as issued
    (Ledger/HotKey.hs:142). Without them (synthesizer/test path) a
    throwaway OCert is issued at the containing evolution-window start
    and the signature derived statically from the pool's root seed.
    """
    if is_leader is None:
        is_leader = evaluate_vrf(pool, slot, epoch_nonce)
    kp = params.kes_period_of(slot)
    if ocert is None:
        # issue the ocert at the containing evolution-window start so
        # that 0 <= t < max_kes_evolutions always holds
        c0 = max(0, kp - (kp % params.max_kes_evolutions))
        ocert = pool.make_ocert(ocert_counter, c0)
    body = HeaderBody(
        block_no=block_no,
        slot=slot,
        prev_hash=prev_hash,
        issuer_vk=pool.vk_cold,
        vrf_vk=pool.vrf_vk,
        vrf_output=is_leader.vrf_output,
        vrf_proof=is_leader.vrf_proof,
        body_size=sum(len(t_) for t_ in txs),
        body_hash=body_hash(txs),
        ocert=ocert,
        protocol_version=protocol_version,
        vrf_leader_output=is_leader.vrf_leader_output,
        vrf_leader_proof=is_leader.vrf_leader_proof,
    )
    if hotkey is not None:
        kes_sig = hotkey.sign(kp, body.signed_bytes)
    else:
        t = kp - ocert.kes_period
        kes_sig = host_kes.sign(pool.kes_seed, pool.kes_depth, t, body.signed_bytes)
    return Block(Header(body, kes_sig), tuple(txs))
