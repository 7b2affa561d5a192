"""The Cardano chain on the normal path: Byron (PBFT) → Shelley (TPraos)
→ Babbage (Praos) as ONE deployment whose params `batch.rules_of` and the
tools take like any other protocol's ("Protocol": "Cardano" in a chain
DB's config, as the reference's db-analyser takes the block type from
the node config: DBAnalyser/Block/Cardano.hs).

Reference: `CardanoBlock c = HardForkBlock (ByronBlock ':
CardanoShelleyEras c)` (Cardano/Block.hs:96) with its eras collapsed to
the three protocol classes, `protocolInfoCardano` with
`TriggerHardForkAtEpoch` (Cardano/Node.hs), the `CanHardFork`
translations (Cardano/CanHardFork.hs:273).

  CardanoParams   each era's params on the hard-fork clock (an era's
                  epochs count on from the last era's: Byron's 21,600-slot
                  epochs, then 43,200-slot ones), the Shelley genesis
                  nonce, and the translations.
  CardanoViews    each era's ledger view: Byron's delegation map,
                  Shelley's pools and genesis delegates, Babbage's pools.
  the stream     `protocol/batch.EraPiece`s: runs of one era's headers
                  (tools/db_analyser -> protocol/batch.validate_stream).

The translations (chain-dependent state only; the ledger's are
hardfork/composite's):
  Byron → Shelley  translateChainDepStateByronToShelley: the TPraos state
                   starts with the evolving, candidate and epoch nonces at
                   the Shelley genesis nonce, the prev-hash and last-epoch
                   -block nonces neutral, no OCert counters (a genesis
                   delegate with none starts at 0) and the last slot the
                   PBFT state's last signed slot. The first Shelley tick
                   is an epoch boundary and rotates to candidate ⭒ neutral
                   = the genesis nonce.
  Shelley → Babbage  tpraos.translate_state: nonces, counters and last
                   slot carry over; the first Babbage tick is an ordinary
                   epoch-boundary TICKN.
"""

from __future__ import annotations

from dataclasses import dataclass, replace

from ..protocol import praos, tpraos
from ..protocol.instances import PBftLedgerView, PBftState
from ..protocol.pbft import PBftEraParams

def byron_to_shelley(state: PBftState, genesis_nonce: bytes):
    """translateChainDepStateByronToShelley (module docstring)."""
    last = state.last_signed_slot if isinstance(state, PBftState) \
        else state.last_slot
    return tpraos.TPraosState(
        last_slot=last, evolving_nonce=genesis_nonce,
        candidate_nonce=genesis_nonce, epoch_nonce=genesis_nonce)


@dataclass(frozen=True)
class CardanoParams:
    """The three eras' params, each carrying its origin on the hard-fork
    clock (`era_start_slot`, `era_start_epoch`), and the nonce the
    Shelley genesis gives."""

    byron: PBftEraParams
    shelley: tpraos.TPraosParams
    babbage: praos.PraosParams
    genesis_nonce: bytes

    @property
    def eras(self) -> tuple:
        return (self.byron, self.shelley, self.babbage)

    def era_params(self, era: int):
        return self.eras[era]

    def era_of_slot(self, slot: int) -> int:
        e = 0
        for i, p in enumerate(self.eras):
            if slot >= p.era_start_slot:
                e = i
        return e

    def era_of_epoch(self, epoch: int) -> int:
        e = 0
        for i, p in enumerate(self.eras):
            if epoch >= p.era_start_epoch:
                e = i
        return e

    def epoch_of(self, slot: int) -> int:
        return self.eras[self.era_of_slot(slot)].epoch_of(slot)

    def translate(self, era: int, state):
        """The chain-dependent state of era `era - 1` into era `era`."""
        if era == 1:
            return byron_to_shelley(state, self.genesis_nonce)
        if era == 2:
            return tpraos.translate_state(state)
        raise ValueError(f"no era {era} to translate into")

    def cross(self, state, from_era: int, to_era: int):
        while from_era < to_era:
            from_era += 1
            state = self.translate(from_era, state)
        return state

    @property
    def batch_rules(self) -> "CardanoRules":
        return CARDANO_RULES


@dataclass(frozen=True)
class CardanoViews:
    """Each era's ledger view (forecast-constant here, as the other
    deployments' are)."""

    byron: PBftLedgerView
    shelley: tpraos.TPraosLedgerView
    babbage: object  # views.LedgerView

    def era_view(self, era: int):
        return (self.byron, self.shelley, self.babbage)[era]

    def for_epoch(self, params: CardanoParams):
        """epoch -> the view of the era it lies in."""
        return lambda e: self.era_view(params.era_of_epoch(e))


class CardanoRules:
    """What the tools ask a chain's params (`batch.rules_of`): the
    protocol's name in the DB's config and its empty state. The window
    loop takes each piece's era's own rules (`era_rules`)."""

    name = "cardano"
    protocol = "Cardano"

    def initial_state(self) -> PBftState:
        return PBftState()


CARDANO_RULES = CardanoRules()


def make(byron: PBftEraParams, shelley: praos.PraosParams, d,
         babbage: praos.PraosParams | None, genesis_nonce: bytes,
         shelley_epoch: int, babbage_epoch: int) -> CardanoParams:
    """The eras placed on one clock: Byron from genesis, Shelley from
    epoch `shelley_epoch` (TriggerHardForkAtEpoch), Babbage from
    `babbage_epoch`; Shelley's and Babbage's epochs are `shelley`'s and
    `babbage`'s length (Babbage defaults to Shelley's numbers)."""
    from fractions import Fraction

    s_start = byron.first_slot_of(shelley_epoch)
    sh = replace(shelley, era_start_slot=s_start,
                 era_start_epoch=shelley_epoch)
    b_start = sh.first_slot_of(babbage_epoch)
    bb = replace(babbage or shelley, era_start_slot=b_start,
                 era_start_epoch=babbage_epoch)
    return CardanoParams(byron, tpraos.TPraosParams(sh, Fraction(d)), bb,
                         genesis_nonce)


def genesis_seed(delegate_cold_seed: bytes, j: int) -> bytes:
    """The seed of Byron genesis key j, whose heavy delegation
    certificate names delegate j (deterministic from the delegate's)."""
    import hashlib

    return hashlib.blake2b(b"byron-genesis-%d" % j + delegate_cold_seed,
                           digest_size=32).digest()


def make_views(pools, delegs, pool_view) -> CardanoViews:
    """The three eras' views over `pools` (the lottery's) and `delegs`
    (Byron's delegates in genesis-key order, Shelley's genesis
    delegates in the same order)."""
    from ..protocol.views import hash_vrf_vk

    return CardanoViews(
        byron=PBftLedgerView({d.vk_cold: j for j, d in enumerate(delegs)}),
        shelley=tpraos.TPraosLedgerView(
            pool_distr=pool_view.pool_distr,
            gen_delegs=tuple(tpraos.GenDeleg(d.vk_cold, hash_vrf_vk(d.vrf_vk))
                             for d in delegs)),
        babbage=pool_view,
    )


def forge(imm, params: CardanoParams, creds, views: CardanoViews, limit,
          trace=lambda s: None):
    """db-synthesizer over the three eras (Forging.hs per era, the HFC's
    clock between them): Byron's slots each a block by the delegate of
    the slot's turn (slot mod the delegates' count), signed by the native
    Ed25519 signer, with an EBB owning every Byron epoch's first slot;
    Shelley's by the overlay schedule or the 512-bit lottery (two
    draft-03 certificates); Babbage's by the lottery (one draft-03
    proof). -> (blocks, slots, the final chain-dependent state)."""
    from ..block.forge import forge_block
    from ..ops.host import fast
    from ..protocol import forge as forge_mod
    from ..protocol import nonces
    from ..protocol.leader import check_leader_value
    from ..protocol.praos import PraosIsLeader
    from . import byron_mock
    from .combinator import HardForkBlock

    by = params.byron
    dlg = views.byron.delegates
    by_creds = sorted((c for c in creds if c.vk_cold in dlg),
                      key=lambda c: dlg[c.vk_cold])
    certs = [byron_mock.delegation_cert(genesis_seed(c.cold_seed, j),
                                        c.vk_cold)
             for j, c in enumerate(by_creds)]
    sh_creds = forge_mod.tpraos_delegate_credentials(views.shelley, creds)
    st = PBftState()
    era_cur = 0
    prev = None
    block_no = n_blocks = slot = 0

    def done() -> bool:
        if limit.slots is not None and slot >= limit.slots:
            return True
        if limit.blocks is not None and n_blocks >= limit.blocks:
            return True
        return limit.epochs is not None and \
            params.epoch_of(slot) >= limit.epochs

    while not done():
        era = params.era_of_slot(slot)
        if era != era_cur:
            st = params.cross(st, era_cur, era)
            era_cur = era
        p = params.eras[era]
        if era == 0:
            if (slot - by.era_start_slot) % by.epoch_length == 0:
                blk = byron_mock.forge_ebb(slot=slot,
                                           block_no=max(0, block_no - 1),
                                           prev_hash=prev)
            else:
                j = slot % len(by_creds)
                blk = byron_mock.forge_block(
                    by_creds[j].cold_seed, slot=slot, block_no=block_no,
                    prev_hash=prev, cert=certs[j], sign=fast.ed25519_sign)
                st = PBftState((st.signers + ((slot, j),))[-by.window:])
        else:
            lv = views.era_view(era)
            ticked = praos.tick(p, lv, slot, st)
            eta0 = ticked.state.epoch_nonce
            if era == 1:
                el = forge_mod.elect_slot_tpraos(p, lv, creds, slot, eta0,
                                                 sh_creds)
                who = None if el is None else (creds[el.pool], el.is_leader)
            else:
                who = None
                for c in creds:
                    entry = lv.pool_distr.get(c.pool_id)
                    if entry is None:
                        continue
                    alpha = nonces.mk_input_vrf(slot, eta0)
                    proof = fast.ecvrf_prove_draft03(c.vrf_seed, alpha)
                    isl = PraosIsLeader(fast.ecvrf_proof_to_hash(proof),
                                        proof)
                    if check_leader_value(
                            nonces.vrf_leader_value(isl.vrf_output),
                            entry.stake, p.active_slot_coeff):
                        who = (c, isl)
                        break
            if who is None:
                slot += 1
                continue
            cred, isl = who
            blk = forge_block(p, cred, slot=slot, block_no=block_no,
                              prev_hash=prev, epoch_nonce=eta0,
                              is_leader=isl)
            rules = tpraos.TPRAOS_RULES if era == 1 else None
            st = (rules.reupdate(p, blk.header.to_view(), slot, ticked)
                  if rules else praos.reupdate(p, blk.header.to_view(),
                                               slot, ticked))
        hfb = HardForkBlock(era, blk)
        imm.append_block(slot, blk.block_no, hfb.hash_, hfb.bytes_)
        prev = hfb.hash_
        n_blocks += 1
        if not getattr(blk.header, "is_ebb", False):
            block_no += 1
        if n_blocks % 5000 == 0:
            trace(f"forged {n_blocks} blocks to slot {slot} (era {era})")
        slot += 1  # an EBB owns its epoch's first slot
    return n_blocks, slot, st


def host_replay(params: CardanoParams, views: CardanoViews, wins,
                res) -> None:
    """db_analyser's backend="host" over a chain over eras: the
    sequential reference, header by header (PBftProtocol.update,
    tpraos.update, praos.update, each after its tick), the state
    translated at each hard fork. Fills `res` (n_valid, error,
    final_state, era, crossings)."""
    from ..protocol.abstract import ConsensusError
    from ..protocol.instances import TickedPBftState

    st = PBftState()
    era_cur = 0
    crossings = []
    proto = params.byron.batch_rules.protocol_of(params.byron, views.byron)
    try:
        for ep in wins:
            era, cols = ep.era, ep.cols
            p = params.eras[era]
            for hv, slot in zip(cols.views(), cols.slot.tolist()):
                if era != era_cur:
                    before = st
                    st = params.cross(st, era_cur, era)
                    crossings.append((era_cur, era, before, st))
                    era_cur = era
                lv = views.era_view(era)
                if era == 0:
                    st = proto.update(hv, slot,
                                      TickedPBftState(st, lv.delegates))
                elif era == 1:
                    st = tpraos.update(p, hv, slot,
                                       tpraos.tick(p, lv, slot, st))
                else:
                    st = praos.update(p, hv, slot,
                                      praos.tick(p, lv, slot, st))
                res.n_valid += 1
    except (ConsensusError, praos.PraosValidationError) as e:
        res.error = e
    res.final_state, res.era = st, era_cur
    res.crossings = crossings or None


def deployment(seed: int, *, delegates: int = 7, pools: int = 1,
               byron_epoch_length: int = 21600, k: int = 2160,
               threshold=None, epoch_length: int = 43200, f=None, d=None,
               kes_depth: int = 7, slots_per_kes_period: int = 3600,
               max_kes_evolutions: int = 62, shelley_epoch: int = 1,
               babbage_epoch: int = 2, genesis_nonce: bytes | None = None,
               delegate_seed: int = 1000):
    """A Cardano deployment from a seed -> (CardanoParams, credentials
    [pools..., delegates...], CardanoViews): pool i is
    `fixtures.make_pool(seed + i)`, delegate j `make_pool(seed +
    delegate_seed + j)` in Byron (signing for genesis key j) and in
    Shelley (genesis delegate j)."""
    import hashlib
    from fractions import Fraction

    from ..testing import fixtures

    pool_creds = [fixtures.make_pool(seed + i, kes_depth=kes_depth)
                  for i in range(pools)]
    delegs = [fixtures.make_pool(seed + delegate_seed + j,
                                 kes_depth=kes_depth)
              for j in range(delegates)]
    byron = PBftEraParams(
        num_genesis_keys=delegates,
        threshold=Fraction(22, 100) if threshold is None else threshold,
        security_param=k, epoch_length=byron_epoch_length)
    shelley = praos.PraosParams(
        slots_per_kes_period=slots_per_kes_period,
        max_kes_evolutions=max_kes_evolutions, security_param=k,
        active_slot_coeff=Fraction(1, 2) if f is None else Fraction(f),
        epoch_length=epoch_length, kes_depth=kes_depth)
    if genesis_nonce is None:
        genesis_nonce = hashlib.blake2b(b"shelley-genesis-%d" % seed,
                                        digest_size=32).digest()
    params = make(byron, shelley, Fraction(1, 2) if d is None else d, None,
                  genesis_nonce, shelley_epoch, babbage_epoch)
    views = make_views(pool_creds, delegs,
                       fixtures.make_ledger_view(pool_creds))
    return params, [*pool_creds, *delegs], views


def parse_chunk(data, offsets):
    """The era-tagged blocks of a chunk at `offsets`, ONE native parse
    (native_loader.scan_eras, extract_byron, extract_headers) ->
    ([EraPiece, ...] in chain order: Byron runs as pbft.ByronColumns,
    Shelley-family runs as ViewColumns, each split where a row width
    steps; (each block's txs span start, end, and the hash its header
    commits to: the deep walk's body-hash compare))."""
    import numpy as np

    from .. import native_loader
    from ..protocol.batch import EraPiece
    from ..protocol.pbft import ByronColumns
    from ..protocol.views import ViewColumns

    pieces: list = []
    n = len(offsets)
    starts = np.zeros(n, np.int64)
    refs = np.zeros((n, 32), np.uint8)
    if not n:
        return pieces, (starts, starts, refs)
    era, off, ln = native_loader.scan_eras(data, offsets)
    cuts = [0, *(np.flatnonzero(np.diff(era)) + 1).tolist(), n]
    for a, b in zip(cuts, cuts[1:]):
        e, offs = int(era[a]), off[a:b]
        if data[int(offs[0])] == 0x83:  # [kind, header, txs]: Byron
            cols = native_loader.extract_byron(data, offs)
            runs = ByronColumns.pieces_from_native(cols, data)
            starts[a:b], refs[a:b] = cols["txs_off"], cols["tx_root"]
        else:
            hc = native_loader.extract_headers(data, offs)
            runs = ViewColumns.pieces_from_header_columns(hc)
            if runs is None:
                raise native_loader.MalformedBlock(a)
            starts[a:b], refs[a:b] = hc.header_end, hc.body_hash
        pieces.extend(EraPiece(e, r) for r in runs)
    return pieces, (starts, off + ln, refs)
