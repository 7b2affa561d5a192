"""The mixed-era composite: ByronMock(PBFT) → Shelley(TPraos) →
Babbage(Praos) [→ Conway(Praos) → Leios(Praos)] through the hard-fork
combinator — BASELINE config 5.

The optional 4th/5th eras (enabled by `conway_epochs`) are Praos-class
eras with GENUINELY different ledger parameters — Conway doubles the
epoch length and halves the active-slot coefficient, Leios changes both
again — so the HFC translations and the per-era epoch/threshold
arithmetic are non-trivial, mirroring the 7-era CardanoBlock
(Cardano/Block.hs:96) where every Shelley-family step changes ledger
params.

Reference: `CardanoBlock` (Cardano/Block.hs:96 — ByronBlock ':
CardanoShelleyEras), the `CanHardFork` pairwise translations
(Cardano/CanHardFork.hs:273), and `protocolInfoCardano` (Cardano/Node.hs)
collapsed to the three protocol classes that matter for consensus: one
PBFT era and the two Praos-class eras sharing the batched TPU crypto
backend. Era boundaries are config-driven (TriggerHardForkAtEpoch).

`synthesize` forges a chain crossing both transitions into an on-disk
ImmutableDB of era-tagged blocks; `revalidate` streams it back and
validates every segment with the chosen backend — the Praos-class
segments as fused device batches, the PBFT segment as a batched Ed25519
verify + host threshold fold.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, replace
from fractions import Fraction
from typing import Sequence

import numpy as np

from ..block import forge as praos_forge
from ..block.praos_block import Block as PraosBlock
from ..ops import ed25519_batch
from ..protocol import batch as pbatch
from ..protocol import nonces, praos, tpraos
from ..protocol.leader import check_leader_value
from ..protocol.instances import PBftParams, PBftProtocol, PraosProtocol
from ..protocol.views import hash_vrf_vk
from ..storage.immutable import ImmutableDB
from ..testing import fixtures
from .byron_mock import ByronMockBlock
from .combinator import (
    Era,
    HardForkBlock,
    HardForkLedger,
    HardForkProtocol,
    decode_block,
)
from .history import EraParams, summarize


@dataclass(frozen=True)
class CardanoMockConfig:
    """Genesis-file analog for the 3-era composite."""

    byron_epochs: int = 2
    byron_epoch_length: int = 40
    shelley_epochs: int = 2
    n_delegs: int = 2  # genesis delegates (byron signers = tpraos overlay)
    shelley_d: Fraction = Fraction(1, 2)
    shelley_f: Fraction = Fraction(1)
    babbage_f: Fraction = Fraction(1)
    epoch_length: int = 60  # shelley + babbage
    # 4th/5th eras (None = the classic 3-era composite). Conway doubles
    # the epoch length and changes f; Leios changes both again.
    conway_epochs: int | None = None  # babbage epochs before conway
    conway_f: Fraction = Fraction(1, 2)
    conway_epoch_length: int = 120
    leios_epochs: int | None = None  # conway epochs before leios
    leios_f: Fraction = Fraction(1)
    leios_epoch_length: int = 30
    k: int = 5
    kes_depth: int = 3
    # with n_delegs=2 round-robin and window k, each delegate signs
    # ~k/2 + 1 of any window — the threshold must clear that
    pbft_threshold: Fraction = Fraction(4, 5)
    shelley_initial_nonce: bytes = b"\x0b" * 32
    # LEDGERS IN THE LOOP: era 0 = real Byron-class UTxO+delegation
    # ledger, era 1 = real Shelley STS, eras 2+ = Mary-class multi-asset
    # rules (each with ITS era's epoch length via the era-relative
    # ShelleyGenesis) — synthesize forges real value-moving txs and
    # revalidate folds every block through the era ledgers (the
    # reference's db-analyser always replays the real ledger; opt-in so
    # the consensus-only bench path stays unchanged).
    with_ledgers: bool = False
    # THE FULL 7-ERA CHAIN (Cardano/Block.hs:96): byron → shelley →
    # allegra → mary → alonzo → babbage → conway, each Shelley-family
    # step a genuinely different RULE SET (timelocks / multi-asset /
    # phase-2 scripts / reference inputs / governance), TPraos through
    # alonzo and Praos from babbage on (Shelley/Eras.hs:85-97). Each
    # bounded era lasts `era_epochs`; conway is open-ended. Overrides
    # the conway_epochs/leios_epochs legacy knobs.
    seven_era: bool = False
    era_epochs: int = 2


class CardanoMock:
    """The assembled composite (protocolInfoCardano analog)."""

    def __init__(self, cfg: CardanoMockConfig):
        self.cfg = cfg
        self.delegs = [
            fixtures.make_pool(100 + i, kes_depth=cfg.kes_depth)
            for i in range(cfg.n_delegs)
        ]
        self.pools = [fixtures.make_pool(0, kes_depth=cfg.kes_depth)]
        base_view = fixtures.make_ledger_view(self.pools)
        self.praos_view = base_view
        self.tpraos_view = tpraos.TPraosLedgerView(
            pool_distr=base_view.pool_distr,
            gen_delegs=[
                tpraos.GenDeleg(d.vk_cold, hash_vrf_vk(d.vrf_vk))
                for d in self.delegs
            ],
        )
        common = dict(
            slots_per_kes_period=100,
            max_kes_evolutions=62,
            security_param=cfg.k,
            epoch_length=cfg.epoch_length,
            kes_depth=cfg.kes_depth,
        )
        self.tpraos_params = tpraos.TPraosParams(
            praos=praos.PraosParams(
                active_slot_coeff=cfg.shelley_f, **common
            ),
            decentralization=cfg.shelley_d,
        )
        self.praos_params = praos.PraosParams(
            active_slot_coeff=cfg.babbage_f, **common
        )
        self.conway_params = praos.PraosParams(
            active_slot_coeff=cfg.conway_f,
            **{**common, "epoch_length": cfg.conway_epoch_length},
        )
        self.leios_params = praos.PraosParams(
            active_slot_coeff=cfg.leios_f,
            **{**common, "epoch_length": cfg.leios_epoch_length},
        )
        self.pbft = PBftProtocol(
            PBftParams(
                num_genesis_keys=cfg.n_delegs,
                threshold=cfg.pbft_threshold,
                window=cfg.k,
                security_param=cfg.k,
            ),
            [d.vk_cold for d in self.delegs],
        )
        self.tpraos_proto = tpraos.TPraosProtocol(self.tpraos_params)
        nonce = cfg.shelley_initial_nonce
        if cfg.seven_era:
            self._init_seven_era(nonce)
            return
        era_params = [
            EraParams(cfg.byron_epoch_length, Fraction(1)),
            EraParams(cfg.epoch_length, Fraction(1)),
            EraParams(cfg.epoch_length, Fraction(1)),
        ]
        bounds = [
            cfg.byron_epochs,
            cfg.byron_epochs + cfg.shelley_epochs,
            None,
        ]
        if cfg.conway_epochs is not None:
            era_params.append(EraParams(cfg.conway_epoch_length, Fraction(1)))
            bounds[-1] = bounds[-2] + cfg.conway_epochs
            bounds.append(None)
            if cfg.leios_epochs is not None:
                era_params.append(
                    EraParams(cfg.leios_epoch_length, Fraction(1))
                )
                bounds[-1] = bounds[-2] + cfg.leios_epochs
                bounds.append(None)
        self.summary = summarize(Fraction(0), era_params, bounds)
        self.praos_proto = PraosProtocol(self.praos_params)
        self.eras = [
            Era("byron", self.pbft, ledger=None),
            Era(
                "shelley",
                self.tpraos_proto,
                ledger=None,
                # Byron's PBftState carries nothing Praos-shaped: Shelley
                # starts from the genesis nonce (CanHardFork.hs
                # translateLedgerStateByronToShelley + protocol init)
                translate_chain_dep=lambda _s: replace(
                    tpraos.TPraosState(), epoch_nonce=nonce
                ),
            ),
            Era(
                "babbage",
                self.praos_proto,
                ledger=None,
                translate_chain_dep=tpraos.translate_state,
            ),
        ]
        self.decoders = [
            ByronMockBlock.from_bytes,
            PraosBlock.from_bytes,
            PraosBlock.from_bytes,
        ]
        if cfg.conway_epochs is not None:
            # Praos -> Praos translation: the chain-dep state (nonces,
            # ocert counters) carries over verbatim; what CHANGES is the
            # era's ledger params (epoch length, f) — the translation is
            # non-trivial at the time layer, exactly like the
            # Shelley-family steps of CanHardFork.hs:273
            self.eras.append(
                Era(
                    "conway",
                    PraosProtocol(self.conway_params),
                    ledger=None,
                    translate_chain_dep=lambda s: s,
                )
            )
            self.decoders.append(PraosBlock.from_bytes)
            if cfg.leios_epochs is not None:
                self.eras.append(
                    Era(
                        "leios",
                        PraosProtocol(self.leios_params),
                        ledger=None,
                        translate_chain_dep=lambda s: s,
                    )
                )
                self.decoders.append(PraosBlock.from_bytes)
        self.hf = HardForkProtocol(self.eras, self.summary)
        self.inner_params = [
            None,
            self.tpraos_params,
            self.praos_params,
            self.conway_params,
            self.leios_params,
        ]
        self.hf_ledger = None
        if cfg.with_ledgers:
            self._init_ledgers()

    def _init_seven_era(self, nonce: bytes) -> None:
        """The full 7-era composite: era list, HFC summary, decoders,
        and (with_ledgers) the six real rule sets with their pairwise
        translations (CanHardFork.hs:273)."""
        cfg = self.cfg
        era_params = [EraParams(cfg.byron_epoch_length, Fraction(1))] + [
            EraParams(cfg.epoch_length, Fraction(1))
        ] * 6
        bounds: list = [cfg.byron_epochs]
        for _ in range(5):
            bounds.append(bounds[-1] + cfg.era_epochs)
        bounds.append(None)
        self.summary = summarize(Fraction(0), era_params, bounds)
        self.praos_proto = PraosProtocol(self.praos_params)
        self.eras = [
            Era("byron", self.pbft, ledger=None),
            Era(
                "shelley", self.tpraos_proto, ledger=None,
                translate_chain_dep=lambda _s: replace(
                    tpraos.TPraosState(), epoch_nonce=nonce
                ),
            ),
            Era("allegra", self.tpraos_proto, ledger=None,
                translate_chain_dep=lambda s: s),
            Era("mary", self.tpraos_proto, ledger=None,
                translate_chain_dep=lambda s: s),
            Era("alonzo", self.tpraos_proto, ledger=None,
                translate_chain_dep=lambda s: s),
            # the protocol CLASS changes here, like the reference's
            # Babbage step (TPraos -> Praos)
            Era("babbage", self.praos_proto, ledger=None,
                translate_chain_dep=tpraos.translate_state),
            Era("conway", self.praos_proto, ledger=None,
                translate_chain_dep=lambda s: s),
        ]
        self.decoders = [ByronMockBlock.from_bytes] + [
            PraosBlock.from_bytes
        ] * 6
        self.inner_params = [
            None,
            self.tpraos_params, self.tpraos_params, self.tpraos_params,
            self.tpraos_params,
            self.praos_params, self.praos_params,
        ]
        self.hf = HardForkProtocol(self.eras, self.summary)
        self.hf_ledger = None
        if cfg.with_ledgers:
            self._init_seven_era_ledgers()

    def _init_seven_era_ledgers(self) -> None:
        from ..ledger import allegra as al
        from ..ledger import alonzo as az
        from ..ledger import babbage as bb
        from ..ledger import conway as cw
        from ..ledger import mary as mary_mod
        from ..ledger.allegra import AllegraLedger
        from ..ledger.alonzo import AlonzoLedger
        from ..ledger.babbage import BabbageLedger
        from ..ledger.byron import ByronGenesis, ByronLedger, ByronPParams
        from ..ledger.conway import ConwayLedger
        from ..ledger.mary import MaryLedger
        from ..ledger.shelley import (
            PParams as ShPParams,
            ShelleyGenesis,
            ShelleyLedger,
        )

        cfg = self.cfg
        shelley_start = self.summary.eras[1].start.slot
        self.byron_ledger = ByronLedger(ByronGenesis(
            pparams=ByronPParams(
                min_fee_a=self.LEDGER_BYRON_FEE, min_fee_b=0
            ),
            genesis_keys=tuple(d.vk_cold for d in self.delegs),
            epoch_length=cfg.byron_epoch_length,
            security_param=cfg.k,
        ))

        def era_genesis(era_ix: int) -> ShelleyGenesis:
            bound = self.summary.eras[era_ix].start
            return ShelleyGenesis(
                pparams=ShPParams(min_fee_a=0, min_fee_b=0),
                epoch_length=cfg.epoch_length,
                stability_window=3 * cfg.k,
                era_start_slot=bound.slot,
                era_start_epoch=bound.epoch,
            )

        shelley_led = ShelleyLedger(era_genesis(1))
        allegra_led = AllegraLedger(era_genesis(2))
        mary_led = MaryLedger(era_genesis(3))
        alonzo_led = AlonzoLedger(era_genesis(4))
        babbage_led = BabbageLedger(era_genesis(5))
        conway_led = ConwayLedger(era_genesis(6))
        self.eras = [
            replace(self.eras[0], ledger=self.byron_ledger),
            replace(
                self.eras[1], ledger=shelley_led,
                translate_ledger_state=(
                    lambda st: shelley_led.translate_from_utxo_ledger(
                        st, at_slot=shelley_start
                    )
                ),
            ),
            replace(
                self.eras[2], ledger=allegra_led,
                # Shelley→Allegra: state identical (Coin stays Coin)
                translate_ledger_state=allegra_led.translate_from_shelley,
                translate_tx=al.translate_tx_from_shelley,
            ),
            replace(
                self.eras[3], ledger=mary_led,
                # Allegra→Mary: Coin widens to MaryValue
                translate_ledger_state=mary_led.translate_from_allegra,
                translate_tx=mary_mod.translate_tx_from_allegra,
            ),
            replace(
                self.eras[4], ledger=alonzo_led,
                # Mary→Alonzo: pparams widen with script economics
                translate_ledger_state=alonzo_led.translate_from_mary,
                translate_tx=az.translate_tx_from_mary,
            ),
            replace(
                self.eras[5], ledger=babbage_led,
                translate_ledger_state=babbage_led.translate_from_alonzo,
                translate_tx=bb.translate_tx_from_alonzo,
            ),
            replace(
                self.eras[6], ledger=conway_led,
                # Babbage→Conway: ConwayState (gov sub-state), PPUP
                # proposals dropped
                translate_ledger_state=conway_led.translate_from_babbage,
                translate_tx=cw.translate_tx_from_babbage,
            ),
        ]
        self.hf = HardForkProtocol(self.eras, self.summary)
        self.hf_ledger = HardForkLedger(self.eras, self.summary)

    def is_tpraos_era(self, era: int) -> bool:
        return isinstance(self.eras[era].protocol, tpraos.TPraosProtocol)

    # the well-known spending key of the ledger-backed composite: the
    # whole synthesized value chain rides on it (revalidate re-derives
    # the genesis outputs from it)
    LEDGER_SPEND_SEED = b"\x51" * 32
    LEDGER_GENESIS_COIN = 10_000_000
    LEDGER_BYRON_FEE = 10
    MINT_POLICY_SEED = b"\x52" * 32
    MINT_ASSET = b"MIX"

    def _init_ledgers(self) -> None:
        from ..ledger import mary as mary_mod
        from ..ledger.byron import ByronGenesis, ByronLedger, ByronPParams
        from ..ledger.mary import MaryLedger
        from ..ledger.shelley import (
            PParams as ShPParams,
            ShelleyGenesis,
            ShelleyLedger,
        )

        cfg = self.cfg
        shelley_start = self.summary.eras[1].start.slot
        self.byron_ledger = ByronLedger(ByronGenesis(
            pparams=ByronPParams(
                min_fee_a=self.LEDGER_BYRON_FEE, min_fee_b=0
            ),
            genesis_keys=tuple(d.vk_cold for d in self.delegs),
            epoch_length=cfg.byron_epoch_length,
            security_param=cfg.k,
        ))

        def era_genesis(era_ix: int, epoch_length: int) -> ShelleyGenesis:
            # era-relative epoch arithmetic from the HFC Summary bound
            # (the reference hands the ledger an EpochInfo the same way)
            bound = self.summary.eras[era_ix].start
            return ShelleyGenesis(
                pparams=ShPParams(min_fee_a=0, min_fee_b=0),
                epoch_length=epoch_length,
                stability_window=3 * cfg.k,
                era_start_slot=bound.slot,
                era_start_epoch=bound.epoch,
            )

        self.shelley_ledger = ShelleyLedger(
            era_genesis(1, cfg.epoch_length)
        )
        self.mary_ledger = MaryLedger(era_genesis(2, cfg.epoch_length))
        ledger_eras = [
            replace(self.eras[0], ledger=self.byron_ledger),
            replace(
                self.eras[1],
                ledger=self.shelley_ledger,
                # Byron->Shelley: carry the UTxO verbatim
                # (CanHardFork.hs translateLedgerStateByronToShelley)
                translate_ledger_state=(
                    lambda st: self.shelley_ledger.translate_from_utxo_ledger(
                        st, at_slot=shelley_start
                    )
                ),
            ),
            replace(
                self.eras[2],
                ledger=self.mary_ledger,
                # Shelley->Mary: Coin widens to MaryValue
                translate_ledger_state=self.mary_ledger.translate_from_shelley,
                translate_tx=mary_mod.translate_tx_from_shelley,
            ),
        ]
        # 4th/5th eras: Mary-class rules under the era's OWN epoch
        # length (the era-relative genesis makes a mid-chain epoch-length
        # change sound); the state carries over verbatim — what changes
        # is the rules' clock, like the reference's later-era steps
        for ix in range(3, len(self.eras)):
            ln = (cfg.conway_epoch_length if ix == 3
                  else cfg.leios_epoch_length)
            led = MaryLedger(era_genesis(ix, ln))
            ledger_eras.append(replace(
                self.eras[ix],
                ledger=led,
                translate_ledger_state=lambda st: st,
                translate_tx=lambda tx: tx,
            ))
        self.eras = ledger_eras
        self.hf = HardForkProtocol(self.eras, self.summary)
        self.hf_ledger = HardForkLedger(self.eras, self.summary)

    def ledger_genesis_state(self):
        """The HFState the ledger-backed chain starts from (Byron era,
        one genesis output held by the well-known spending key)."""
        from ..ledger.byron import addr_of
        from ..ops.host import ed25519 as host_ed25519

        addr = addr_of(host_ed25519.secret_to_public(self.LEDGER_SPEND_SEED))
        inner = self.byron_ledger.genesis_state(
            [(addr, self.LEDGER_GENESIS_COIN)]
        )
        return self.hf_ledger.genesis_state(inner)

    def view_for_era(self, era: int):
        if era == 0:
            return None
        return self.tpraos_view if self.is_tpraos_era(era) else self.praos_view


# ---------------------------------------------------------------------------
# Synthesis (db-synthesizer over the composite)
# ---------------------------------------------------------------------------


class _LedgerTxChain:
    """The value chain the ledger-backed composite forges: era-0 txs
    spend Byron UTxO (fee-paying, witnessed), the carried output is
    spent under the Shelley rules, and the Mary-class era mints a native
    asset that rides the rest of the chain — so revalidation proves
    era-0 value stayed spendable across BOTH translations."""

    def __init__(self, cm: "CardanoMock"):
        from ..ledger.byron import addr_of
        from ..ops.host import ed25519 as host_ed25519

        self.cm = cm
        self.vk = host_ed25519.secret_to_public(cm.LEDGER_SPEND_SEED)
        self.addr = addr_of(self.vk)
        self.outpoint = (bytes(32), 0)
        self.value = cm.LEDGER_GENESIS_COIN
        self.assets: dict = {}
        self.minted = False

    def tx_for(self, era: int) -> bytes:
        """One tx for the next block of `era`, dispatched on the era's
        LEDGER CLASS (the same builder serves the legacy 3/5-era chain,
        where the later eras run Mary-class rules, and the 7-era chain,
        where every era has its own rule set)."""
        from ..ledger.allegra import AllegraLedger
        from ..ledger.alonzo import AlonzoLedger
        from ..ledger.babbage import BabbageLedger
        from ..ledger.byron import ByronLedger
        from ..ledger.conway import ConwayLedger
        from ..ledger.mary import MaryLedger
        from ..ledger.shelley import ShelleyLedger

        led = self.cm.eras[era].ledger
        if isinstance(led, ByronLedger):
            return self._byron_tx()
        if isinstance(led, ConwayLedger):
            return self._conway_tx()
        if isinstance(led, BabbageLedger):
            return self._babbage_tx()
        if isinstance(led, AlonzoLedger):
            return self._alonzo_tx()
        if isinstance(led, MaryLedger):
            return self._mary_tx()
        if isinstance(led, AllegraLedger):
            return self._allegra_tx()
        assert isinstance(led, ShelleyLedger), led
        return self._shelley_tx()

    def _byron_tx(self) -> bytes:
        from ..ledger import byron as byron_led

        fee = self.cm.LEDGER_BYRON_FEE
        outs = [(self.addr, self.value - fee)]
        tx = byron_led.make_tx(
            [self.outpoint], outs, [self.cm.LEDGER_SPEND_SEED]
        )
        self.outpoint = (byron_led.tx_id_of([self.outpoint], outs), 0)
        self.value -= fee
        return tx

    def _shelley_tx(self) -> bytes:
        from ..ledger import shelley as shelley_mod

        tx = shelley_mod.encode_tx(
            [self.outpoint], [(self.addr, None, self.value)],
            fee=0, ttl=2**62,
        )
        self.outpoint = (shelley_mod.tx_id(tx), 0)
        return tx

    def _allegra_tx(self) -> bytes:
        from ..ledger import allegra as al
        from ..ledger import shelley as shelley_mod

        tx = al.encode_tx(
            [self.outpoint], [(self.addr, None, self.value)], fee=0,
        )
        self.outpoint = (shelley_mod.tx_id(tx), 0)
        return tx

    def _mary_tx(self) -> bytes:
        from ..ledger import mary as mary_mod
        from ..ledger import shelley as shelley_mod
        from ..ops.host import ed25519 as host_ed25519

        # mint once, then carry the asset along
        pid = mary_mod.policy_id(
            host_ed25519.secret_to_public(self.cm.MINT_POLICY_SEED)
        )
        if not self.minted:
            self.assets = {(pid, self.cm.MINT_ASSET): 1_000}
            outs = [(self.addr, None,
                     mary_mod.MaryValue(self.value, self.assets))]
            wit = mary_mod.make_mint_witness(
                self.cm.MINT_POLICY_SEED, [self.outpoint], outs, 0,
                (None, None), {self.cm.MINT_ASSET: 1_000},
            )
            tx = mary_mod.encode_tx([self.outpoint], outs, mint=[wit])
            self.minted = True
        else:
            outs = [(self.addr, None,
                     mary_mod.MaryValue(self.value, self.assets))]
            tx = mary_mod.encode_tx([self.outpoint], outs)
        self.outpoint = (shelley_mod.tx_id(tx), 0)
        return tx

    # phase-2 exercise state (alonzo era): 0 = not started, 1 = locked
    # (p2/collateral outpoints live), 2 = spent
    _p2_stage = 0
    _p2_out = None
    _coll_out = None
    _gov_stage = 0
    _gov_action_tid = None

    def _p2_script(self):
        from ..ledger import alonzo as az
        from ..utils import cbor

        script = az.plutus_script([4, [1], [2]])  # redeemer == datum
        datum = cbor.encode(b"open-sesame")
        return script, datum

    def _alonzo_tx(self) -> bytes:
        from ..ledger import allegra as al
        from ..ledger import alonzo as az
        from ..ledger import mary as mary_mod
        from ..ledger import shelley as shelley_mod
        from ..utils import cbor

        script, datum = self._p2_script()
        if self._p2_stage == 0:
            # split: carry + a phase-2 locked output + ada-only collateral
            saddr = al.script_addr(script)
            dh = az.datum_hash(datum)
            outs = [
                (self.addr, None,
                 mary_mod.MaryValue(self.value - 10, self.assets)),
                (saddr, None, 5, dh),
                (self.addr, None, 5),
            ]
            tx = az.encode_tx([self.outpoint], outs)
            tid = shelley_mod.tx_id(tx)
            self.outpoint = (tid, 0)
            self._p2_out = (tid, 1)
            self._coll_out = (tid, 2)
            self.value -= 10
            self._p2_stage = 1
            return tx
        if self._p2_stage == 1:
            # spend the locked output under the script (phase 2 runs
            # during revalidation, incl. the ledger replay)
            tx = az.encode_tx(
                [self._p2_out], [(self.addr, None, 4)],
                collateral=[self._coll_out],
                scripts=[script], datums=[datum],
                redeemers=[(0, 0, cbor.decode(datum))],
                budget=100, fee=1,
            )
            self._p2_stage = 2
            return tx
        tx = az.encode_tx(
            [self.outpoint],
            [(self.addr, None, mary_mod.MaryValue(self.value, self.assets))],
        )
        self.outpoint = (shelley_mod.tx_id(tx), 0)
        return tx

    def _babbage_tx(self) -> bytes:
        from ..ledger import babbage as bb
        from ..ledger import mary as mary_mod
        from ..ledger import shelley as shelley_mod

        tx = bb.encode_tx(
            [self.outpoint],
            [(self.addr, None, mary_mod.MaryValue(self.value, self.assets))],
        )
        self.outpoint = (shelley_mod.tx_id(tx), 0)
        return tx

    DREP_CRED = b"composite-drep-cred-28-bytes"  # 28 bytes

    def _conway_tx(self) -> bytes:
        from ..ledger import conway as cw
        from ..ledger import mary as mary_mod
        from ..ledger import shelley as shelley_mod

        if self._gov_stage == 0:
            # register a DRep and propose a (harmless) param change —
            # deposits ride the conservation equation; with no stake
            # delegated the action expires and refunds to treasury
            pp = cw.ConwayPParams()
            dep = pp.drep_deposit + pp.gov_action_deposit
            tx = cw.encode_tx(
                [self.outpoint],
                [(self.addr, None,
                  mary_mod.MaryValue(self.value - dep, self.assets))],
                certs=[[7, self.DREP_CRED]],
                proposals=[(self.DREP_CRED, [0, {b"min_fee_b": 0}])],
            )
            tid = shelley_mod.tx_id(tx)
            self.outpoint = (tid, 0)
            self.value -= dep
            self._gov_action_tid = tid
            self._gov_stage = 1
            return tx
        if self._gov_stage == 1:
            # the registered DRep votes yes (zero stake — exercises the
            # vote path without ratifying)
            tx = cw.encode_tx(
                [self.outpoint],
                [(self.addr, None,
                  mary_mod.MaryValue(self.value, self.assets))],
                votes=[(self.DREP_CRED, self._gov_action_tid, 0, True)],
            )
            self.outpoint = (shelley_mod.tx_id(tx), 0)
            self._gov_stage = 2
            return tx
        tx = cw.encode_tx(
            [self.outpoint],
            [(self.addr, None, mary_mod.MaryValue(self.value, self.assets))],
        )
        self.outpoint = (shelley_mod.tx_id(tx), 0)
        return tx


def synthesize(path: str, cfg: CardanoMockConfig, n_slots: int, chunk_size: int = 500):
    """Forge a chain crossing both era boundaries; returns block count."""
    from . import byron_mock

    cm = CardanoMock(cfg)
    os.makedirs(path, exist_ok=True)
    imm = ImmutableDB(os.path.join(path, "immutable"), chunk_size=chunk_size)
    if not imm.is_empty:
        raise RuntimeError(f"refusing to forge into non-empty DB at {path}")

    st = cm.hf.initial_state()
    chain = _LedgerTxChain(cm) if cfg.with_ledgers else None
    lst = cm.ledger_genesis_state() if cfg.with_ledgers else None
    prev: bytes | None = None
    block_no = 0
    n_blocks = 0
    for slot in range(n_slots):
        era = cm.hf.era_of_slot(slot)
        ticked = cm.hf.tick(cm.view_for_era(era), slot, st)
        if era == 0:
            if slot % cfg.byron_epoch_length == 0:
                # each Byron epoch opens with an EBB (Byron/EBBs.hs):
                # unsigned, empty, block number NOT advanced
                ebb = byron_mock.forge_ebb(
                    slot=slot, block_no=max(0, block_no - 1), prev_hash=prev
                )
                hfb = HardForkBlock(era, ebb)
                imm.append_block(slot, ebb.block_no, hfb.hash_, hfb.bytes_)
                st = cm.hf.reupdate(ebb.header.to_view(), slot, ticked)
                if lst is not None:
                    lst = cm.hf_ledger.tick_then_apply(lst, hfb)
                prev = hfb.hash_
                n_blocks += 1
                continue  # the EBB owns the epoch's first slot
            j = slot % cfg.n_delegs
            blk = byron_mock.forge_block(
                cm.delegs[j].cold_seed,
                slot=slot, block_no=block_no, prev_hash=prev,
                txs=(
                    (chain.tx_for(0),) if chain is not None
                    else (b"byron-tx-%d" % slot,)
                ),
            )
        else:
            params = cm.inner_params[era]
            eta0 = ticked.inner.state.epoch_nonce
            is_leader = None  # Praos: forge_block proves its one proof
            if cm.is_tpraos_era(era):
                a = tpraos.overlay_slot_assignment(
                    cm.tpraos_params, cfg.n_delegs, slot
                )
                if a is not None:
                    active, j = a
                    if not active:
                        continue  # inactive overlay slot stays empty
                    creds = cm.delegs[j]
                else:
                    creds = cm.pools[0]
                inner_params = cm.tpraos_params.praos
                # a Shelley-era header: the nonce and the leader
                # certificate (f = 1 in these eras: the pool wins every
                # lottery slot)
                is_leader = tpraos.prove_certificates(
                    creds.vrf_seed, slot, eta0)
                if a is None and inner_params.active_slot_coeff != 1:
                    # f < 1: the 512-bit lottery on the raw leader output
                    entry = cm.tpraos_view.pool_distr.get(creds.pool_id)
                    if entry is None or not check_leader_value(
                        int.from_bytes(is_leader.vrf_leader_output, "big"),
                        entry.stake, inner_params.active_slot_coeff,
                        tpraos.LEADER_VALUE_MAX,
                    ):
                        continue
            else:
                creds = cm.pools[0]
                inner_params = params
                if inner_params.active_slot_coeff != 1:
                    # f < 1 era: consult the real leader lottery
                    win = praos.check_is_leader(
                        inner_params,
                        fixtures.can_be_leader(creds),
                        slot,
                        praos.TickedPraosState(
                            replace(
                                praos.PraosState(), epoch_nonce=eta0
                            ),
                            cm.praos_view,
                        ),
                    )
                    if win is None:
                        continue
            blk = praos_forge.forge_block(
                inner_params, creds,
                slot=slot, block_no=block_no, prev_hash=prev,
                epoch_nonce=eta0, is_leader=is_leader,
                txs=(
                    (chain.tx_for(era),) if chain is not None
                    else (b"tx-%d" % slot,)
                ),
            )
        hfb = HardForkBlock(era, blk)
        imm.append_block(slot, block_no, hfb.hash_, hfb.bytes_)
        st = cm.hf.reupdate(blk.header.to_view(), slot, ticked)
        if lst is not None:
            lst = cm.hf_ledger.tick_then_apply(lst, hfb)
        prev = hfb.hash_
        block_no += 1
        n_blocks += 1
    imm.flush()
    return n_blocks


# ---------------------------------------------------------------------------
# Revalidation (db-analyser --only-validation over the composite)
# ---------------------------------------------------------------------------


@dataclass
class MixedResult:
    n_blocks: int = 0
    n_valid: int = 0
    error: Exception | None = None
    final_state: object | None = None
    per_era: dict | None = None
    final_ledger_state: object | None = None  # with_ledgers only


def _bucket_pad(items, fill):
    n = pbatch.bucket_size(len(items))
    return items + [fill] * (n - len(items)), len(items)


def _validate_pbft_segment(proto: PBftProtocol, headers, st, backend: str):
    """Byron segment: signatures batched (device Ed25519 kernel or the
    native C++ verifier), delegate-membership + window threshold folded
    sequentially on host — the exact PBft rule order (Protocol/PBFT.hs
    :284: delegate check, signature, threshold)."""
    from ..protocol.instances import PBFT_BOUNDARY_VIEW

    views = [h.to_view() for h in headers]
    if backend == "host":
        for i, (h, view) in enumerate(zip(headers, views)):
            try:
                st = proto.update(view, h.slot, proto.tick(None, h.slot, st))
            except Exception as e:
                return st, i, e
        return st, len(views), None

    # EBBs (PBftValidateBoundary) carry no signature: exclude their
    # lanes from the batch and skip them in the host fold below
    regular = [v for v in views if v is not PBFT_BOUNDARY_VIEW]
    if backend == "native":
        from .. import native_loader as nl

        reg_ok = [
            nl.native_ed25519_verify(
                v.issuer_vk, v.signature, v.signed_bytes
            )
            for v in regular
        ]
    elif regular:
        padded, n = _bucket_pad(regular, regular[0])
        ok = ed25519_batch.verify_batch(
            [v.issuer_vk for v in padded],
            [v.signature for v in padded],
            [v.signed_bytes for v in padded],
        )
        reg_ok = list(ok[:n])
    else:
        reg_ok = []
    it = iter(reg_ok)
    sig_ok = [True if v is PBFT_BOUNDARY_VIEW else next(it) for v in views]
    for i, (h, view) in enumerate(zip(headers, views)):
        try:
            if view is PBFT_BOUNDARY_VIEW:
                continue  # boundary: no state change (PBFT.hs:326)
            st = proto.apply_checked_sig(st, h.slot, view.issuer_vk, sig_ok[i])
        except Exception as e:
            return st, i, e
    return st, len(views), None


def revalidate(path: str, cfg: CardanoMockConfig, backend: str = "device") -> MixedResult:
    """Full mixed-era revalidation (config 5: Cardano/CanHardFork.hs:273
    semantics): decode era-tagged blocks, walk the telescope, validate
    each era segment with its protocol — Praos-class eras through the
    batched backend."""
    cm = CardanoMock(cfg)
    # repair=False: this analysis holds no DB lock (direct embedder —
    # COVERAGE.md §5.17 honest gap), so it must never mutate the store;
    # a lagging index is reparsed in memory only
    imm = ImmutableDB(os.path.join(path, "immutable"), repair=False)
    res = MixedResult(per_era={})

    blocks = [decode_block(raw, cm.decoders) for _e, raw in imm.stream_all()]
    res.n_blocks = len(blocks)
    st = cm.hf.initial_state()
    i = 0
    while i < len(blocks):
        era = blocks[i].era
        j = i
        while j < len(blocks) and blocks[j].era == era:
            j += 1
        seg = blocks[i:j]
        # walk the telescope into this era (translations)
        st = cm.hf._cross_eras(st, era)
        proto = cm.eras[era].protocol
        if era == 0:
            inner, n_ok, err = _validate_pbft_segment(
                proto, [b.header for b in seg], st.inner, backend
            )
            st = replace(st, inner=inner)
        else:
            params = cm.inner_params[era]
            lview = cm.view_for_era(era)
            inner = st.inner
            n_ok = 0
            err = None
            # epoch-segmented batches inside the era segment
            s0 = 0
            hvs = [b.header.to_view() for b in seg]
            inner_backend = "host-fold" if backend == "host" else backend
            while s0 < len(hvs):
                s1 = s0
                ep = params.epoch_of(hvs[s0].slot)
                while s1 < len(hvs) and params.epoch_of(hvs[s1].slot) == ep:
                    s1 += 1
                ticked = proto.tick(lview, hvs[s0].slot, inner)
                b = proto.validate_batch(
                    ticked, hvs[s0:s1], backend=inner_backend
                )
                inner = b.state
                n_ok += b.n_valid
                if b.error is not None:
                    err = b.error
                    break
                s0 = s1
            st = replace(st, inner=inner)
        res.n_valid += n_ok
        res.per_era[cm.eras[era].name] = res.per_era.get(cm.eras[era].name, 0) + n_ok
        if err is not None:
            res.error = err
            break
        i = j
    res.final_state = st
    if cfg.with_ledgers and res.error is None:
        # the ledger replay (db-analyser always does this; opt-in here):
        # full rule application per block, translations at era crossings;
        # a ledger-rule failure reports through MixedResult.error exactly
        # like a consensus-segment failure
        from ..ledger.abstract import LedgerError

        lst = cm.ledger_genesis_state()
        try:
            for blk in blocks:
                lst = cm.hf_ledger.tick_then_apply(lst, blk)
        except LedgerError as e:
            res.error = e
        res.final_ledger_state = lst
    return res
