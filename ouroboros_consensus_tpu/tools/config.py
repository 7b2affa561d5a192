"""JSON node config + genesis loading for the CLI tools.

Reference: `ouroboros-consensus-cardano/src/tools/Cardano/Node/`
(Types.hs + Protocol/{Byron,Shelley,Alonzo,Conway}.hs) — db-analyser and
db-synthesizer read a `config.json` pointing at per-era genesis files and
credential files (fixture: `test/tools-test/disk/config/config.json`),
from which `mkProtocolInfo` assembles the protocol configuration.

This framework's single-protocol analog:

  config.json            {"Protocol": "Praos" | "TPraos",
                          "GenesisFile": "genesis.json",
                          "CredentialsFile": "credentials.json"?}
  genesis.json           protocol parameters + pool distribution
                         (verification side: what validation needs);
                         a TPraos chain's also holds `decentralisation`
                         [num, den] and `genDelegs` (cold key + VRF key
                         hash of each genesis delegate, in order)
  credentials.json       signing seeds per pool (synthesizer side only,
                         the analog of the bulk credentials file
                         DBSynthesizer/Run.hs loads)

`write_genesis_files` is the inverse, emitted by db_synthesizer so a
synthesized chain carries its own config — the tools-test pipeline shape
(synthesize with config → analyse with the same config).
"""

from __future__ import annotations

import json
import os
from fractions import Fraction

from ..protocol import batch as pbatch
from ..protocol import tpraos
from ..protocol.praos import PraosParams
from ..protocol.views import IndividualPoolStake, LedgerView
from ..testing.fixtures import PoolCredentials


def _params_to_json(p: PraosParams) -> dict:
    return {
        "slotsPerKESPeriod": p.slots_per_kes_period,
        "maxKESEvolutions": p.max_kes_evolutions,
        "securityParam": p.security_param,
        "activeSlotsCoeff": [
            p.active_slot_coeff.numerator, p.active_slot_coeff.denominator
        ],
        "epochLength": p.epoch_length,
        "kesDepth": p.kes_depth,
    }


def _params_from_json(o: dict) -> PraosParams:
    num, den = o["activeSlotsCoeff"]
    return PraosParams(
        slots_per_kes_period=o["slotsPerKESPeriod"],
        max_kes_evolutions=o["maxKESEvolutions"],
        security_param=o["securityParam"],
        active_slot_coeff=Fraction(num, den),
        epoch_length=o["epochLength"],
        kes_depth=o["kesDepth"],
    )


def write_genesis_files(
    dir_path: str,
    params: PraosParams,
    lview: LedgerView,
    pools: list[PoolCredentials] | None = None,
) -> str:
    """Write config.json + genesis.json (+ credentials.json when signing
    material is provided). Returns the config.json path."""
    os.makedirs(dir_path, exist_ok=True)
    rules = pbatch.rules_of(params)
    genesis = {
        "params": _params_to_json(params),
        "poolDistr": [
            {
                "poolId": pid.hex(),
                "stake": [ips.stake.numerator, ips.stake.denominator],
                "vrfKeyHash": ips.vrf_key_hash.hex(),
            }
            for pid, ips in sorted(lview.pool_distr.items())
        ],
    }
    if rules.overlay:
        d = params.decentralization
        genesis["decentralisation"] = [d.numerator, d.denominator]
        genesis["genDelegs"] = [
            {"vkCold": g.vk_cold.hex(), "vrfKeyHash": g.vrf_key_hash.hex()}
            for g in lview.gen_delegs
        ]
    with open(os.path.join(dir_path, "genesis.json"), "w") as f:
        json.dump(genesis, f, indent=1, sort_keys=True)
    config = {"Protocol": rules.protocol, "GenesisFile": "genesis.json"}
    if pools is not None:
        creds = [
            {
                "coldSeed": p.cold_seed.hex(),
                "vrfSeed": p.vrf_seed.hex(),
                "kesSeed": p.kes_seed.hex(),
                "kesDepth": p.kes_depth,
            }
            for p in pools
        ]
        with open(os.path.join(dir_path, "credentials.json"), "w") as f:
            json.dump(creds, f, indent=1)
        config["CredentialsFile"] = "credentials.json"
    cpath = os.path.join(dir_path, "config.json")
    with open(cpath, "w") as f:
        json.dump(config, f, indent=1, sort_keys=True)
    return cpath


def load_config(config_path: str):
    """mkProtocolInfo analog: (params, ledger_view, pools|None). The
    config's `Protocol` says what the params and the view are: a TPraos
    chain's are `TPraosParams` / `TPraosLedgerView`, and every tool that
    takes them (db_synthesizer.synthesize, db_analyser.revalidate)
    forges or validates by that protocol's rules."""
    base = os.path.dirname(os.path.abspath(config_path))
    with open(config_path) as f:
        config = json.load(f)
    protocol = config.get("Protocol", "Praos")
    if protocol not in ("Praos", "TPraos"):
        raise ValueError(
            f"unsupported Protocol {protocol!r}: this tool takes "
            '"Praos" and "TPraos"'
        )
    with open(os.path.join(base, config["GenesisFile"])) as f:
        genesis = json.load(f)
    params = _params_from_json(genesis["params"])
    pool_distr = {
        bytes.fromhex(e["poolId"]): IndividualPoolStake(
            Fraction(e["stake"][0], e["stake"][1]),
            bytes.fromhex(e["vrfKeyHash"]),
        )
        for e in genesis["poolDistr"]
    }
    if protocol == "TPraos":
        params = tpraos.TPraosParams(
            params, Fraction(*genesis["decentralisation"]))
        lview = tpraos.TPraosLedgerView(
            pool_distr=pool_distr,
            gen_delegs=tuple(
                tpraos.GenDeleg(bytes.fromhex(g["vkCold"]),
                                bytes.fromhex(g["vrfKeyHash"]))
                for g in genesis["genDelegs"]
            ),
        )
    else:
        lview = LedgerView(pool_distr=pool_distr)
    pools = None
    if "CredentialsFile" in config:
        with open(os.path.join(base, config["CredentialsFile"])) as f:
            creds = json.load(f)
        pools = [
            PoolCredentials(
                cold_seed=bytes.fromhex(c["coldSeed"]),
                vrf_seed=bytes.fromhex(c["vrfSeed"]),
                kes_seed=bytes.fromhex(c["kesSeed"]),
                kes_depth=c["kesDepth"],
            )
            for c in creds
        ]
    return params, lview, pools


# ---------------------------------------------------------------------------
# TextEnvelope credential files (Cardano.Api shim)
# ---------------------------------------------------------------------------

# The reference's tools read node credentials from TextEnvelope JSON
# files ({"type", "description", "cborHex"} — src/tools/Cardano/Api/,
# KeysShelley.hs / SerialiseTextEnvelope): one file per key. The same
# format here, with this framework's type strings.

_ENVELOPE_TYPES = {
    "cold": "ColdSigningKey_ed25519",
    "vrf": "VrfSigningKey_ecvrf25519",
    "kes": "KesSigningKey_compactsum",
}


def write_text_envelopes(dir_path: str, pool: PoolCredentials) -> dict:
    """cold.skey / vrf.skey / kes.skey, one TextEnvelope JSON each
    (operational certificates are issued at runtime from these keys —
    protocol/hotkey.issue_ocert). Returns {kind: path}."""
    from ..utils import cbor as _cbor

    os.makedirs(dir_path, exist_ok=True)
    paths = {}
    seeds = {"cold": pool.cold_seed, "vrf": pool.vrf_seed, "kes": pool.kes_seed}
    for kind, seed in seeds.items():
        payload = (
            _cbor.encode([seed, pool.kes_depth]) if kind == "kes"
            else _cbor.encode(seed)
        )
        env = {
            "type": _ENVELOPE_TYPES[kind],
            "description": f"{kind} signing key",
            "cborHex": payload.hex(),
        }
        p = os.path.join(dir_path, f"{kind}.skey")
        with open(p, "w") as f:
            json.dump(env, f, indent=1)
        paths[kind] = p
    return paths


def read_text_envelope(path: str, expected_type: str) -> bytes:
    """One envelope -> raw CBOR payload; type string is CHECKED (the
    reference fails on a type mismatch, SerialiseTextEnvelope)."""
    with open(path) as f:
        env = json.load(f)
    if env.get("type") != expected_type:
        raise ValueError(
            f"{path}: envelope type {env.get('type')!r}, "
            f"expected {expected_type!r}"
        )
    return bytes.fromhex(env["cborHex"])


def load_pool_from_envelopes(dir_path: str) -> PoolCredentials:
    from ..utils import cbor as _cbor

    cold = _cbor.decode(
        read_text_envelope(
            os.path.join(dir_path, "cold.skey"), _ENVELOPE_TYPES["cold"]
        )
    )
    vrf = _cbor.decode(
        read_text_envelope(
            os.path.join(dir_path, "vrf.skey"), _ENVELOPE_TYPES["vrf"]
        )
    )
    kes_seed, kes_depth = _cbor.decode(
        read_text_envelope(
            os.path.join(dir_path, "kes.skey"), _ENVELOPE_TYPES["kes"]
        )
    )
    return PoolCredentials(
        cold_seed=bytes(cold), vrf_seed=bytes(vrf),
        kes_seed=bytes(kes_seed), kes_depth=kes_depth,
    )


# ---------------------------------------------------------------------------
# Shelley genesis files (the reference's shelley-genesis.json shape:
# sgProtocolParams / sgInitialFunds / sgStaking — Node config points at
# it per era; cardano-node ShelleyGenesis + protocolInfoShelley)
# ---------------------------------------------------------------------------


def _frac_json(f):
    from fractions import Fraction

    if isinstance(f, Fraction):
        return [f.numerator, f.denominator]
    return f


def write_shelley_genesis(
    dir_path: str,
    genesis,  # ledger.shelley.ShelleyGenesis
    initial_funds,  # [(payment, stake|None, coin)]
    initial_pools=(),  # [shelley.PoolParams]
    initial_delegations=(),  # [(cred, pool_id)]
    filename: str = "shelley-genesis.json",
) -> str:
    """Write a Shelley genesis file (sgInitialFunds + sgStaking)."""
    from ..ledger import shelley as sh

    pp = genesis.pparams
    obj = {
        "protocolParams": {
            f: _frac_json(getattr(pp, f)) for f in sh.PParams.UPDATABLE
        },
        "epochLength": genesis.epoch_length,
        "stabilityWindow": genesis.stability_window,
        "maxSupply": genesis.max_supply,
        "updateQuorum": genesis.update_quorum,
        "genDelegs": [d.hex() for d in genesis.genesis_delegates],
        "initialFunds": [
            [p.hex(), None if s is None else s.hex(), c]
            for p, s, c in initial_funds
        ],
        "staking": {
            "pools": [
                {
                    "poolId": p.pool_id.hex(),
                    "vrfKeyHash": p.vrf_hash.hex(),
                    "pledge": p.pledge,
                    "cost": p.cost,
                    "margin": _frac_json(p.margin),
                    "rewardCred": p.reward_cred.hex(),
                    "owners": [o.hex() for o in p.owners],
                }
                for p in initial_pools
            ],
            "stake": [
                [c.hex(), pid.hex()] for c, pid in initial_delegations
            ],
        },
    }
    path = os.path.join(dir_path, filename)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1, sort_keys=True)
    return path


def load_shelley_genesis(path: str):
    """-> (ShelleyLedger, genesis ShelleyState) — protocolInfoShelley."""
    from fractions import Fraction

    from ..ledger import shelley as sh

    with open(path) as f:
        obj = json.load(f)
    pp_kw = {}
    for k, v in obj["protocolParams"].items():
        pp_kw[k] = Fraction(v[0], v[1]) if isinstance(v, list) else int(v)
    genesis = sh.ShelleyGenesis(
        pparams=sh.PParams(**pp_kw),
        epoch_length=int(obj["epochLength"]),
        stability_window=int(obj["stabilityWindow"]),
        max_supply=int(obj["maxSupply"]),
        genesis_delegates=tuple(
            bytes.fromhex(d) for d in obj.get("genDelegs", [])
        ),
        update_quorum=int(obj.get("updateQuorum", 1)),
    )
    ledger = sh.ShelleyLedger(genesis)
    staking = obj.get("staking", {})
    pools = tuple(
        sh.PoolParams(
            pool_id=bytes.fromhex(p["poolId"]),
            vrf_hash=bytes.fromhex(p["vrfKeyHash"]),
            pledge=int(p["pledge"]),
            cost=int(p["cost"]),
            margin=(
                Fraction(p["margin"][0], p["margin"][1])
                if isinstance(p["margin"], list) else Fraction(p["margin"])
            ),
            reward_cred=bytes.fromhex(p["rewardCred"]),
            owners=tuple(bytes.fromhex(o) for o in p.get("owners", [])),
        )
        for p in staking.get("pools", [])
    )
    delegations = tuple(
        (bytes.fromhex(c), bytes.fromhex(pid))
        for c, pid in staking.get("stake", [])
    )
    state = ledger.genesis_state(
        [
            (bytes.fromhex(p), None if s is None else bytes.fromhex(s), c)
            for p, s, c in obj.get("initialFunds", [])
        ],
        initial_pools=pools,
        initial_delegations=delegations,
    )
    return ledger, state
