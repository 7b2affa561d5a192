"""Traffic kind `replay_cardano`: kind `replay` over a Cardano chain that
crosses two hard forks from genesis: Byron (PBFT over seven genesis
delegates, an EBB an epoch), Shelley (TPraos at d = 1/2), Babbage (Praos,
draft-03 proofs), era-tagged blocks, ONE `validate_stream` a replay.

What differs from `traffic/replay.py`, and only that, is here: the
deployment (the configuration's per-era numbers make the program's
`hardfork/cardano.CardanoParams` / `CardanoViews` and the reference's
`CParams`), the plain reference (`benchmark/reference/cardano.py`, which
takes `pbft.py`, `tpraos.py` and `praos.py`), the six corruptions, each in
a production-width window of its own era's headers, the state compared on
each side of both hard forks, and the stage names of the check that
nothing hid the chip (`finish_pbft`, `finish_tp`, `finish`). The timed path
(`replay.replay_once`: the program's normal entry point, which takes the
protocol from the params), the caches, the seams and the rule of the
comparison are `replay`'s, imported, as are the TPraos views' and
states' conversions (`replay_tpraos`); `_forge_in_child`, `make_inputs`,
`nothing_hid_the_chip`, `judge` and `run` are copies that name this
module's reference, stages and forging child, number for number. Folding the replay kinds
into one is a `benchmark` issue's (PERF.md section 7).

The control of `correct` for this kind:

    python3 -m benchmark.traffic.replay_cardano --control <cell> <seeds>

replays on the chip with every Byron lane's signature verdict forced true
in `finish_pbft`'s outputs, and has to fail `wrong_header_mismatches`,
and that alone.
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import os
import random
import shutil
import subprocess
import sys
import time
from fractions import Fraction

from benchmark import harness, replay_rate, xplane
from benchmark.harness import FailedRun, emit
from benchmark.reference import cardano as ref
from benchmark.reference import pbft as rpbft
from benchmark.reference import praos as rpraos
from benchmark.reference import tpraos as rtpraos
from benchmark.traffic import replay as _replay
from benchmark.traffic import replay_tpraos as _tp
from benchmark.traffic.replay import (  # noqa: F401 - run.py, tests
    BENCH, CACHE, FORGE_TIMEOUT_S, Inputs, _flip, check_seams, error_doc,
    every_program_stored, mix_of, place_caches, replay_once, state_doc)

# the per-lane stage programs of the three eras' packed dispatch
PK_STAGES = ("unpack_", "ed@", "kes@", "vrf@", "finish@", "finish_tp@",
             "finish_pbft@", "reduce@")
DELEGATE_SEED = 1000  # delegate j is make_pool(seed + 1000 + j)
STRANGER_SEED = 7000  # the key outside the delegation map


# ---------------------------------------------------------------------------
# the input, from the seed
# ---------------------------------------------------------------------------


def _numbers(cell, rehearsal: bool) -> dict:
    """The deployment's numbers: the configuration's, and in a rehearsal
    the mix's toy `scale` over them."""
    cfg = cell.config
    p = cfg["protocol"]
    n = {"byron_epoch_length": cfg["byron_epoch_length"],
         "epoch_length": p["epoch_length"],
         "security_param": p["security_param"],
         "pbft_threshold": cfg["pbft_threshold"],
         "slots_per_kes_period": p["slots_per_kes_period"],
         "max_kes_evolutions": p["max_kes_evolutions"],
         "kes_depth": p["kes_depth"],
         "active_slot_coeff": p["active_slot_coeff"],
         "decentralisation": cfg["decentralisation"],
         "genesis_delegates": cfg["genesis_delegates"],
         "pools": cfg["pools"],
         "shelley_epoch": cfg["shelley_epoch"],
         "babbage_epoch": cfg["babbage_epoch"]}
    if rehearsal:
        n.update(mix_of(cell, True).get("scale", {}))
    return n


def genesis_nonce(seed: int) -> bytes:
    """The Shelley genesis nonce of the seed's chain."""
    return hashlib.blake2b(b"shelley-genesis-%d" % seed,
                           digest_size=32).digest()


def _deployment(cell, seed: int, rehearsal: bool):
    """-> (params, every credential, views, the chain's home), from the
    seed: the pool is `make_pool(seed)`, delegate j `make_pool(seed + 1000
    + j)` in Byron and in Shelley."""
    try:
        from ouroboros_consensus_tpu.hardfork import cardano
    except ImportError:
        raise FailedRun("this program validates no Cardano chain on the "
                        "normal path (hardfork/cardano): it cannot run "
                        "this configuration", rc=1) from None
    n = _numbers(cell, rehearsal)
    params, creds, views = cardano.deployment(
        seed, delegates=n["genesis_delegates"], pools=n["pools"],
        byron_epoch_length=n["byron_epoch_length"], k=n["security_param"],
        threshold=Fraction(n["pbft_threshold"]),
        epoch_length=n["epoch_length"], f=Fraction(n["active_slot_coeff"]),
        d=Fraction(n["decentralisation"]), kes_depth=n["kes_depth"],
        slots_per_kes_period=n["slots_per_kes_period"],
        max_kes_evolutions=n["max_kes_evolutions"],
        shelley_epoch=n["shelley_epoch"], babbage_epoch=n["babbage_epoch"],
        genesis_nonce=genesis_nonce(seed), delegate_seed=DELEGATE_SEED)
    tag = "rehearsal-" if rehearsal else ""
    home = os.path.join(CACHE, f"{tag}{cell.config_name}-"
                               f"{cell.traffic_name}-s{seed}")
    return params, creds, views, home


def _rparams(n: dict, views, seed: int) -> ref.CParams:
    """The deployment as the reference takes it: plain numbers, keys and
    the nonce."""
    base = rpraos.Params(n["slots_per_kes_period"], n["max_kes_evolutions"],
                         n["security_param"],
                         Fraction(n["active_slot_coeff"]),
                         n["epoch_length"], n["kes_depth"])
    return ref.CParams(
        rpbft.Params(n["security_param"], Fraction(n["pbft_threshold"]),
                     {bytes(k): int(v)
                      for k, v in views.byron.delegates.items()}),
        n["byron_epoch_length"],
        rtpraos.TParams(base, Fraction(n["decentralisation"]),
                        tuple((bytes(g.vk_cold), bytes(g.vrf_key_hash))
                              for g in views.shelley.gen_delegs)),
        base, n["shelley_epoch"], n["babbage_epoch"], genesis_nonce(seed))


def forge_chain(cell, seed: int, rehearsal: bool) -> None:
    """What the forging child does: the chain of the seed, and its own
    config ("Protocol": "Cardano"), on disk under benchmark/_cache/ with a
    COMPLETE marker."""
    from ouroboros_consensus_tpu.tools import config as tconfig
    from ouroboros_consensus_tpu.tools import db_synthesizer as synth

    mix = mix_of(cell, rehearsal)
    params, creds, views, home = _deployment(cell, seed, rehearsal)
    path = os.path.join(home, "chain")
    shutil.rmtree(home, ignore_errors=True)
    os.makedirs(path)
    res = synth.synthesize(path, params, creds, views,
                           synth.ForgeLimit(blocks=mix["blocks"]),
                           vrf_backend="host")
    tconfig.write_genesis_files(os.path.join(path, "config"), params, views)
    with open(os.path.join(home, "COMPLETE"), "w") as f:
        f.write(str(res.n_blocks))


def _forge_in_child(cell, seed: int, rehearsal: bool) -> None:
    """`replay._forge_in_child`, the child this module's `__main__`."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "-m", "benchmark.traffic.replay_cardano",
           cell.name, str(seed)] + (["--cpu-rehearsal"] if rehearsal else [])
    try:
        p = subprocess.run(cmd, cwd=os.path.dirname(BENCH), env=env,
                           stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                           text=True, errors="replace",
                           timeout=FORGE_TIMEOUT_S)
    except subprocess.TimeoutExpired as e:  # run() has ended the child
        raise FailedRun("the forging child did not end",
                        seconds=FORGE_TIMEOUT_S, tail=str(e.stdout)[-2000:])
    if p.returncode:
        raise FailedRun("the forging child failed", child_rc=p.returncode,
                        tail=p.stdout[-2000:])


def make_inputs(cell, seed: int, rehearsal: bool) -> Inputs:
    """Credentials, views and chain, all from the seed, the chain kept
    under benchmark/_cache/ as `traffic/replay` keeps its. `pools` holds
    every credential (the pool, then the delegates in their order)."""
    cfg, mix = cell.config, mix_of(cell, rehearsal)
    params, creds, views, home = _deployment(cell, seed, rehearsal)
    n = _numbers(cell, rehearsal)
    pool_distr = {bytes(k): (e.stake, bytes(e.vrf_key_hash))
                  for k, e in views.babbage.pool_distr.items()}
    marker = os.path.join(home, "COMPLETE")
    reused = os.path.exists(marker)
    t0 = time.monotonic()
    if not reused:
        _forge_in_child(cell, seed, rehearsal)
    with open(marker) as f:
        n_blocks = int(f.read())
    max_headers = mix.get("max_headers")
    return Inputs(os.path.join(home, "chain"), params,
                  _rparams(n, views, seed), creds, views, pool_distr,
                  mix.get("max_batch", cfg["max_batch"]), max_headers,
                  reused, time.monotonic() - t0,
                  min(n_blocks, max_headers or n_blocks))


# ---------------------------------------------------------------------------
# the timed path is `replay.replay_once`; the checks round it
# ---------------------------------------------------------------------------


def nothing_hid_the_chip(inp: Inputs, results, events, built: dict,
                         before: dict, rehearsal: bool) -> dict:
    """`replay.nothing_hid_the_chip` with the three eras' stage programs."""
    from ouroboros_consensus_tpu.obs.warmup import WARMUP
    from ouroboros_consensus_tpu.protocol import batch as pbatch
    from ouroboros_consensus_tpu.utils.trace import (RecoveryEvent,
                                                     WindowStaged)

    report = WARMUP.report()
    recov = [dataclasses.asdict(e) for e in events
             if isinstance(e, RecoveryEvent)]
    if recov or report["recovery"]:
        raise FailedRun("the recovery ladder fired: a fallback path "
                        "produced verdicts", events=recov
                        or report["recovery"])
    if report["refusals"]:
        raise FailedRun("the compile gate refused a window",
                        refusals=report["refusals"])
    staged = [e for e in events if isinstance(e, WindowStaged)]
    bad = [dataclasses.asdict(e) for e in staged
           if e.outcome != "packed" or e.gate is not None]
    if bad:
        raise FailedRun("a window left the packed per-lane path",
                        windows=bad[:8])
    for r in results:
        if not (r.n_windows > 0 and r.packed_windows == r.n_windows):
            raise FailedRun("a window failed the packing check",
                            n_windows=r.n_windows,
                            packed_windows=r.packed_windows)
    lane_counts = sorted({e.lanes_padded for e in staged})
    if not rehearsal and lane_counts != [pbatch.bucket_size(inp.max_batch)]:
        raise FailedRun("windows were dispatched at more than one lane "
                        "count, or not at the production one",
                        lane_counts=lane_counts)
    aot_bad = [e for e in report["aot_events"][before["aot_events"]:]
               if e["outcome"] in ("run_failed", "rejected", "failed")]
    if aot_bad:
        raise FailedRun("a stored executable died inside the window and "
                        "gave way to the jit", events=aot_bad)
    if not rehearsal:
        stray = {k: v for k, v in report["stages"].items()
                 if not k.startswith(PK_STAGES)
                 or v["via"] not in ("jit", "aot")}
        if stray:
            raise FailedRun("a first execute ran outside the per-lane pk "
                            "stages", stages=stray)
    if built["programs_built"] or len(report["stages"]) != before["stages"]:
        raise FailedRun("a program was built inside the window (compile, "
                        "cache load or a new first-execute note)",
                        first_execute_notes=[before["stages"],
                                             len(report["stages"])],
                        **built)
    return {"windows": len(staged), "lane_counts": lane_counts,
            "recovery_events": 0, "gate_refusals": 0, "all_packed": True,
            "programs_built_in_window": 0}


# ---------------------------------------------------------------------------
# what `correct` compares
# ---------------------------------------------------------------------------


def program_doc(era: int, st) -> dict:
    """The program's chain-dependent state of era `era` as plain values
    (as `reference/cardano.state_doc`)."""
    if era == 0:
        return {"era": 0, "signers": [list(s) for s in st.signers]}
    return {"era": era, **state_doc(st)}


def _crossings_doc(crossings) -> list:
    return [[a, b, program_doc(a, x), program_doc(b, y)]
            for a, b, x, y in crossings or ()]


def _to_state(era: int, st):
    """The reference's state of era `era` as the program's."""
    from ouroboros_consensus_tpu.protocol.instances import PBftState

    if era == 0:
        return PBftState(tuple(tuple(s) for s in st.signers))
    if era == 1:
        return _tp._to_state(st)
    return _replay._to_state(st)


def _byron_columns(headers):
    """Reference Byron headers as the program's window columns."""
    import numpy as np

    from ouroboros_consensus_tpu.protocol.pbft import ByronColumns

    n = len(headers)
    w = max(len(h.signed_bytes) for h in headers)

    def col(get, width):
        out = np.zeros((n, width), np.uint8)
        for i, h in enumerate(headers):
            v = get(h)
            if v:
                out[i, :len(v)] = np.frombuffer(v, np.uint8)
        return out

    return ByronColumns(
        np.array([0 if h.ebb else 1 for h in headers], np.int64),
        np.array([h.slot for h in headers], np.int64),
        np.array([h.block_no for h in headers], np.int64),
        col(lambda h: h.prev_hash, 32),
        np.array([h.prev_hash is not None for h in headers], np.uint8),
        col(lambda h: h.delegate_vk, 32), col(lambda h: h.signature, 64),
        col(lambda h: h.signed_bytes, w))


def _validate_window(inp: Inputs, era: int, headers, st0,
                     validate_chain=None):
    """One window of era `era`'s headers through the program's
    `validate_chain` under that era's params."""
    from ouroboros_consensus_tpu.protocol import batch as pbatch
    from ouroboros_consensus_tpu.protocol.views import ViewColumns

    if era == 0:
        cols = _byron_columns(headers)
    else:
        views = [(_tp._to_view if era == 1 else _replay._to_view)(h)
                 for h in headers]
        cols = ViewColumns.from_views(views) or views
    view = inp.lview.era_view(era)
    return (validate_chain or pbatch.validate_chain)(
        inp.params.era_params(era), lambda _e: view, st0, cols,
        max_batch=inp.max_batch, backend=_replay.BACKEND)


def _era_window(inp: Inputs, headers, era: int, second: bool = False):
    """One production-width window of era `era`'s own headers, one signed
    width and one epoch (the second such window where the run holds two:
    the signing window then carries into it) -> (its start, its headers,
    the reference's walk up to it)."""
    cp = inp.rparams
    width = inp.max_batch
    start = None
    for i in range(len(headers)):
        e, h = headers[i]
        if e != era:
            start = None
            continue
        if start is not None:
            pe, ph = headers[i - 1]
            if (len(ph.signed_bytes) != len(h.signed_bytes)
                    or cp.epoch_of(ph.slot) != cp.epoch_of(h.slot)):
                start = None
        if start is None:
            start = i
        if i + 1 - start >= width * (2 if second else 1):
            w0 = start + (width if second else 0)
            break
    else:
        if not second:
            raise FailedRun(f"no run of one layout fills a whole window "
                            f"of era {era}")
        return _era_window(inp, headers, era)
    before = ref.replay(cp, inp.pool_distr, headers[:w0], crypto_at=())
    return w0, headers[w0:w0 + width], before


def _state_in(cp, era: int, walked):
    """The walked state carried into era `era` (a window that opens an
    era starts from the translated state)."""
    st, e = walked.state, walked.era
    while e < era:
        e += 1
        st = ref.translate(cp, e, st)
    return st


def _sign_kes(inp: Inputs, h, body: bytes) -> bytes:
    from ouroboros_consensus_tpu.ops.host import kes as host_kes

    cred = next(c for c in inp.pools if c.vk_cold == h.vk_cold)
    t = h.slot // inp.rparams.shelley.slots_per_kes_period \
        - h.ocert_kes_period
    return host_kes.sign(cred.kes_seed, cred.kes_depth, t, body)


def _delegate(inp: Inputs, vk: bytes):
    return next(c for c in inp.pools if c.vk_cold == vk)


def corrupt(what: str, window, lane: int, inp: Inputs, st0, seed: int):
    """The window's headers with lane `lane` (pbft-threshold: every lane
    from it on) made wrong, as an attacker would send them."""
    from ouroboros_consensus_tpu.ops.host import fast

    cp = inp.rparams
    out = list(window)
    h = window[lane]
    if what == "pbft-signature":
        out[lane] = dataclasses.replace(h, signature=_flip(h.signature, 40))
    elif what == "pbft-not-delegate":
        s = hashlib.blake2b(b"stranger-%d" % (seed + STRANGER_SEED),
                            digest_size=32).digest()
        out[lane] = dataclasses.replace(
            h, delegate_vk=fast.ed25519_public(s),
            signature=fast.ed25519_sign(s, h.signed_bytes))
    elif what == "pbft-threshold":
        # every lane from `lane` on signed, correctly, by ONE delegate:
        # its share of the trailing k rises past the threshold
        cred = _delegate(inp, h.delegate_vk)
        for i in range(lane, len(window)):
            g = window[i]
            if not g.ebb:
                out[i] = dataclasses.replace(
                    g, delegate_vk=cred.vk_cold,
                    signature=fast.ed25519_sign(cred.cold_seed,
                                                g.signed_bytes))
    elif what == "overlay-wrong-delegate":
        out[lane] = _other_delegate(inp, h, st0)
    elif what == "vrf-leader-proof":
        proof = _flip(h.vrf_leader_proof, len(h.vrf_leader_proof) - 32)
        o = h.signed_bytes.index(h.vrf_leader_proof)
        body = h.signed_bytes[:o] + proof + h.signed_bytes[o + len(proof):]
        out[lane] = dataclasses.replace(h, vrf_leader_proof=proof,
                                        signed_bytes=body,
                                        kes_sig=_sign_kes(inp, h, body))
    elif what == "babbage-stale-nonce":
        out[lane] = _stale_nonce(inp, h)
    else:
        raise ValueError(f"unknown corruption {what!r}")
    return out


def _body_block_no(h) -> int:
    body, _ = rpraos._cbor_item(h.signed_bytes, 0)
    return body[0]


def _other_delegate(inp: Inputs, h, st0):
    """The header of `h`'s (active overlay) slot forged whole by ANOTHER
    genesis delegate: every signature and both proofs are right, only the
    overlay schedule says no."""
    from ouroboros_consensus_tpu.block.forge import forge_block
    from ouroboros_consensus_tpu.protocol import tpraos

    cp = inp.rparams
    eta0 = ref.tick(cp, 1, h.slot, st0).epoch_nonce
    _, j = ref.overlay(cp, h.slot)
    colds = [cold for cold, _vrf in cp.shelley.gen_delegs]
    other = _delegate(inp, colds[(j + 1) % len(colds)])
    blk = forge_block(
        inp.params.shelley, other, slot=h.slot, block_no=_body_block_no(h),
        prev_hash=h.prev_hash, epoch_nonce=eta0,
        is_leader=tpraos.prove_certificates(other.vrf_seed, h.slot, eta0))
    return rtpraos._header_at(blk.bytes_, 0)[0]


def _stale_nonce(inp: Inputs, h):
    """`h`'s Babbage header made again, correctly in every signature,
    with its VRF proof under the Shelley GENESIS nonce in place of the
    nonce the hard fork and the boundary tick give."""
    from ouroboros_consensus_tpu.block.forge import forge_block
    from ouroboros_consensus_tpu.ops.host import fast
    from ouroboros_consensus_tpu.protocol import nonces
    from ouroboros_consensus_tpu.protocol.praos import PraosIsLeader

    stale = inp.rparams.genesis_nonce
    pool = _delegate(inp, h.vk_cold)
    proof = fast.ecvrf_prove_draft03(pool.vrf_seed,
                                     nonces.mk_input_vrf(h.slot, stale))
    blk = forge_block(
        inp.params.babbage, pool, slot=h.slot, block_no=_body_block_no(h),
        prev_hash=h.prev_hash, epoch_nonce=stale,
        ocert_counter=h.ocert_counter,
        is_leader=PraosIsLeader(fast.ecvrf_proof_to_hash(proof), proof))
    return rpraos._header_at(blk.bytes_, 0)[0]


# which era's window a corruption falls in, and (Shelley) on which lanes:
# the wrong delegate on an overlay lane, the leader proof on a lottery one
_ERA_OF = {"pbft-signature": 0, "pbft-not-delegate": 0, "pbft-threshold": 0,
           "overlay-wrong-delegate": 1, "vrf-leader-proof": 1,
           "babbage-stale-nonce": 2}
_OVERLAY_LANE = {"overlay-wrong-delegate": True, "vrf-leader-proof": False}


def wrong_header_cases(inp: Inputs, headers, mix: dict, seed: int,
                       validate_chain=None):
    """`replay.wrong_header_cases` over the three eras: for each
    corruption of the mix a production-width window of its era's own
    headers (Byron's second, so that the signing window is carried into
    it; Shelley's first; Babbage's first) goes through `validate_chain`
    with the corruption in a lane of its upper half, and has to come back
    with the reference's first failure: index, error, fields, state."""
    cp = inp.rparams
    rng = random.Random(seed)
    windows = {}
    cases = []
    for what in mix["corrupt"]:
        era = _ERA_OF[what]
        if era not in windows:
            w0, window, before = _era_window(inp, headers, era,
                                             second=era == 0)
            st0 = _state_in(cp, era, before)
            windows[era] = (w0, window, st0)
        w0, window, st0 = windows[era]
        width = len(window)
        hdrs = [h for _e, h in window]
        want_overlay = _OVERLAY_LANE.get(what)
        # the threshold's run starts early in the upper half: it needs
        # room to climb past floor(threshold x k)
        top = width // 2 + max(2, width // 16) if what == "pbft-threshold" \
            else width
        lanes = [i for i in range(width // 2, top)
                 if not (era == 0 and hdrs[i].ebb)
                 and (want_overlay is None
                      or (ref.overlay(cp, hdrs[i].slot) is not None)
                      == want_overlay)]
        lane = rng.choice(lanes)
        bad = corrupt(what, hdrs, lane, inp, st0, seed)
        tagged = [(era, h) for h in bad]
        want = ref.replay(cp, inp.pool_distr, tagged, st=st0, era=era,
                          crypto_at=(lane,))
        got = _validate_window(inp, era, bad, _to_state(era, st0),
                               validate_chain)
        cases.append({
            "corrupted": what, "era": era, "lane": lane,
            "window_start": w0,
            "overlay_lane": (ref.overlay(cp, hdrs[lane].slot) is not None
                             if era == 1 else None),
            "reference": [want.n_valid, want.error],
            "program": [got.n_valid, error_doc(got.error)],
            "agree": (want.error is not None and want.n_valid >= lane
                      and got.n_valid == want.n_valid
                      and error_doc(got.error) == want.error
                      and program_doc(era, got.state)
                      == ref.state_doc(era, want.state)),
        })
    return cases


def judge(inp: Inputs, results, mix: dict, seed: int, validate_chain=None):
    """`replay.judge` over the three eras: the state compared is the final
    one AND the state on each side of both hard forks."""
    cp = inp.rparams
    t0 = time.monotonic()
    headers = ref.read_chain(inp.path)[:inp.headers]
    rng = random.Random(seed ^ 0x5EED)
    k = min(mix["reference_sample"], len(headers))
    # the genesis header, each era's first and each epoch's first are
    # always in the sample
    firsts = {0} | {i for i in range(1, len(headers))
                    if headers[i][0] != headers[i - 1][0]
                    or cp.epoch_of(headers[i][1].slot)
                    != cp.epoch_of(headers[i - 1][1].slot)}
    sample = firsts | set(rng.sample(range(len(headers)), k))
    want = ref.replay(cp, inp.pool_distr, headers, crypto_at=sample)
    want_state = ref.state_doc(want.era, want.state)
    want_cross = [list(c) for c in want.crossings]
    n_gap = state_gap = error_gap = failed = 0
    for r in results:
        gap = max(abs(r.n_valid - want.n_valid),
                  abs(r.n_blocks - len(headers)))
        state_bad = (program_doc(r.era, r.final_state) != want_state
                     or _crossings_doc(r.crossings) != want_cross)
        error_bad = error_doc(r.error) != want.error
        n_gap = max(n_gap, gap)
        state_gap += state_bad
        error_gap += error_bad
        if gap or state_bad or error_bad:
            failed += max(gap, 1)  # headers whose verdict differs
    t1 = time.monotonic()
    cases = wrong_header_cases(inp, headers, mix, seed, validate_chain)
    wrong = sum(not c["agree"] for c in cases)
    compared = {
        "n_valid_gap": {"value": n_gap, "limit": 0},
        "state_mismatches": {"value": state_gap, "limit": 0},
        "error_mismatches": {"value": error_gap, "limit": 0},
        "wrong_header_mismatches": {"value": wrong, "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    per_era = [sum(1 for e, _h in headers if e == x) for x in range(3)]
    detail = {"reference_headers": len(headers),
              "reference_headers_per_era": per_era,
              "reference_ebbs": sum(1 for e, h in headers
                                    if e == 0 and h.ebb),
              "reference_crypto_verified": want.n_crypto,
              "reference_n_valid": want.n_valid,
              "reference_error": want.error,
              "reference_crossings": [c[:2] for c in want.crossings],
              "reference_s": round(t1 - t0, 3),
              "wrong_header_s": round(time.monotonic() - t1, 3),
              "wrong_header_cases": cases}
    return correct, compared, failed, detail


# ---------------------------------------------------------------------------
# one run: `replay.run` over this module's `make_inputs`, checks and `judge`
# ---------------------------------------------------------------------------


def _span_doc(span) -> dict:
    """A window span as plain values; the PBFT fields only on a window
    that holds Byron lanes, so that `pbft_*_per_window` are means over
    those windows."""
    d = dataclasses.asdict(span)
    if not d.get("pbft_lanes"):
        for k in ("pbft_lanes", "pbft_s", "ebbs"):
            d.pop(k, None)
    return d


def run(cell, args, device: dict) -> dict:
    """Set-up, window, judgement. -> what run.py prints."""
    from ouroboros_consensus_tpu import native_loader, obs
    from ouroboros_consensus_tpu.obs.warmup import WARMUP
    from ouroboros_consensus_tpu.utils.trace import WindowSpan, WindowStaged

    rehearsal = args.cpu_rehearsal
    mix = mix_of(cell, rehearsal)
    seams = check_seams(rehearsal)
    cache_dir = place_caches(cell, rehearsal)
    cc = harness.CompileCounter()
    if native_loader.load() is None or native_loader.load_crypto() is None:
        raise FailedRun("native/headerscan.cpp or native/hostcrypto.cpp did "
                        "not build or load (no g++?)")
    inp = make_inputs(cell, args.seed, rehearsal)
    emit("chain", path=os.path.relpath(inp.path), reused=inp.reused,
         forge_s=round(inp.forge_s, 3), headers=inp.headers,
         credentials=len(inp.pools), max_batch=inp.max_batch, seams=seams,
         cache_dir=cache_dir)

    # -- set-up: one whole replay, as `replay.run`
    mark = cc.mark()
    with every_program_stored():
        r0, wall0 = replay_once(inp)
    report = WARMUP.report()
    stages = report["stages"]
    emit("setup_replay", wall_s=round(wall0, 3), n_valid=r0.n_valid,
         error=repr(r0.error), built=cc.since(mark),
         unpack_programs=sum(k.startswith("unpack_") for k in stages),
         stage_setup_s={k: [v["wall_s"], v["via"]]
                        for k, v in stages.items()},
         stored_programs=report.get("aot"))
    before = {"stages": len(stages), "aot_events": len(report["aot_events"])}
    rec = obs.install()
    try:
        setup_s = harness.process_age_s()

        # -- the window
        tracing = bool(args.trace)
        trace_dir = os.path.join(CACHE, f"trace-{cell.name}-s{args.seed}")
        n_ev = len(rec.events)
        mark = cc.mark()
        results, walls, stretch = [], [], None
        if tracing:
            shutil.rmtree(trace_dir, ignore_errors=True)
            stretch = xplane.Stretch(
                trace_dir, mix["trace_seconds"],
                retired=lambda: [e.t_materialized
                                 for _, e in rec.events[n_ev:]
                                 if isinstance(e, WindowSpan)],
                lead_s=mix["trace_lead_seconds"],
                wait_s=mix["trace_wait_seconds"])
        t0 = time.monotonic()
        while True:
            r, wall = replay_once(inp)
            results.append(r)
            walls.append(wall)
            if time.monotonic() - t0 >= args.seconds:
                break
        window_s = time.monotonic() - t0
        built = cc.since(mark)
        events = [e for _, e in rec.events[n_ev:]]
        peak = harness.memory_peak_bytes()
        hid = nothing_hid_the_chip(inp, results, events, built, before,
                                   rehearsal)
    finally:
        obs.uninstall()

    headers_done = sum(r.n_valid for r in results)
    spans = [e for e in events if isinstance(e, WindowSpan)]
    staged = [e for e in events if isinstance(e, WindowStaged)]
    rate = headers_done / window_s
    stats = replay_rate.window_stats(headers_done, walls, window_s)
    emit("window", seconds=round(window_s, 4), replays=len(results),
         replay_headers_per_s=rate, **stats,
         replay_walls_s=[round(w, 3) for w in walls], headers=headers_done,
         eras=[r.era for r in results[:1]],
         crossings=[[c[0], c[1]] for c in (results[0].crossings or ())],
         materialize_ms=[round(e.materialize_s * 1e3) for e in spans],
         stage_ms=[round(e.stage_s * 1e3) for e in spans], **hid)

    # -- judgement, once the window has closed and the peak has been read
    correct, compared, failed, detail = judge(inp, results, mix, args.seed)
    emit("judged", **detail)
    rn, wall_n = replay_once(inp, backend="native",
                             max_headers=min(inp.headers, inp.max_batch))
    emit("native_witness", headers_per_s=round(rn.n_valid / wall_n, 1),
         headers=rn.n_valid, wall_s=round(wall_n, 3), error=repr(rn.error),
         note="the program's own C++ verifier on one core over the chain's "
              "first window (Byron's: one Ed25519 check a header); not part "
              "of `correct`")

    trace_path = stretch.path() if tracing else None

    phase_wall: dict = {}
    for r in results:
        for k, v in (r.phases or {}).items():
            phase_wall[k] = phase_wall.get(k, 0.0) + v
    counters = {
        "headers": headers_done,
        "windows": len(staged),
        "h2d_bytes": sum(r.h2d_bytes for r in results),
        "d2h_bytes": sum(r.d2h_bytes for r in results),
        "lanes_live": sum(e.lanes for e in staged),
        "lanes_padded": sum(e.lanes_padded for e in staged),
        "vrf_proofs": sum(e.vrf_proofs for e in spans),
        # epoch boundary blocks the window's replays passed through (a
        # program without the field counts none)
        "ebbs": sum(getattr(e, "ebbs", 0) for e in spans),
    }
    sources = {
        "replays": len(results),
        "window_stats": stats,
        "phase_wall": phase_wall,
        "window_spans": [_span_doc(s) for s in spans],
        "counters": counters,
        "wire": {"lanes": inp.max_batch,
                 "kes_depth": inp.rparams.babbage.kes_depth},
        "device_kind": device["kind"],
        "trace": None,
    }
    return {
        "correct": correct, "attempted": inp.headers * len(results),
        "failed": failed, "compared": compared,
        "end_to_end": {"replay_headers_per_s": rate, "setup_s": setup_s},
        "sources": sources, "memory_peak_bytes": peak,
        "trace_path": trace_path, "stretch": stretch,
        "window": (t0, t0 + window_s), "replay_walls": walls,
    }


# ---------------------------------------------------------------------------
# the control of `correct` (benchmark/control.py for this kind)
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def skip_byron_signature_check():
    """The packed per-lane dispatch with every Byron lane's signature
    verdict forced true in `finish_pbft`'s outputs."""
    from ouroboros_consensus_tpu.ops.pk import kernels

    orig = kernels._stage_call

    def stage_call(name, fn, b, kes_depth, *args, **kw):
        out = orig(name, fn, b, kes_depth, *args, **kw)
        if name == "finish_pbft":
            flags, eta = out
            return (flags.at[0].set(1), eta)
        return out

    kernels._stage_call = stage_call
    try:
        yield
    finally:
        kernels._stage_call = orig


def control(cell, seeds, replays: int = 1) -> int:
    """`control.py`'s loop under `skip_byron_signature_check`: the program
    has to come out correct and the control not correct, on every seed."""
    from ouroboros_consensus_tpu import obs

    try:
        device = harness.acquire_device(cell.chips, rehearsal=False)
        check_seams(False)
    except FailedRun as e:
        harness.say(f"control: {e.what} {e.detail}")
        return e.rc
    place_caches(cell)
    ok = True
    obs.install()
    try:
        for seed in seeds:
            t0 = time.monotonic()
            inp = make_inputs(cell, seed, False)
            mix = mix_of(cell, False)
            results = [replay_once(inp)[0] for _ in range(replays)]
            correct, compared, _f, _d = judge(inp, results, mix, seed)
            with skip_byron_signature_check():
                c_results = [replay_once(inp)[0] for _ in range(replays)]
                c_correct, c_compared, _f, c_detail = judge(
                    inp, c_results, mix, seed)
            ok = ok and correct and not c_correct
            print(json.dumps({
                "seed": seed, "device": device,
                "seconds": round(time.monotonic() - t0, 1),
                "program": {"correct": correct, "compared": compared},
                "control": {"correct": c_correct, "compared": c_compared,
                            "cases": [[c["corrupted"], c["agree"]] for c in
                                      c_detail["wrong_header_cases"]]},
            }), flush=True)
    finally:
        obs.uninstall()
    print(json.dumps({"program_correct_and_control_not_on_every_seed": ok}),
          flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    from benchmark.manifest import Manifest

    if sys.argv[1] == "--control":
        # python3 -m benchmark.traffic.replay_cardano --control <cell> 11,12
        sys.exit(control(Manifest().cell(sys.argv[2]),
                         [int(s) for s in sys.argv[3].split(",")]))
    # the forging child: python3 -m benchmark.traffic.replay_cardano <cell> <seed>
    forge_chain(Manifest().cell(sys.argv[1]), int(sys.argv[2]),
                "--cpu-rehearsal" in sys.argv[3:])
