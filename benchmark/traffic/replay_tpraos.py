"""Traffic kind `replay_tpraos`: kind `replay` over a chain of the Shelley
era's protocol, TPraos: two VRF certificates a header, the BFT overlay
schedule, genesis delegates.

What differs from `traffic/replay.py`, and only that, is here: the
deployment (the configuration's `decentralisation` and `genesis_delegates`
make the program's `TPraosParams` / `TPraosLedgerView` and the reference's
`TParams`), the plain reference (`benchmark/reference/tpraos.py`), the five
corruptions, and one stage name more in the check that nothing hid the chip
(`finish_tp`). The timed path (`replay_once`: the program's normal entry
point, which takes the protocol from the params), the caches, the seams and
the rule of the comparison are `replay`'s, imported; `_forge_in_child`,
`make_inputs`, `nothing_hid_the_chip`, `judge` and `run` are copies that name
this module's reference, stages and forging child, number for number.
Folding the three replay kinds into one is a `benchmark` issue's (PERF.md
section 7): this PR may edit no file that is here.

The control of `correct` for this kind is here too (`control.py` names the
Praos `finish` stage):

    python3 -m benchmark.traffic.replay_tpraos --control <cell> <seeds>

replays on the chip with the NONCE proof's check left out of `finish_tp`
and has to fail `wrong_header_mismatches`, and that alone.
"""

from __future__ import annotations

import contextlib
import dataclasses
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
from benchmark.reference import tpraos as ref
from benchmark.traffic import replay as _replay
from benchmark.traffic.replay import (  # noqa: F401 - run.py, tests
    BENCH, CACHE, FORGE_TIMEOUT_S, Inputs, _flip, _protocol, _uniform_window,
    _validate_window, check_seams, corrupt as _corrupt_signature, error_doc,
    every_program_stored, mix_of, place_caches, replay_once, state_doc)

# the per-lane stage programs of a TPraos window's packed dispatch: the
# draft-03 cell's, with `finish_tp` where it has `finish`
PK_STAGES = ("unpack_", "ed@", "kes@", "vrf@", "finish_tp@", "reduce@")
DELEGATE_SEED = 1000  # genesis delegate j is make_pool(seed + 1000 + j)


# ---------------------------------------------------------------------------
# the input, from the seed
# ---------------------------------------------------------------------------


def _deployment(cell, seed: int, rehearsal: bool):
    """-> (params, every credential, ledger view, the chain's home), from
    the seed: the pool is the program's `make_pool(seed)`, genesis delegate
    j `make_pool(seed + 1000 + j)`."""
    from ouroboros_consensus_tpu.protocol import praos
    from ouroboros_consensus_tpu.testing import fixtures
    from ouroboros_consensus_tpu.tools import db_synthesizer as synth

    if not hasattr(synth, "make_tpraos"):
        raise FailedRun("this program forges and validates no TPraos chain "
                        "(tools/db_synthesizer.make_tpraos): it cannot run "
                        "this configuration", rc=1)
    cfg = cell.config
    proto = _protocol(cfg)
    pools = [fixtures.make_pool(seed + i, kes_depth=proto["kes_depth"])
             for i in range(cfg["pools"])]
    params, creds, lview = synth.make_tpraos(
        praos.PraosParams(**proto), pools, fixtures.make_ledger_view(pools),
        cfg["genesis_delegates"],
        Fraction(cfg["decentralisation"]),
        first_seed=seed + DELEGATE_SEED)
    tag = "rehearsal-" if rehearsal else ""
    home = os.path.join(CACHE, f"{tag}{cell.config_name}-"
                               f"{cell.traffic_name}-s{seed}")
    return params, creds, lview, home


def forge_chain(cell, seed: int, rehearsal: bool) -> None:
    """What the forging child does: the chain of the seed, on disk under
    benchmark/_cache/ with a COMPLETE marker."""
    from ouroboros_consensus_tpu.tools import db_synthesizer as synth

    mix = mix_of(cell, rehearsal)
    params, creds, lview, home = _deployment(cell, seed, rehearsal)
    limit = (synth.ForgeLimit(blocks=mix["blocks"]) if mix.get("blocks")
             else synth.ForgeLimit(epochs=mix["epochs"]))
    path = os.path.join(home, "chain")
    shutil.rmtree(home, ignore_errors=True)
    os.makedirs(path)
    res = synth.synthesize(path, params, creds, lview, limit,
                           vrf_backend="host")
    with open(os.path.join(home, "COMPLETE"), "w") as f:
        f.write(str(res.n_blocks))


def _forge_in_child(cell, seed: int, rehearsal: bool) -> None:
    """`replay._forge_in_child`, the child this module's `__main__`."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               **{k: str(v) for k, v in
                  cell.config.get("forge_env", {}).items()})
    cmd = [sys.executable, "-m", "benchmark.traffic.replay_tpraos",
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
    """Credentials, ledger view and chain, all from the seed; the chain
    kept under benchmark/_cache/ as `traffic/replay` keeps its. `pools`
    holds every credential (the pool, then the delegates in their
    order); the reference gets the deployment as plain values."""
    cfg, mix = cell.config, mix_of(cell, rehearsal)
    params, creds, lview, home = _deployment(cell, seed, rehearsal)
    pool_distr = {k: (e.stake, e.vrf_key_hash)
                  for k, e in lview.pool_distr.items()}
    rparams = ref.TParams(
        ref.Params(**_protocol(cfg)), Fraction(cfg["decentralisation"]),
        tuple((g.vk_cold, g.vrf_key_hash) for g in lview.gen_delegs))
    marker = os.path.join(home, "COMPLETE")
    reused = os.path.exists(marker)
    t0 = time.monotonic()
    if not reused:
        _forge_in_child(cell, seed, rehearsal)
    with open(marker) as f:
        n_blocks = int(f.read())
    max_headers = mix.get("max_headers")
    return Inputs(os.path.join(home, "chain"), params, rparams, creds, lview,
                  pool_distr, mix.get("max_batch", cfg["max_batch"]),
                  max_headers, reused, time.monotonic() - t0,
                  min(n_blocks, max_headers or n_blocks))


# ---------------------------------------------------------------------------
# the timed path is `replay.replay_once`; the checks round it
# ---------------------------------------------------------------------------


def nothing_hid_the_chip(inp: Inputs, results, events, built: dict,
                         before: dict, rehearsal: bool) -> dict:
    """`replay.nothing_hid_the_chip` with this kind's stage programs."""
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


def _sign_again(h: ref.Header, body: bytes, inp: Inputs) -> bytes:
    """The altered body under its own issuer's KES key, so that what was
    altered is the first thing wrong."""
    from ouroboros_consensus_tpu.ops.host import kes as host_kes

    cred = next(c for c in inp.pools if c.vk_cold == h.vk_cold)
    t = h.slot // inp.rparams.slots_per_kes_period - h.ocert_kes_period
    return host_kes.sign(cred.kes_seed, cred.kes_depth, t, body)


def _other_delegate(h: ref.Header, eta0, inp: Inputs) -> ref.Header:
    """The header of `h`'s (active overlay) slot forged whole by ANOTHER
    genesis delegate: every signature and both proofs are right, only the
    overlay schedule says no."""
    from ouroboros_consensus_tpu.block.forge import forge_block
    from ouroboros_consensus_tpu.protocol import tpraos

    _, j = ref.overlay(inp.rparams, h.slot)
    colds = [cold for cold, _vrf in inp.rparams.gen_delegs]
    other = next(c for c in inp.pools
                 if c.vk_cold == colds[(j + 1) % len(colds)])
    body, _ = ref._cbor_item(h.signed_bytes, 0)
    blk = forge_block(
        inp.params, other, slot=h.slot, block_no=body[0],
        prev_hash=h.prev_hash, epoch_nonce=eta0,
        is_leader=tpraos.prove_certificates(other.vrf_seed, h.slot, eta0))
    bad, _end = ref._header_at(blk.bytes_, 0)
    return bad


def corrupt(what: str, h: ref.Header, inp: Inputs, eta0=None) -> ref.Header:
    """One wrong header, as an attacker would send it."""
    if what in ("ocert-signature", "kes-signature"):
        return _corrupt_signature(what, h, inp)
    if what in ("vrf-eta-proof", "vrf-leader-proof"):
        field = "vrf_proof" if what == "vrf-eta-proof" else "vrf_leader_proof"
        old = getattr(h, field)
        new = _flip(old, len(old) - 32)
        o = h.signed_bytes.index(old)
        body = h.signed_bytes[:o] + new + h.signed_bytes[o + len(new):]
        return dataclasses.replace(h, **{field: new}, signed_bytes=body,
                                   kes_sig=_sign_again(h, body, inp))
    if what == "overlay-wrong-delegate":
        return _other_delegate(h, eta0, inp)
    raise ValueError(f"unknown corruption {what!r}")


def _to_view(h: ref.Header):
    from ouroboros_consensus_tpu.protocol.views import HeaderView, OCert

    return HeaderView(
        prev_hash=h.prev_hash, vk_cold=h.vk_cold, vrf_vk=h.vrf_vk,
        vrf_output=h.vrf_output, vrf_proof=h.vrf_proof,
        ocert=OCert(h.ocert_vk_hot, h.ocert_counter, h.ocert_kes_period,
                    h.ocert_sigma),
        slot=h.slot, signed_bytes=h.signed_bytes, kes_sig=h.kes_sig,
        vrf_leader_output=h.vrf_leader_output,
        vrf_leader_proof=h.vrf_leader_proof)


def _to_state(st: ref.State):
    from ouroboros_consensus_tpu.protocol import tpraos

    return tpraos.TPraosState(
        last_slot=st.last_slot, ocert_counters=dict(st.counters),
        **{f: getattr(st, f) for f in _replay.NONCES})


def _full_window(inp: Inputs, headers):
    """`replay._full_window` under this kind's reference."""
    width = min(inp.max_batch, len(headers))
    w0 = _uniform_window(headers, inp.rparams, width)
    if w0 is None:
        raise FailedRun("no run of one layout fills a whole window")
    before = ref.replay(inp.rparams, inp.pool_distr, headers[:w0],
                        crypto_at=())  # bookkeeping up to the window
    return w0, headers[w0:w0 + width], before


# which lanes a corruption may fall on: the overlay rule needs an overlay
# lane, and of the two proofs one is broken under each leader rule
_LANE_OF = {"vrf-eta-proof": False, "vrf-leader-proof": True,
            "overlay-wrong-delegate": True}


def wrong_header_cases(inp: Inputs, headers, mix: dict, seed: int,
                       validate_chain=None):
    """`replay.wrong_header_cases` over a TPraos window: one lane of the
    window's upper half for each corruption of the mix, drawn from the
    seed among the lanes of the leader rule the corruption is to meet (an
    active overlay slot's, or the lottery's), each signed again by its own
    issuer."""
    kinds = mix["corrupt"]
    w0, window, before = _full_window(inp, headers)
    width = len(window)
    is_overlay = [ref.overlay(inp.rparams, h.slot) is not None
                  for h in window]
    eta0 = ref.tick(inp.rparams, window[0].slot, before.state).epoch_nonce
    rng = random.Random(seed)
    upper = range(width // 2, width)
    views = [_to_view(h) for h in window]
    st0 = _to_state(before.state)
    # the corruptions that need a lane of one leader rule draw first
    lane_of: dict = {}
    for what in sorted(kinds, key=lambda k: _LANE_OF.get(k) is None):
        want_overlay = _LANE_OF.get(what)
        lane_of[what] = rng.choice(
            [i for i in upper if i not in lane_of.values()
             and want_overlay in (None, is_overlay[i])])
    cases = []
    for what in kinds:
        lane = lane_of[what]
        bad = corrupt(what, window[lane], inp, eta0)
        want = ref.replay(inp.rparams, inp.pool_distr,
                          window[:lane] + [bad], st=before.state,
                          crypto_at=(lane,))
        hvs = list(views)
        hvs[lane] = _to_view(bad)
        got = _validate_window(inp, hvs, st0, validate_chain)
        cases.append({
            "corrupted": what, "lane": lane, "window_start": w0,
            "overlay_lane": is_overlay[lane],
            "reference": [want.n_valid, want.error],
            "program": [got.n_valid, error_doc(got.error)],
            "agree": (want.error is not None and want.n_valid == lane
                      and got.n_valid == want.n_valid
                      and error_doc(got.error) == want.error
                      and state_doc(got.state) == want.state.doc()),
        })
    return cases


def judge(inp: Inputs, results, mix: dict, seed: int, validate_chain=None):
    """`replay.judge`, number for number, under this kind's reference and
    `wrong_header_cases`. The state compared holds the counters of the
    pool and of every genesis delegate, and the five nonces."""
    t0 = time.monotonic()
    headers = ref.read_chain(inp.path)[:inp.headers]
    rng = random.Random(seed ^ 0x5EED)
    k = min(mix["reference_sample"], len(headers))
    firsts = {0} | {i for i in range(1, len(headers))
                    if headers[i].slot // inp.rparams.epoch_length
                    != headers[i - 1].slot // inp.rparams.epoch_length}
    sample = firsts | set(rng.sample(range(len(headers)), k))
    want = ref.replay(inp.rparams, inp.pool_distr, headers,
                      crypto_at=sample)
    want_state = want.state.doc()
    n_gap = state_gap = error_gap = failed = 0
    for r in results:
        gap = max(abs(r.n_valid - want.n_valid),
                  abs(r.n_blocks - len(headers)))
        state_bad = state_doc(r.final_state) != want_state
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
    overlay = sum(ref.overlay(inp.rparams, h.slot) is not None
                  for h in headers)
    detail = {"reference_headers": len(headers),
              "body_bytes": max(len(h.signed_bytes) for h in headers),
              "reference_crypto_verified": want.n_crypto,
              "reference_n_valid": want.n_valid,
              "reference_error": want.error,
              "reference_issuers": len({h.vk_cold for h in headers}),
              "reference_counters": len(want_state["counters"]),
              "reference_overlay_headers": overlay,
              "reference_s": round(t1 - t0, 3),
              "wrong_header_s": round(time.monotonic() - t1, 3),
              "wrong_header_cases": cases}
    return correct, compared, failed, detail


# ---------------------------------------------------------------------------
# one run: `replay.run` over this module's `make_inputs`, checks and `judge`
# ---------------------------------------------------------------------------


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
              "first window (two proofs a header); not part of `correct`")

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
    }
    if spans and hasattr(spans[0], "vrf_proofs"):
        # the proofs the device verified, window by window (exact): a
        # program that does not count them leaves the metric out
        counters["vrf_proofs"] = sum(e.vrf_proofs for e in spans)
    sources = {
        "replays": len(results),
        "window_stats": stats,
        "phase_wall": phase_wall,
        "window_spans": [dataclasses.asdict(s) for s in spans],
        "counters": counters,
        "wire": {"lanes": inp.max_batch,
                 "kes_depth": inp.rparams.kes_depth,
                 "body_bytes": detail["body_bytes"]},
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
def skip_nonce_proof_check():
    """The packed per-lane dispatch with the NONCE proof's verdict forced
    true in `finish_tp`'s outputs: `ok_vrf` becomes the leader proof's
    alone."""
    from ouroboros_consensus_tpu.ops.pk import kernels

    orig = kernels._stage_call

    def stage_call(name, fn, b, kes_depth, *args, **kw):
        out = orig(name, fn, b, kes_depth, *args, **kw)
        if name == "finish_tp":
            flags, eta, lv, vrf_ok = out
            # vrf_ok rows: the nonce proof, the leader proof
            return (flags.at[2].set(vrf_ok[1]), eta, lv,
                    vrf_ok.at[0].set(1))
        return out

    kernels._stage_call = stage_call
    try:
        yield
    finally:
        kernels._stage_call = orig


def control(cell, seeds, replays: int = 1) -> int:
    """`control.py`'s loop under `skip_nonce_proof_check`: the program has
    to come out correct and the control not correct, on every seed."""
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
            with skip_nonce_proof_check():
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
        # python3 -m benchmark.traffic.replay_tpraos --control <cell> 11,12
        sys.exit(control(Manifest().cell(sys.argv[2]),
                         [int(s) for s in sys.argv[3].split(",")]))
    # the forging child: python3 -m benchmark.traffic.replay_tpraos <cell> <seed>
    forge_chain(Manifest().cell(sys.argv[1]), int(sys.argv[2]),
                "--cpu-rehearsal" in sys.argv[3:])
