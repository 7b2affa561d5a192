"""Traffic kind `replay`: `db-analyser --only-validation` over a chain that
`db-synthesizer` forged from the seed.

The window drives the program's normal entry point,

    ouroboros_consensus_tpu.tools.db_analyser.revalidate(
        path, params, lview, backend="device", validate_all="stream",
        max_batch=<the configuration's>)

in this process, which holds the chip, again and again over the same chain
on disk until `--seconds` have passed, finishing the replay in flight. The
parameters of a mix (how many epochs, which headers the control corrupts,
how many headers the reference verifies in full) are the data file
benchmark/traffic/<mix>.json; the deployment is the configuration's file.

From the program this module takes the system under test (`revalidate`,
`validate_chain`), its forger and fixtures to make the INPUT from the seed,
and its spans and counters (`obs` flight recorder, `res.phases`). What is
compared, and with what, is benchmark/reference/ and `judge()` below.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import random
import shutil
import subprocess
import sys
import time
from fractions import Fraction

from benchmark import harness, replay_rate, xplane
from benchmark.harness import FailedRun, emit
from benchmark.reference import praos as ref

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE = os.path.join(BENCH, "_cache")
# a forge takes 76-92 s on the chip's host; a child that hangs is ended
FORGE_TIMEOUT_S = 900
# the per-lane stage programs of the packed pk dispatch: a first execute
# outside them means a window took another path (copied from chip_smoke.py)
PK_STAGES = ("unpack_", "ed@", "kes@", "vrf_bc@", "vrf@", "finish@",
             "reduce@")
# the backend of the timed path. Only benchmark/tests/ set another (the
# program's native verifier, which needs no compile on the CPU), to drive
# the judgement with the timed path broken underneath.
BACKEND = "device"
NONCES = ("evolving_nonce", "candidate_nonce", "epoch_nonce", "lab_nonce",
          "last_epoch_block_nonce")


# ---------------------------------------------------------------------------
# the input, from the seed
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Inputs:
    path: str
    params: object  # the program's PraosParams
    rparams: ref.Params
    pools: list
    lview: object
    pool_distr: dict  # plain values, for the reference
    max_batch: int
    max_headers: int | None
    reused: bool
    forge_s: float
    headers: int


def _protocol(cfg: dict) -> dict:
    p = dict(cfg["protocol"])
    p["active_slot_coeff"] = Fraction(p["active_slot_coeff"])
    return p


def mix_of(cell, rehearsal: bool) -> dict:
    """The mix's parameters; a rehearsal takes its tiny sizes from the
    mix's own `cpu_rehearsal` group."""
    mix = dict(cell.traffic)
    if rehearsal:
        mix.update(mix.get("cpu_rehearsal", {}))
    return mix


def _deployment(cell, seed: int, rehearsal: bool):
    """-> (params, pools, ledger view, the chain's home), from the seed:
    the pool's index IS the seed, so keys, VRF outputs, leader slots and
    every header differ by seed."""
    from ouroboros_consensus_tpu.protocol import praos
    from ouroboros_consensus_tpu.testing import fixtures

    cfg = cell.config
    proto = _protocol(cfg)
    pools = [fixtures.make_pool(seed + i, kes_depth=proto["kes_depth"])
             for i in range(cfg["pools"])]
    tag = "rehearsal-" if rehearsal else ""
    home = os.path.join(CACHE, f"{tag}{cell.config_name}-"
                               f"{cell.traffic_name}-s{seed}")
    return (praos.PraosParams(**proto), pools,
            fixtures.make_ledger_view(pools), home)


def forge_chain(cell, seed: int, rehearsal: bool) -> None:
    """What the forging child does: the chain of the seed, on disk under
    benchmark/_cache/ with a COMPLETE marker."""
    from ouroboros_consensus_tpu.tools import db_synthesizer as synth

    mix = mix_of(cell, rehearsal)
    params, pools, lview, home = _deployment(cell, seed, rehearsal)
    limit = (synth.ForgeLimit(blocks=mix["blocks"]) if mix.get("blocks")
             else synth.ForgeLimit(epochs=mix["epochs"]))
    path = os.path.join(home, "chain")
    shutil.rmtree(home, ignore_errors=True)
    os.makedirs(path)
    # vrf_backend="host": forging must not touch the device
    res = synth.synthesize(path, params, pools, lview, limit,
                           vrf_backend="host")
    with open(os.path.join(home, "COMPLETE"), "w") as f:
        f.write(str(res.n_blocks))


def _forge_in_child(cell, seed: int, rehearsal: bool) -> None:
    """The chain is forged by a process of its own, held to the CPU, and
    the timed process opens it from disk, as `db-analyser` opens a chain
    that a node wrote. Forged in the timed process (until PR 31), a run
    that forged opened its window in an older process with another heap
    than one that found its chain cached, met staging's slow phases at
    other replays, and two sets of one tree differed by up to 3.9% in
    their medians (PERF.md sections 5 and 6). The configuration's
    `forge_env` is the child's alone."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               **{k: str(v) for k, v in
                  cell.config.get("forge_env", {}).items()})
    cmd = [sys.executable, "-m", "benchmark.traffic.replay", cell.name,
           str(seed)] + (["--cpu-rehearsal"] if rehearsal else [])
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
    """Pool credentials, ledger view and chain, all from the seed. The
    chain is kept under benchmark/_cache/ with a COMPLETE marker; a
    second run of a seed in one checkout reuses it."""
    cfg, mix = cell.config, mix_of(cell, rehearsal)
    params, pools, lview, home = _deployment(cell, seed, rehearsal)
    pool_distr = {p.pool_id: (e.stake, e.vrf_key_hash)
                  for p in pools
                  for e in [lview.pool_distr[p.pool_id]]}
    marker = os.path.join(home, "COMPLETE")
    reused = os.path.exists(marker)
    t0 = time.monotonic()
    if not reused:
        _forge_in_child(cell, seed, rehearsal)
    with open(marker) as f:
        n_blocks = int(f.read())
    max_headers = mix.get("max_headers")
    return Inputs(os.path.join(home, "chain"), params,
                  ref.Params(**_protocol(cfg)), pools, lview,
                  pool_distr, mix.get("max_batch", cfg["max_batch"]),
                  max_headers, reused, time.monotonic() - t0,
                  min(n_blocks, max_headers or n_blocks))


# ---------------------------------------------------------------------------
# the timed path
# ---------------------------------------------------------------------------


def replay_once(inp: Inputs, backend: str | None = None,
                max_headers: int | None = None):
    """One whole replay through the normal entry point; -> (result, wall)."""
    from ouroboros_consensus_tpu.tools import db_analyser as ana

    t0 = time.monotonic()
    r = ana.revalidate(inp.path, inp.params, inp.lview,
                       backend=backend or BACKEND,
                       validate_all="stream", max_batch=inp.max_batch,
                       max_headers=max_headers or inp.max_headers,
                       collect_phases=True)
    return r, time.monotonic() - t0


def state_doc(st) -> dict:
    """The program's PraosState as plain values (as ref.State.doc())."""
    return {
        "last_slot": st.last_slot,
        "counters": {k.hex(): v for k, v in st.ocert_counters.items()},
        **{f: (getattr(st, f) or b"").hex() for f in NONCES},
    }


def error_doc(e):
    """A PraosValidationError of the program as (name, fields)."""
    if e is None:
        return None
    fields = (dataclasses.asdict(e) if dataclasses.is_dataclass(e)
              else {"repr": repr(e)})
    return type(e).__name__, fields


def place_caches(cell, rehearsal: bool = False) -> str:
    """The compile cache where JAX_COMPILATION_CACHE_DIR says, else
    .jax_cache/ in the checkout; and what the deployment sets in its
    process (the configuration's file says why): the program's store of
    compiled stage programs, kept inside that directory, in place of
    JAX's own entries there. -> the directory"""
    from ouroboros_consensus_tpu import compile_cache

    cache_dir = compile_cache.configure()
    if not rehearsal:
        env = cell.config.get("process_env", {})
        for k, v in env.items():
            os.environ[k] = str(v).replace("<compile-cache>", cache_dir)
        if env.get("OCT_PK_AOT_WRITEBACK") == "1":
            # the store in that directory holds every stage program, and
            # is what a later process loads; JAX's own cache would keep
            # each a second time and is never read while the store is
            # whole. A directory that is kept between runs only up to a
            # size (256 MiB between chip calls) lost programs to that.
            import jax

            jax.config.update("jax_enable_compilation_cache", False)
    return cache_dir


def check_seams(rehearsal: bool) -> dict:
    """Each seam quietly takes its CPU branch when it finds no chip."""
    from ouroboros_consensus_tpu.ops.pk import hashes, kernels
    from ouroboros_consensus_tpu.protocol import batch as pbatch

    seams = {"pk_interpret": kernels._interpret(),
             "hashes_unrolled": hashes._unrolled(),
             "impl": pbatch._impl(),
             "agg_default": pbatch._agg_enabled()}
    want = {"pk_interpret": False, "hashes_unrolled": True, "impl": "pk",
            "agg_default": False}
    if not rehearsal and seams != want:
        raise FailedRun("a CPU/TPU seam of the program took its CPU branch",
                        seams=seams, wanted=want)
    return seams


def nothing_hid_the_chip(inp: Inputs, results, events, built: dict,
                         before: dict, rehearsal: bool) -> dict:
    """chip_smoke.py's "nothing hid the chip" checks over the window: a
    run in which one fires is a failed run, not a slow one."""
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
    # inside the window only: a stored program that is missing or will not
    # load at set-up gives way to the jit there, and set-up pays for it
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


def _flip(b: bytes, i: int) -> bytes:
    return b[:i] + bytes([b[i] ^ 1]) + b[i + 1:]


def _uniform_window(headers, params: ref.Params, width: int):
    """Start of the first run of `width` headers of one epoch whose signed
    bodies have one length: the replay streams such a run as full windows
    of one layout, so the control's window meets only programs the timed
    replays built."""
    start = 0
    for i in range(1, len(headers) + 1):
        if i - start >= width:
            return start
        if i == len(headers):
            break
        a, b = headers[i - 1], headers[i]
        if (len(a.signed_bytes) != len(b.signed_bytes)
                or a.slot // params.epoch_length
                != b.slot // params.epoch_length):
            start = i
    return None


def corrupt(what: str, h: ref.Header, inp: Inputs) -> ref.Header:
    """One wrong header, as an attacker would send it. `vrf-proof` signs
    the altered body again with the pool's KES key, so that the proof is
    the first thing wrong."""
    if what == "ocert-signature":
        sigma = _flip(h.ocert_sigma, 32)
        o = h.signed_bytes.index(h.ocert_sigma)
        body = h.signed_bytes[:o] + sigma + h.signed_bytes[o + 64:]
        return dataclasses.replace(h, ocert_sigma=sigma, signed_bytes=body)
    if what == "kes-signature":
        return dataclasses.replace(h, kes_sig=_flip(h.kes_sig, 32))
    if what == "vrf-proof":
        from ouroboros_consensus_tpu.ops.host import kes as host_kes

        proof = _flip(h.vrf_proof, len(h.vrf_proof) - 32)
        o = h.signed_bytes.index(h.vrf_proof)
        body = h.signed_bytes[:o] + proof + h.signed_bytes[o + len(proof):]
        pool = inp.pools[0]
        t = h.slot // inp.rparams.slots_per_kes_period - h.ocert_kes_period
        sig = host_kes.sign(pool.kes_seed, pool.kes_depth, t, body)
        return dataclasses.replace(h, vrf_proof=proof, signed_bytes=body,
                                   kes_sig=sig)
    raise ValueError(f"unknown corruption {what!r}")


def _to_view(h: ref.Header):
    from ouroboros_consensus_tpu.protocol.views import HeaderView, OCert

    return HeaderView(
        prev_hash=h.prev_hash, vk_cold=h.vk_cold, vrf_vk=h.vrf_vk,
        vrf_output=h.vrf_output, vrf_proof=h.vrf_proof,
        ocert=OCert(h.ocert_vk_hot, h.ocert_counter, h.ocert_kes_period,
                    h.ocert_sigma),
        slot=h.slot, signed_bytes=h.signed_bytes, kes_sig=h.kes_sig)


def _to_state(st: ref.State):
    from ouroboros_consensus_tpu.protocol import praos

    return praos.PraosState(
        last_slot=st.last_slot, ocert_counters=dict(st.counters),
        **{f: getattr(st, f) for f in NONCES})


def _full_window(inp: Inputs, headers):
    """One production-width window of the chain's own headers, all of one
    layout -> (its start, its headers, the reference's fold up to it)."""
    width = min(inp.max_batch, len(headers))
    w0 = _uniform_window(headers, inp.rparams, width)
    if w0 is None:
        raise FailedRun("no run of one layout fills a whole window")
    before = ref.replay(inp.rparams, inp.pool_distr, headers[:w0],
                        crypto_at=())  # bookkeeping up to the window
    return w0, headers[w0:w0 + width], before


def _validate_window(inp: Inputs, views, st0, validate_chain=None):
    from ouroboros_consensus_tpu.protocol import batch as pbatch
    from ouroboros_consensus_tpu.protocol.views import ViewColumns

    cols = ViewColumns.from_views(views)
    return (validate_chain or pbatch.validate_chain)(
        inp.params, lambda _e: inp.lview, st0,
        cols if cols is not None else views,
        max_batch=inp.max_batch, backend=BACKEND)


class _NoFirsts(set):
    """A note of first executes that remembers none."""

    def __contains__(self, item) -> bool:
        return False


@contextlib.contextmanager
def every_program_stored():
    """Round the set-up replay. The program's write-back stores a stage's
    program at the stage's first execute in a process and at no later
    one, and a chain can need two programs of one stage: a draft-03
    chain's genesis body hashes 4 SHA-512 blocks under `kes`, every other
    body 5. The second was stored by no draft-03 run, and where no bc run
    had left it (or the cache directory had since lost it) every draft-03
    process traced and lowered it anew, which took a run past its 360 s
    (PERF.md section 7, 1). So while set-up replays, the program's note of
    which stages have had a first execute (`ops/pk/kernels._FIRST_EXEC`)
    remembers none: a program that the store does not hold is a first,
    whichever comes when, and is stored. One that it holds is loaded as
    before, nothing is traced or built twice (a stored program is kept in
    memory under its own shape), and the window runs with the note as the
    program keeps it. Where the program keeps no such note any more, this
    does nothing."""
    from ouroboros_consensus_tpu.ops.pk import kernels

    real = getattr(kernels, "_FIRST_EXEC", None)
    if not isinstance(real, set):
        yield False
        return
    kernels._FIRST_EXEC = forgetful = _NoFirsts(real)
    try:
        yield True
    finally:
        real.update(set.__iter__(forgetful))
        kernels._FIRST_EXEC = real


def wrong_header_cases(inp: Inputs, headers, mix: dict, seed: int,
                       validate_chain=None):
    """A verifier that answers "valid" to everything agrees with the
    reference on an honest chain. So one production-width window of the
    chain's own headers goes through `validate_chain(backend="device")`
    once for each corruption of the mix, with ONE lane corrupted, and has
    to come back with the reference's first failure: the same index, the
    same error with the same fields, the same state. -> [case, ...]"""
    kinds = mix["corrupt"]
    w0, window, before = _full_window(inp, headers)
    width = len(window)
    rng = random.Random(seed)
    lanes = sorted(rng.sample(range(width // 2, width), len(kinds)))
    views = [_to_view(h) for h in window]
    st0 = _to_state(before.state)
    cases = []
    for what, lane in zip(kinds, lanes):
        bad = corrupt(what, window[lane], inp)
        want = ref.replay(inp.rparams, inp.pool_distr,
                          window[:lane] + [bad], st=before.state,
                          crypto_at=(lane,))
        hvs = list(views)
        hvs[lane] = _to_view(bad)
        got = _validate_window(inp, hvs, st0, validate_chain)
        cases.append({
            "corrupted": what, "lane": lane, "window_start": w0,
            "reference": [want.n_valid, want.error],
            "program": [got.n_valid, error_doc(got.error)],
            "agree": (want.error is not None and want.n_valid == lane
                      and got.n_valid == want.n_valid
                      and error_doc(got.error) == want.error
                      and state_doc(got.state) == want.state.doc()),
        })
    return cases


def judge(inp: Inputs, results, mix: dict, seed: int, validate_chain=None):
    """-> (correct, compared, failed, detail). `compared` is each number
    compared with its limit; every comparison is exact, so every limit is
    0. Run once the window has closed and the peak has been read."""
    t0 = time.monotonic()
    headers = ref.read_chain(inp.path)[:inp.headers]
    rng = random.Random(seed ^ 0x5EED)
    k = min(mix["reference_sample"], len(headers))
    # the genesis header (its own layout) and the first header of every
    # epoch (the nonce rotation) are always in the sample
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
    detail = {"reference_headers": len(headers),
              "body_bytes": max(len(h.signed_bytes) for h in headers),
              "reference_crypto_verified": want.n_crypto,
              "reference_n_valid": want.n_valid,
              "reference_error": want.error,
              "reference_s": round(t1 - t0, 3),
              "wrong_header_s": round(time.monotonic() - t1, 3),
              "wrong_header_cases": cases}
    return correct, compared, failed, detail


# ---------------------------------------------------------------------------
# one run
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
    # both native libraries must be REAL: a missing compiler otherwise
    # turns into pure-Python signing and scanning, and reads as a slow chip
    if native_loader.load() is None or native_loader.load_crypto() is None:
        raise FailedRun("native/headerscan.cpp or native/hostcrypto.cpp did "
                        "not build or load (no g++?)")
    inp = make_inputs(cell, args.seed, rehearsal)
    emit("chain", path=os.path.relpath(inp.path), reused=inp.reused,
         forge_s=round(inp.forge_s, 3), headers=inp.headers,
         max_batch=inp.max_batch, seams=seams, cache_dir=cache_dir)

    # -- set-up: one whole replay of the cell's own chain pays every trace,
    # lowering, compile or load of a stored program, and the first parse.
    # The flight recorder is installed after it: installed, it makes each
    # stage's first execute lower its program a second time to count its
    # resources, which serves no request.
    mark = cc.mark()
    with every_program_stored():
        r0, wall0 = replay_once(inp)
    report = WARMUP.report()
    stages = report["stages"]
    emit("setup_replay", wall_s=round(wall0, 3), n_valid=r0.n_valid,
         error=repr(r0.error), built=cc.since(mark),
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
            # a traced run profiles a short stretch of its window, laid
            # over the moment a device window retires and the next starts
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
    # the rate is all the headers of the window over all its wall; what
    # the replays' walls say beside it are per-layer metrics
    rate = headers_done / window_s
    stats = replay_rate.window_stats(headers_done, walls, window_s)
    emit("window", seconds=round(window_s, 4), replays=len(results),
         replay_headers_per_s=rate, **stats,
         replay_walls_s=[round(w, 3) for w in walls], headers=headers_done,
         # per device window, for the day a run reads far off
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
              "first window: the north-star ratio's base; not part of "
              "`correct`")

    # the profiler's timer thread has been writing the trace out meanwhile
    trace_path = stretch.path() if tracing else None

    phase_wall: dict = {}
    for r in results:
        for k, v in (r.phases or {}).items():
            phase_wall[k] = phase_wall.get(k, 0.0) + v
    sources = {
        "replays": len(results),
        "window_stats": stats,
        "phase_wall": phase_wall,
        "window_spans": [dataclasses.asdict(s) for s in spans],
        "counters": {
            "headers": headers_done,
            "windows": len(staged),
            "h2d_bytes": sum(r.h2d_bytes for r in results),
            "d2h_bytes": sum(r.d2h_bytes for r in results),
            "lanes_live": sum(e.lanes for e in staged),
            "lanes_padded": sum(e.lanes_padded for e in staged),
        },
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


if __name__ == "__main__":
    # the forging child: python3 -m benchmark.traffic.replay <cell> <seed>
    from benchmark.manifest import Manifest

    forge_chain(Manifest().cell(sys.argv[1]), int(sys.argv[2]),
                "--cpu-rehearsal" in sys.argv[3:])
