"""Traffic kind `replay_stake`: kind `replay` over a chain that MANY pools
forged, each by its stake.

What differs from `traffic/replay.py`, and only that, is here: the
deployment (the configuration's `pools` and `stake` law make the ledger
view), how the chain is forged, and who signs a corrupted header again (its
own issuer). The timed path, the checks that nothing hid the chip and the
comparison that decides `correct` are `replay`'s, imported; `judge` and
`run` are copies that call this module's `make_inputs` and
`wrong_header_cases`, number for number. Folding the two kinds into one is
a `benchmark` issue's (PERF.md section 7): this PR may edit no file that is
here.

The forge. A chain of N pools is an election over every (slot, pool) pair:
512 x 85,000 = 4.35e7 VRF evaluations, hours on the host. The program's
leader-value sweep (`protocol/forge.LeaderSweep`, ops/pk/elect.py) makes it
on the chip in about 90 s. So this process, which holds the chip, elects,
and a child held to the CPU proves the winners and assembles the blocks
(`synthesize(elector=...)`), asking for each window's rows over a pipe:

    child -> parent   {"elect": [lo, hi], "eta0": "<hex>" | null}
    parent -> child   [[slot, pool index], ...]

An epoch's nonce comes of the blocks of the epoch before, so the sweep of an
epoch starts at the child's first question about it and runs ahead of the
later ones. A rehearsal on the CPU has no chip to elect on: its child elects
for itself, on the host, over the mix's 8 pools.
"""

from __future__ import annotations

import dataclasses
import json
import os
import random
import select
import shutil
import subprocess
import sys
import time

from benchmark import harness, replay_rate, xplane
from benchmark.harness import FailedRun, emit
from benchmark.reference import praos as ref
from benchmark.reference import stake as ref_stake
from benchmark.traffic.replay import (  # noqa: F401 - run.py, control.py
    BENCH, CACHE, FORGE_TIMEOUT_S, Inputs, _full_window, _protocol,
    _to_state, _to_view, _validate_window, check_seams, corrupt, error_doc,
    every_program_stored, mix_of, nothing_hid_the_chip, place_caches,
    replay_once, state_doc)


@dataclasses.dataclass
class StakeInputs(Inputs):
    # what the forge cost, for the `chain` line (None: chain reused)
    forge: dict | None = None


# ---------------------------------------------------------------------------
# the input, from the seed
# ---------------------------------------------------------------------------


def _deployment(cell, seed: int, rehearsal: bool):
    """-> (params, pools, ledger view, the chain's home), from the seed:
    the pool of rank r is the program's `make_pool(seed + r - 1)`, its stake
    the configuration's law at rank r."""
    from ouroboros_consensus_tpu.protocol import praos
    from ouroboros_consensus_tpu.testing import fixtures

    cfg, mix = cell.config, mix_of(cell, rehearsal)
    proto = _protocol(cfg)
    n = mix.get("pools", cfg["pools"])
    pools = [fixtures.make_pool(seed + i, kes_depth=proto["kes_depth"])
             for i in range(n)]
    tag = "rehearsal-" if rehearsal else ""
    home = os.path.join(CACHE, f"{tag}{cell.config_name}-"
                               f"{cell.traffic_name}-s{seed}")
    lview = fixtures.make_ledger_view(
        pools, stakes=ref_stake.stakes(cfg["stake"], n))
    return praos.PraosParams(**proto), pools, lview, home


def forge_chain(cell, seed: int, rehearsal: bool, elector=None) -> None:
    """What the forging child does: the chain of the seed, on disk under
    benchmark/_cache/ with a COMPLETE marker; the election `elector`'s
    where there is one."""
    from ouroboros_consensus_tpu.tools import db_synthesizer as synth

    mix = mix_of(cell, rehearsal)
    params, pools, lview, home = _deployment(cell, seed, rehearsal)
    limit = (synth.ForgeLimit(blocks=mix["blocks"]) if mix.get("blocks")
             else synth.ForgeLimit(epochs=mix["epochs"]))
    path = os.path.join(home, "chain")
    shutil.rmtree(home, ignore_errors=True)
    os.makedirs(path)
    # vrf_backend="host": the child must not touch the device
    res = synth.synthesize(path, params, pools, lview, limit,
                           vrf_backend="host", elector=elector)
    with open(os.path.join(home, "COMPLETE"), "w") as f:
        f.write(str(res.n_blocks))


class _Election:
    """The parent's side of the pipe: the leader-value sweep of one epoch
    at a time, started at the child's first question about the epoch and
    kept ahead of the later ones."""

    def __init__(self, params, pools, lview):
        from ouroboros_consensus_tpu.protocol import forge

        if not hasattr(forge, "LeaderSweep"):
            raise FailedRun("this program has no leader-value election "
                            "(protocol/forge.LeaderSweep): it cannot forge "
                            "this configuration's chain", rc=1)
        self.params = params
        self.sweep = forge.LeaderSweep(params, pools)
        self.thr = forge.pool_thresholds(params, lview, pools)
        self.programs = forge.SWEEP_PROGRAMS
        self.key = self.rows_of = None
        self.won: dict = {}
        self.upto = 0
        self.pairs = 0
        # the sweeps' wall, first dispatch to last rows, epoch by epoch
        # (the child assembles meanwhile, and paces it: the sweep runs
        # SWEEP_DEPTH dispatches ahead of the child's questions), and
        # the part of it this process stood waiting for the device
        self.seconds = self.wait_s = 0.0
        self.t_first = self.t_last = None

    def rows(self, lo: int, hi: int, eta0):
        length = self.params.epoch_length
        key = (lo // length, eta0)
        t0 = time.monotonic()
        if key != self.key or lo < self.base:
            if self.t_first is not None:
                self.seconds += self.t_last - self.t_first
            self.t_first = t0
            self.key, self.base, self.upto, self.won = key, lo, lo, {}
            self.rows_of = self.sweep.rows(
                self.thr, range(lo, (lo // length + 1) * length), eta0)
        while self.upto < hi:
            chunk, part = next(self.rows_of)
            self.won.update(part)
            self.upto = chunk[-1] + 1
            self.pairs += len(chunk) * len(self.sweep.pools)
        self.t_last = time.monotonic()
        self.wait_s += self.t_last - t0
        return [[s, self.won[s]] for s in range(lo, hi) if s in self.won]

    def cost(self) -> dict:
        wall = self.seconds + (self.t_last - self.t_first
                               if self.t_first is not None else 0.0)
        return {"election_s": round(wall, 3),
                "election_wait_s": round(self.wait_s, 3),
                "pairs": self.pairs,
                "sweep_programs": list(self.programs)}


def _lines(proc, deadline: float, log: str):
    """The child's lines on its standard output, until it closes it."""
    buf = b""
    fd = proc.stdout.fileno()
    while True:
        while b"\n" not in buf:
            left = deadline - time.monotonic()
            if left <= 0 or not select.select([fd], [], [], left)[0]:
                proc.kill()
                raise FailedRun("the forging child did not end",
                                seconds=FORGE_TIMEOUT_S, tail=_tail(log))
            got = os.read(fd, 1 << 16)
            if not got:
                return
            buf += got
        line, buf = buf.split(b"\n", 1)
        yield json.loads(line)


def _tail(log: str) -> str:
    try:
        with open(log, errors="replace") as f:
            return f.read()[-2000:]
    except OSError:
        return ""


def _forge_in_child(cell, seed: int, rehearsal: bool, election) -> None:
    """The chain is assembled by a process of its own, held to the CPU, as
    `traffic/replay`'s is (PERF.md sections 5 and 6 say why); `election`
    answers its questions from this process, which holds the chip."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               **{k: str(v) for k, v in
                  cell.config.get("forge_env", {}).items()})
    cmd = [sys.executable, "-m", "benchmark.traffic.replay_stake", cell.name,
           str(seed)] + (["--cpu-rehearsal"] if rehearsal else [])
    os.makedirs(CACHE, exist_ok=True)
    log = os.path.join(CACHE, f"forge-{cell.name}-s{seed}.log")
    with open(log, "wb") as err:
        proc = subprocess.Popen(cmd, cwd=os.path.dirname(BENCH), env=env,
                                stdin=subprocess.PIPE,
                                stdout=subprocess.PIPE, stderr=err,
                                bufsize=0)
        try:
            for ask in _lines(proc, time.monotonic() + FORGE_TIMEOUT_S, log):
                lo, hi = ask["elect"]
                eta0 = ask["eta0"] and bytes.fromhex(ask["eta0"])
                rows = election.rows(lo, hi, eta0)
                proc.stdin.write(json.dumps(rows).encode() + b"\n")
            rc = proc.wait(timeout=60)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
    if rc:
        raise FailedRun("the forging child failed", child_rc=rc,
                        tail=_tail(log))


def make_inputs(cell, seed: int, rehearsal: bool) -> StakeInputs:
    """Pool credentials, ledger view and chain, all from the seed; the
    chain kept under benchmark/_cache/ as `traffic/replay` keeps its."""
    cfg, mix = cell.config, mix_of(cell, rehearsal)
    params, pools, lview, home = _deployment(cell, seed, rehearsal)
    pool_distr = {p.pool_id: (e.stake, e.vrf_key_hash)
                  for p in pools
                  for e in [lview.pool_distr[p.pool_id]]}
    marker = os.path.join(home, "COMPLETE")
    reused = os.path.exists(marker)
    t0 = time.monotonic()
    forge = None
    if not reused:
        election = None if rehearsal else _Election(params, pools, lview)
        _forge_in_child(cell, seed, rehearsal, election)
        forge = election.cost() if election is not None else None
    with open(marker) as f:
        n_blocks = int(f.read())
    max_headers = mix.get("max_headers")
    return StakeInputs(
        os.path.join(home, "chain"), params, ref.Params(**_protocol(cfg)),
        pools, lview, pool_distr, mix.get("max_batch", cfg["max_batch"]),
        max_headers, reused, time.monotonic() - t0,
        min(n_blocks, max_headers or n_blocks), forge)


# ---------------------------------------------------------------------------
# what `correct` compares: `replay.judge`'s numbers; each wrong header is
# signed again by its own issuer, and none is the top pool's
# ---------------------------------------------------------------------------


def wrong_header_cases(inp: Inputs, headers, mix: dict, seed: int,
                       validate_chain=None):
    """`replay.wrong_header_cases` over a window of many issuers: the
    corrupted lanes are drawn from the seed among those NOT forged by the
    pool of rank 1, and `replay.corrupt` is handed the lane's issuer."""
    kinds = mix["corrupt"]
    w0, window, before = _full_window(inp, headers)
    width = len(window)
    by_key = {p.vk_cold: p for p in inp.pools}
    rng = random.Random(seed)
    others = [i for i in range(width // 2, width)
              if len(inp.pools) == 1
              or window[i].vk_cold != inp.pools[0].vk_cold]
    lanes = sorted(rng.sample(others, len(kinds)))
    views = [_to_view(h) for h in window]
    st0 = _to_state(before.state)
    cases = []
    for what, lane in zip(kinds, lanes):
        issuer = dataclasses.replace(
            inp, pools=[by_key[window[lane].vk_cold]])
        bad = corrupt(what, window[lane], issuer)
        want = ref.replay(inp.rparams, inp.pool_distr,
                          window[:lane] + [bad], st=before.state,
                          crypto_at=(lane,))
        hvs = list(views)
        hvs[lane] = _to_view(bad)
        got = _validate_window(inp, hvs, st0, validate_chain)
        cases.append({
            "corrupted": what, "lane": lane, "window_start": w0,
            "issuer_rank": inp.pools.index(issuer.pools[0]) + 1,
            "window_issuers": len({h.vk_cold for h in window}),
            "reference": [want.n_valid, want.error],
            "program": [got.n_valid, error_doc(got.error)],
            "agree": (want.error is not None and want.n_valid == lane
                      and got.n_valid == want.n_valid
                      and error_doc(got.error) == want.error
                      and state_doc(got.state) == want.state.doc()),
        })
    return cases


def judge(inp: Inputs, results, mix: dict, seed: int, validate_chain=None):
    """`replay.judge`, number for number, over this module's
    `wrong_header_cases`. The state compared holds every issuer's
    counter."""
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
    detail = {"reference_headers": len(headers),
              "body_bytes": max(len(h.signed_bytes) for h in headers),
              "reference_crypto_verified": want.n_crypto,
              "reference_n_valid": want.n_valid,
              "reference_error": want.error,
              "reference_issuers": len({h.vk_cold for h in headers}),
              "reference_counters": len(want_state["counters"]),
              "reference_s": round(t1 - t0, 3),
              "wrong_header_s": round(time.monotonic() - t1, 3),
              "wrong_header_cases": cases}
    return correct, compared, failed, detail


# ---------------------------------------------------------------------------
# one run: `replay.run` over this module's `make_inputs` and `judge`
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
    mark = cc.mark()
    inp = make_inputs(cell, args.seed, rehearsal)
    emit("chain", path=os.path.relpath(inp.path), reused=inp.reused,
         forge_s=round(inp.forge_s, 3), headers=inp.headers,
         pools=len(inp.pools), max_batch=inp.max_batch, seams=seams,
         cache_dir=cache_dir,
         # the election, made in this process on the chip: its wall
         # (the child assembling meanwhile), its share of the forge, and
         # every program the forge built or loaded
         **(dict(inp.forge, election_share=round(
             inp.forge["election_s"] / max(inp.forge_s, 1e-9), 3),
             forge_built=cc.since(mark)) if inp.forge else {}))

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
         stage_ms=[round(e.stage_s * 1e3) for e in spans],
         # the first replay's windows, by their issuers
         issuers=[e.issuers for e in spans[:len(spans) // len(results)]],
         **hid)

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
    # the forging child: python3 -m benchmark.traffic.replay_stake <cell>
    # <seed>; its standard output is the pipe to the electing parent, so
    # whatever else would print there goes to standard error
    from benchmark.manifest import Manifest

    pipe = os.fdopen(os.dup(sys.stdout.fileno()), "w")
    os.dup2(sys.stderr.fileno(), sys.stdout.fileno())
    rehearse = "--cpu-rehearsal" in sys.argv[3:]

    def ask(slots, eta0):
        pipe.write(json.dumps({"elect": [slots.start, slots.stop],
                               "eta0": eta0 and eta0.hex()}) + "\n")
        pipe.flush()
        return [tuple(r) for r in json.loads(sys.stdin.readline())]

    forge_chain(Manifest().cell(sys.argv[1]), int(sys.argv[2]), rehearse,
                None if rehearse else ask)
