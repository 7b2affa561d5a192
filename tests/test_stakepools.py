"""A chain that many pools forged, each by its stake (PR 32: the deployment
`praos-bc-stakepools`, the cell `replay-stakepools-2epoch`).

  * the stake law: the program's (`fixtures.capped_zipf_stakes`) against the
    benchmark's plain reference (`benchmark/reference/stake.py`);
  * the forge: every engine, and an election handed over as rows
    (`synthesize(elector=...)`), forges the reference loop's chain byte for
    byte on 8 pools of capped-Zipf stake. The device engines run the stub
    hash-twin kernels, as tests/test_forge.py's do; the real leader-value
    kernel against the host prover is the slow-tier test at the end;
  * the replay: `revalidate` over such a chain against `benchmark/reference`
    (verdicts, the final state with every issuer's counter), and one wrong
    header a corruption, signed again by its own issuer;
  * the new `WindowSpan` fields against counts taken from the chain.

Every test has its own time limit (`limit`): no plugin here offers one.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import signal
from fractions import Fraction

import numpy as np
import pytest

from benchmark.reference import praos as ref
from benchmark.reference import stake as ref_stake
from benchmark.traffic import replay as bench_replay
from benchmark.traffic import replay_stake
from ouroboros_consensus_tpu import obs
from ouroboros_consensus_tpu.obs.warmup import WARMUP
from ouroboros_consensus_tpu.protocol import batch as pbatch
from ouroboros_consensus_tpu.protocol import forge as forge_mod
from ouroboros_consensus_tpu.protocol import praos
from ouroboros_consensus_tpu.testing import fixtures, stubs
from ouroboros_consensus_tpu.tools import db_analyser as ana
from ouroboros_consensus_tpu.tools import db_synthesizer as synth
from ouroboros_consensus_tpu.utils import trace as T


def limit(seconds: int):
    """The test's own time limit: past it the test fails where it stands
    (SIGALRM on the worker's main thread, where pytest runs the test)."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*a, **k):
            def late(*_):
                raise TimeoutError(f"{fn.__name__} passed its {seconds} s")

            old = signal.signal(signal.SIGALRM, late)
            signal.alarm(seconds)
            try:
                return fn(*a, **k)
            finally:
                signal.alarm(0)
                signal.signal(signal.SIGALRM, old)

        return wrapper

    return deco


PARAMS = praos.PraosParams(
    slots_per_kes_period=100, max_kes_evolutions=62, security_param=4,
    active_slot_coeff=Fraction(1, 2), epoch_length=60, kes_depth=3,
)
N_POOLS = 8
POOLS = [fixtures.make_pool(70 + i, kes_depth=3) for i in range(N_POOLS)]
# the cap at 1/3: ranks 1-3 saturated, a Zipf tail below (the benchmark's
# 1/18 would cap all of 8 pools)
STAKES = fixtures.capped_zipf_stakes(N_POOLS, cap_weight=Fraction(1, 3))
LVIEW = fixtures.make_ledger_view(POOLS, STAKES)


def _chain(db):
    imm = ana.open_immutable(str(db))
    return [(e.slot, e.block_no, e.hash_, raw)
            for e, raw in imm.stream_all()]


# -- the stake law ----------------------------------------------------------


@limit(30)
@pytest.mark.parametrize("n,cap", [(8, Fraction(1, 3)), (512, Fraction(1, 18)),
                                   (3000, Fraction(1, 105))])
def test_stake_law_program_against_reference(n, cap):
    got = fixtures.capped_zipf_stakes(n, cap_weight=cap)
    want = ref_stake.stakes(
        {"law": "capped-zipf", "exponent": 1, "cap_weight": str(cap)}, n)
    assert got == want and all(isinstance(s, Fraction) for s in got)
    assert sum(got) == 1  # exactly
    assert got == sorted(got, reverse=True)  # rank order
    k = int(1 / cap)
    assert len(set(got[:k])) == 1 and got[k] < got[k - 1]  # the cap
    assert got[-1] * n * cap == got[0]  # the tail is 1/r under it


@limit(30)
def test_the_configurations_stakes_are_what_the_issue_says():
    from benchmark.manifest import Manifest

    cfg = Manifest().cell("replay-stakepools-2epoch").config
    s = ref_stake.stakes(cfg["stake"], cfg["pools"])
    assert len(s) == 512 and sum(s) == 1
    assert 0.0128 < s[0] == s[17] < 0.0130 and s[18] < s[17]  # 1.29% x 18
    assert 6.5 < s[0] * 512 < 6.7  # 6.6 x the mean share
    assert 0.00045 < s[-1] < 0.00046  # ~19 blocks of 42,500
    with pytest.raises(ValueError):
        ref_stake.stakes({"law": "uniform"}, 8)


# -- the forge: every engine, one chain -------------------------------------


@pytest.fixture()
def fresh(monkeypatch):
    WARMUP.reset()
    obs.reset_for_tests()
    for var in ("OCT_FORGE_DEVICE", "OCT_VRF_BATCH", "OCT_TRACE"):
        monkeypatch.delenv(var, raising=False)
    synth._REPLAY_MEMO.clear()
    yield
    WARMUP.reset()
    obs.reset_for_tests()
    synth._REPLAY_MEMO.clear()


def _forge(path, monkeypatch, lever, leader=None, elector=None):
    if lever is None:
        monkeypatch.delenv("OCT_FORGE_DEVICE", raising=False)
    else:
        monkeypatch.setenv("OCT_FORGE_DEVICE", lever)
    monkeypatch.setattr(forge_mod, "LEADER_SWEEP", leader)
    return synth.synthesize(
        str(path), PARAMS, POOLS, LVIEW, synth.ForgeLimit(slots=150),
        txs_per_block=1, chunk_size=PARAMS.epoch_length, elector=elector)


@limit(600)
def test_every_engine_forges_the_loops_chain_on_zipf_stake(
        tmp_path, monkeypatch, fresh):
    """loop / host / device (full-prove sweep) / device (leader-value
    sweep) / rows handed over: byte-identical, 150 slots over three
    epochs, the first under the neutral nonce."""
    stubs.install_stub_forge(monkeypatch, bucket=256)
    r_loop = _forge(tmp_path / "loop", monkeypatch, "0")
    want = _chain(tmp_path / "loop")
    assert r_loop.n_blocks == len(want) > 40
    for name, lever, leader in (("host", None, None), ("sweep", "1", False),
                                ("leader", "1", True)):
        r = _forge(tmp_path / name, monkeypatch, lever, leader)
        assert _chain(tmp_path / name) == want, name
        assert r.final_state == r_loop.final_state, name
    # the election made elsewhere, handed over as rows
    monkeypatch.setattr(forge_mod, "LEADER_SWEEP", True)
    sweep = forge_mod.LeaderSweep(PARAMS, POOLS)
    thr = forge_mod.pool_thresholds(PARAMS, LVIEW, POOLS)
    asked = []

    def elector(slots, eta0):
        asked.append((slots.start, slots.stop, eta0))
        return [r for _c, part in sweep.rows(thr, slots, eta0) for r in part]

    r = _forge(tmp_path / "rows", monkeypatch, None, elector=elector)
    assert _chain(tmp_path / "rows") == want
    assert r.final_state == r_loop.final_state
    assert asked[0][2] is None and asked[-1][2] is not None
    # every pool forges, the three saturated ones most
    from ouroboros_consensus_tpu.block.praos_block import Block

    keys = [p.vk_cold for p in POOLS]
    by_pool = np.zeros(N_POOLS, int)
    for *_x, raw in want:
        by_pool[keys.index(Block.from_bytes(raw).header.to_view().vk_cold)] += 1
    assert (by_pool > 0).sum() >= 6 and by_pool[:3].sum() > by_pool[3:].sum()


@limit(120)
def test_an_elector_and_a_ledger_in_the_loop_do_not_mix(tmp_path):
    with pytest.raises(ValueError, match="elector"):
        synth.synthesize(str(tmp_path / "x"), PARAMS, POOLS, LVIEW,
                         synth.ForgeLimit(slots=4), ledger=object(),
                         genesis_state=object(), elector=lambda s, e: [])


@limit(300)
def test_leader_sweep_resolves_the_ambiguous_band_exactly(monkeypatch,
                                                          fresh):
    """Every pair is thrown into the bracket (lo = 0, hi = 2^256 - 1): the
    sweep then decides each on the host, by the exact check, and elects
    what the reference elects."""
    stubs.install_stub_forge(monkeypatch, bucket=64)
    sweep = forge_mod.LeaderSweep(PARAMS, POOLS)
    lo, hi, sigmas = forge_mod.pool_thresholds(PARAMS, LVIEW, POOLS)
    wide = (np.zeros_like(lo), np.full_like(hi, 255), sigmas)
    eta0 = b"\x11" * 32
    got = [r for _c, part in sweep.rows(wide, range(60, 80), eta0)
           for r in part]
    want = [(e.slot, e.pool) for e in forge_mod._elect_window_reference(
        PARAMS, POOLS, LVIEW, range(60, 80), eta0)]
    assert got == want and len(want) >= 5


# -- the replay, against the benchmark's plain reference ---------------------


CORRUPT = ["ocert-signature", "kes-signature", "vrf-proof"]


@pytest.fixture(scope="module")
def stake_db(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("stake") / "db")
    res = synth.synthesize(path, PARAMS, POOLS, LVIEW,
                           synth.ForgeLimit(slots=160), chunk_size=32)
    assert res.n_blocks > 60
    return path, res


def _inputs(path, max_batch=16):
    rparams = ref.Params(**{f.name: getattr(PARAMS, f.name)
                            for f in dataclasses.fields(ref.Params)})
    distr = {p.pool_id: (e.stake, e.vrf_key_hash) for p in POOLS
             for e in [LVIEW.pool_distr[p.pool_id]]}
    return replay_stake.StakeInputs(
        path, PARAMS, rparams, POOLS, LVIEW, distr, max_batch, None, True,
        0.0, len(ref.read_chain(path)))


@limit(300)
def test_revalidate_agrees_with_the_reference_on_a_chain_of_many_issuers(
        stake_db, monkeypatch):
    """Verdicts and the whole final state, every issuer's counter in it,
    from the program (native backend: the CPU twin of the device path
    costs minutes of XLA:CPU compile a layout; the device path over this
    chain is the next test's, under stub crypto, and the chip's) against
    the pure-Python reference that verifies EVERY header's signatures; then
    one wrong header a corruption, signed again by its own issuer, none the
    top pool's, refused at its own index with the reference's error."""
    path, forged = stake_db
    monkeypatch.setattr(bench_replay, "BACKEND", "native")
    inp = _inputs(path)
    headers = ref.read_chain(path)
    want = ref.replay(inp.rparams, inp.pool_distr, headers,
                      crypto_at=range(len(headers)))
    assert want.error is None and want.n_valid == len(headers)
    r, _wall = replay_stake.replay_once(inp)
    assert (r.n_valid, r.error) == (want.n_valid, None)
    doc = replay_stake.state_doc(r.final_state)
    assert doc == want.state.doc()
    assert len(doc["counters"]) == len({h.vk_cold for h in headers}) >= 6
    assert doc == replay_stake.state_doc(forged.final_state)
    correct, compared, failed, detail = replay_stake.judge(
        inp, [r], {"reference_sample": 8, "corrupt": CORRUPT}, 77)
    assert correct and failed == 0
    assert all(c["value"] == 0 for c in compared.values())
    errors = [c["reference"][1][0] for c in detail["wrong_header_cases"]]
    assert errors == ["InvalidSignatureOCERT", "InvalidKesSignatureOCERT",
                      "VRFKeyBadProof"]
    for c in detail["wrong_header_cases"]:
        assert c["agree"] and c["issuer_rank"] != 1
        assert c["window_issuers"] >= 4


@limit(300)
def test_window_span_counts_who_the_window_holds(stake_db, monkeypatch):
    """`issuers` and `kes_tails` of every retired window against
    counts taken from the chain's own headers; the two new spans' walls
    inside their parents'."""
    path, _ = stake_db
    mp = pytest.MonkeyPatch()
    before = set(pbatch._JIT)
    mp.delenv("OCT_STAGE_THREAD", raising=False)
    mp.setattr(pbatch, "_WARM_SEEN", set())
    stubs.install_stub_crypto(mp)
    obs.reset_for_tests()
    rec = obs.install()
    try:
        r = ana.revalidate(path, PARAMS, LVIEW, backend="device",
                           validate_all="stream", max_batch=16)
    finally:
        obs.uninstall()
        events = [e for _, e in rec.timed_events()]
        obs.reset_for_tests()
        mp.undo()
        for k in set(pbatch._JIT) - before:
            del pbatch._JIT[k]
        WARMUP.reset()
    assert r.error is None and r.n_valid == r.n_blocks
    spans = [e for e in events if isinstance(e, T.WindowSpan)]
    headers = ref.read_chain(path)
    assert sum(s.lanes for s in spans) == len(headers)
    at = 0
    for s in spans:
        win = headers[at:at + s.lanes]
        at += s.lanes
        assert s.outcome.startswith("packed")  # the twin aggregates
        assert s.issuers == len({h.vk_cold for h in win})
        assert s.kes_tails == len({h.kes_sig[64:] for h in win})
        assert 0 < s.prechecks_s <= s.stage_s
        assert 0 < s.epilogue_counters_s <= s.epilogue_s
    assert max(s.issuers for s in spans) >= 5
    assert max(s.kes_tails for s in spans) > max(s.issuers for s in spans) \
        or PARAMS.slots_per_kes_period > 16 * 2
    # the same fields reach the benchmark's reader as plain values
    from benchmark import readers

    srcs = {"window_spans": [dataclasses.asdict(s) for s in spans]}
    assert readers.read({"kind": "window_span", "key": "issuers"}, srcs) == \
        pytest.approx(sum(s.issuers for s in spans) / len(spans))
    assert readers.read({"kind": "window_span", "key": "nothing"},
                        srcs) is None


# -- the packed window's dedup tables ----------------------------------------


@limit(30)
def test_table_shapes_follow_the_window_not_who_happens_to_be_in_it():
    """The rows of `kt_tab` / `thr_tab` are the bucket of what a window of
    these lanes and KES periods COULD hold under this ledger view: one
    pool keeps 8 and 8; among 512 pools a 13-lane window is 16 and 16
    whether 8 issuers forged it or 13; a count past the bound (a pool
    that changed its hot key, unknown pools) is bucketed itself."""
    import types

    params = praos.PraosParams(
        slots_per_kes_period=3600, max_kes_evolutions=62,
        security_param=2160, active_slot_coeff=Fraction(1, 2),
        epoch_length=43200, kes_depth=7)

    def view(n):
        return types.SimpleNamespace(pool_distr=dict.fromkeys(range(n)))

    full = np.arange(20000, 20000 + 2 * 8192, 2)  # 8192 lanes, 5 periods
    for tails, thr in ((1, 1), (3, 1), (6, 1)):
        assert pbatch._table_rows(params, view(1), full, tails, thr) == (8, 8)
    few = np.arange(100, 126, 2)  # 13 lanes, one period
    for issuers in (7, 8, 9, 13):
        assert pbatch._table_rows(params, view(512), few, issuers,
                                  issuers) == (16, 16)
    for tails in (900, 1500, 2049):
        assert pbatch._table_rows(params, view(512), full, tails,
                                  500) == (4096, 512)
    assert pbatch._table_rows(params, view(512), full, 5000, 600) == \
        (8192, 1024)
    assert pbatch._table_rows(params, view(2), few, 3, 2) == (8, 8)


# -- the counter gate of a clean window's epilogue ---------------------------


def _gate_by_issuer(cnt, inv, uniq_hk, counters, pool_distr):
    """The gate as it stood until PR 32, one pass an issuer: the rule
    spelled out, kept here as what `_counters_gate` is compared with."""
    counters = dict(counters)
    for j, hk in enumerate(uniq_hk):
        m = pbatch._counter_m(hk, counters, pool_distr)
        if m is None:
            return None
        cs = cnt[inv == j]
        d = np.diff(cs)
        if not (m <= cs[0] <= m + 1 and (d >= 0).all() and (d <= 1).all()):
            return None
        counters[hk] = int(cs[-1])
    return counters


@limit(60)
def test_counters_gate_by_one_sort_is_the_gate_by_issuer():
    """500 random windows of up to 40 issuers: honest counters (constant
    or stepping by one), and the same with a drop, a jump of two, a
    first counter off its baseline, an issuer with no stake and no
    counter; accepted and declined alike, and the same counters out."""
    rng = np.random.default_rng(32)
    seen = {"ok": 0, "declined": 0}
    for _ in range(500):
        n_iss = int(rng.integers(1, 40))
        lanes = int(rng.integers(n_iss, 300))
        inv = np.concatenate([np.arange(n_iss),
                              rng.integers(0, n_iss, lanes - n_iss)])
        rng.shuffle(inv)
        # uniq order is by first appearance in a real window; the gate
        # must not lean on it
        hks = [bytes([j]) * 28 for j in range(n_iss)]
        base = {hk: int(rng.integers(0, 5)) for hk in hks
                if rng.random() < 0.7}
        distr = {hk: None for hk in hks}
        cnt = np.zeros(lanes, np.int64)
        for j, hk in enumerate(hks):
            idx = np.flatnonzero(inv == j)
            steps = (rng.random(len(idx)) < 0.1).astype(np.int64)
            steps[0] = rng.integers(0, 2)
            cnt[idx] = base.get(hk, 0) + np.cumsum(steps)
        fault = rng.integers(0, 6)
        if fault == 0:
            cnt[rng.integers(0, lanes)] -= 1
        elif fault == 1:
            cnt[rng.integers(0, lanes)] += 2
        elif fault == 2:
            j = int(rng.integers(0, n_iss))
            cnt[inv == j] += 2
        elif fault == 3:
            hk = hks[int(rng.integers(0, n_iss))]
            distr.pop(hk)
            base.pop(hk, None)
        want = _gate_by_issuer(cnt, inv, hks, base, distr)
        got = pbatch._counters_gate(cnt, inv, hks, base, distr)
        assert got == want
        seen["ok" if want is not None else "declined"] += 1
    assert min(seen.values()) > 100, seen


# -- the real kernel (slow tier: ~2 min of XLA:CPU compile) -----------------


@pytest.mark.slow
def test_leader_value_core_brackets_what_the_host_prover_outputs():
    """ops/pk/elect.elect_core over 8 pairs against the host prover's beta:
    the bracket set one above / at each pair's own leader value, so the
    certain-win and the ambiguous verdict both hang on every byte of it."""
    import jax
    from jax import numpy as jnp

    from ouroboros_consensus_tpu.ops.host import ed25519 as he
    from ouroboros_consensus_tpu.ops.host import fast
    from ouroboros_consensus_tpu.ops.host.hashes import blake2b_256
    from ouroboros_consensus_tpu.ops.pk import elect
    from ouroboros_consensus_tpu.protocol import nonces

    eta0 = hashlib.blake2b(b"eta", digest_size=32).digest()
    alphas = [nonces.mk_input_vrf(1000 + i, eta0 if i else None)
              for i in range(N_POOLS)]
    lv = [blake2b_256(b"L" + fast.ecvrf_proof_to_hash(
        fast.ecvrf_prove(p.vrf_seed, a))) for p, a in zip(POOLS, alphas)]

    def rows(bs):
        return jnp.asarray(np.stack(
            [np.frombuffer(b, np.uint8) for b in bs]).T.astype(np.int32))

    def inc(b):
        return (int.from_bytes(b, "big") + 1).to_bytes(32, "big")

    x = [he.secret_expand(p.vrf_seed)[0].to_bytes(32, "little")
         for p in POOLS]
    lo = [inc(v) if i % 2 else v for i, v in enumerate(lv)]
    win, amb = jax.jit(elect.elect_core)(
        rows(x), rows([p.vrf_vk for p in POOLS]), rows(alphas), rows(lo),
        rows([inc(v) for v in lv]))
    assert np.asarray(win).tolist() == [bool(i % 2) for i in range(N_POOLS)]
    assert np.asarray(amb).tolist() == [not i % 2 for i in range(N_POOLS)]
