"""A Cardano chain (Byron → Shelley → Babbage, era-tagged blocks) on the
normal path: `db_synthesizer` forges it, `db_analyser.revalidate` replays
it through ONE `validate_stream` (the XLA twin with the stub cores, and
the pk path with stand-in cores), and every backend agrees with the plain
reference (`benchmark/reference/cardano.py`): the valid count, the first
error with its fields, the final state and the state on each side of
both hard forks. At a toy scale: a 40-slot Byron epoch, 120-slot epochs
after, k = 10, three genesis delegates."""

import dataclasses
import functools
import os
import shutil
from fractions import Fraction

import pytest

from benchmark.reference import cardano as ref
from benchmark.reference import pbft as rpbft
from benchmark.reference import praos as rpraos
from benchmark.reference import tpraos as rtpraos
from ouroboros_consensus_tpu.hardfork import cardano
from ouroboros_consensus_tpu.protocol import batch as pbatch
from ouroboros_consensus_tpu.tools import db_analyser as ana
from ouroboros_consensus_tpu.tools import db_synthesizer as synth

SEED = 31
NONCE = b"\x3c" * 32
N_SLOTS = 40 + 120 + 110


def _deployment():
    return cardano.deployment(
        SEED, delegates=3, byron_epoch_length=40, k=10,
        threshold=Fraction(1, 2), epoch_length=120, kes_depth=3,
        slots_per_kes_period=40, genesis_nonce=NONCE)


def _rparams(views):
    base = rpraos.Params(40, 62, 10, Fraction(1, 2), 120, 3)
    return ref.CParams(
        rpbft.Params(10, Fraction(1, 2), dict(views.byron.delegates)), 40,
        rtpraos.TParams(base, Fraction(1, 2),
                        tuple((g.vk_cold, g.vrf_key_hash)
                              for g in views.shelley.gen_delegs)),
        base, 1, 2, NONCE)


@functools.cache
def _chain(tmp_root):
    path = os.path.join(tmp_root, "chain")
    shutil.rmtree(path, ignore_errors=True)
    params, creds, views = _deployment()
    res = synth.synthesize(path, params, creds, views,
                           synth.ForgeLimit(slots=N_SLOTS),
                           vrf_backend="host")
    return path, res.n_blocks


@pytest.fixture(scope="module")
def chain(tmp_path_factory):
    return _chain(str(tmp_path_factory.mktemp("cardano")))


def _doc(era, st):
    if era == 0:
        return {"era": 0, "signers": [list(s) for s in st.signers]}
    from benchmark.traffic.replay import state_doc

    return {"era": era, **state_doc(st)}


def _agree(res, walked):
    assert res.n_valid == walked.n_valid
    assert res.era == walked.era
    assert _doc(res.era, res.final_state) == ref.state_doc(walked.era,
                                                            walked.state)
    assert [[a, b, _doc(a, x), _doc(b, y)] for a, b, x, y in
            res.crossings or ()] == [list(c) for c in walked.crossings]


def test_reference_and_every_backend_agree(chain, monkeypatch):
    from ouroboros_consensus_tpu.testing import stubs

    path, n = chain
    params, _creds, views = _deployment()
    headers = ref.read_chain(path)
    eras = [e for e, _h in headers]
    assert eras == sorted(eras) and set(eras) == {0, 1, 2}
    assert len(headers) == n and headers[0][1].ebb
    walked = ref.replay(_rparams(views), _pool_distr(views), headers)
    assert walked.error is None and walked.n_valid == n
    assert [c[:2] for c in walked.crossings] == [(0, 1), (1, 2)]
    for backend in ("host", "native"):
        _agree(ana.revalidate(path, params, views, backend=backend,
                              validate_all="stream"), walked)
    stubs.install_stub_crypto(monkeypatch)
    events = []
    pbatch.set_batch_tracer(events.append)
    try:
        res = ana.revalidate(path, params, views, backend="device",
                             validate_all="stream", max_batch=16,
                             collect_phases=True)
    finally:
        pbatch.set_batch_tracer(None)
    _agree(res, walked)
    # one pipeline, every window packed, the crossings inside it
    spans = [e for e in events if type(e).__name__ == "WindowSpan"]
    assert all(s.outcome == "packed" for s in spans)
    byron = [s for s in spans if s.pbft_lanes]
    assert sum(s.pbft_lanes for s in byron) == sum(
        1 for e, h in headers if e == 0 and not h.ebb)
    assert all(s.pbft_s > 0 and s.vrf_proofs == 0 for s in byron)
    assert sum(s.ebbs for s in spans) == 1  # the Byron epoch's EBB
    labels = [e.label for e in events
              if type(e).__name__ == "EncloseEvent" and e.edge == "start"]
    assert labels.count("validate-chain") == 1
    assert labels.count("era-cross") == 2
    assert "stage.pbft" in labels and "epilogue.pbft" in labels
    assert res.phases["era-cross"] >= 0


def _pool_distr(views):
    return {k: (e.stake, e.vrf_key_hash)
            for k, e in views.babbage.pool_distr.items()}


def _corrupt_chunk(path, tmp_path, at_era, mutate):
    """A copy of the chain with the middle block of era `at_era`
    re-written by `mutate(inner block bytes) -> bytes` -> (its path, the
    block's index)."""
    from ouroboros_consensus_tpu.storage.immutable import ImmutableDB
    from ouroboros_consensus_tpu.utils import cbor

    src = ImmutableDB(os.path.join(path, "immutable"), repair=False)
    blocks = list(src.stream_all())
    idx = [i for i, (_e, raw) in enumerate(blocks) if raw[1] == at_era]
    k = idx[len(idx) // 2]
    bad = str(tmp_path / "bad")
    dst = ImmutableDB(os.path.join(bad, "immutable"), chunk_size=100000)
    for i, (e, raw) in enumerate(blocks):
        if i == k:
            era, inner = cbor.decode(raw)
            raw = cbor.encode([era, mutate(inner)])
        dst.append_block(e.slot, e.block_no, e.hash_, raw)
    dst.flush()
    return bad, k


@pytest.mark.parametrize("at_era", [0, 1, 2])
def test_a_wrong_header_in_each_era(chain, tmp_path, monkeypatch, at_era):
    """A header signed over other bytes than it carries (Byron: the
    delegate's signature; Shelley and Babbage: the KES signature) in the
    middle of its era: every backend stops at its index with the
    reference's error and the state before it."""
    from ouroboros_consensus_tpu.testing import stubs
    from ouroboros_consensus_tpu.utils import cbor

    path, _n = chain
    params, _creds, views = _deployment()

    def mutate(inner):
        blk = cbor.decode(inner)
        if at_era == 0:  # [kind, [to_sign, vk, sig, cert], txs]
            sig = blk[1][2]
            blk[1][2] = sig[:40] + bytes([sig[40] ^ 1]) + sig[41:]
        else:  # [[body, kes_sig], txs]
            sig = blk[0][1]
            blk[0][1] = sig[:40] + bytes([sig[40] ^ 1]) + sig[41:]
        return cbor.encode(blk)

    bad, k = _corrupt_chunk(path, tmp_path, at_era, mutate)
    walked = ref.replay(_rparams(views), _pool_distr(views),
                        ref.read_chain(bad))
    assert walked.n_valid == k and walked.error is not None
    want = {0: "PBftInvalidSignature"}.get(at_era,
                                           "InvalidKesSignatureOCERT")
    assert walked.error[0] == want
    results = [ana.revalidate(bad, params, views, backend=b,
                              validate_all="stream")
               for b in ("host", "native")]
    if at_era == 0:  # the stub's Byron verdicts are the native verifier's
        stubs.install_stub_crypto(monkeypatch)
        results.append(ana.revalidate(bad, params, views, backend="device",
                                      validate_all="stream", max_batch=16))
    for r in results:
        _agree(r, walked)
        assert type(r.error).__name__ == want
        assert dataclasses.asdict(r.error) == walked.error[1]


def test_a_cardano_window_on_the_pk_path(chain, monkeypatch):
    """The whole chain on the `pk` path with stand-in cores (the stage
    programs' wiring: Byron windows run `unpack`, `ed` at three hash
    blocks, `finish_pbft`, `reduce`; no KES or VRF kernel), a Byron
    signature scalar zeroed: refused at its index."""
    import jax
    from jax import numpy as jnp

    from ouroboros_consensus_tpu.ops.pk import curve as pc
    from ouroboros_consensus_tpu.ops.pk import kernels as K
    from ouroboros_consensus_tpu.ops.pk import verify as pv

    path, n = chain
    params, _creds, views = _deployment()

    def ok_of(s):
        return jnp.any(s != 0, axis=0)

    def ed_core(pk, s, hb, hnb):
        return ok_of(s), pc.identity(s.shape[-1])

    def kes_core(vk, period, s, vk_leaf, siblings, hb, hnb, depth):
        return ok_of(s), pc.identity(s.shape[-1])

    def vrf_core_prep(pk, gamma, c, s, alpha):
        pt = pc.identity(s.shape[-1])
        return ok_of(s), pt, pt, pt

    def vrf_core_ladders(c, s, h, y, g):
        return h, g, h, y, g

    def finish_pbft_core(ok, _pt, _r):
        return ok

    def finish_tp_core(ok_ed, _edp, _edr, ok_kes, _kp, _kr, ok_e, _ep, _ce,
                       ok_l, _lp, _cl, b_e, b_l, lo, _hi, over):
        win = jnp.ones_like(ok_ed)
        return pv.TPraosCoreVerdicts(ok_ed, ok_kes, ok_e & ok_l, win, ~win,
                                     b_e[:32], b_l, ok_e, ok_l)

    def finish_core(ok_ed, _edp, _edr, ok_kes, _kp, _kr, ok_v, _vp, c, beta,
                    lo, hi):
        win = jnp.ones_like(ok_ed)
        return pv.CoreVerdicts(ok_ed, ok_kes, ok_v, win, ~win, beta[:32],
                               beta[:32])

    for fn in (ed_core, kes_core, vrf_core_prep, vrf_core_ladders,
               finish_pbft_core, finish_tp_core, finish_core):
        monkeypatch.setattr(pv, fn.__name__, fn)
    monkeypatch.setattr(K, "_jit1",
                        lambda key, fn: jax.jit(lambda *a: fn(*a)))
    monkeypatch.setattr(K, "_FIRST_EXEC", set())
    monkeypatch.setenv("OCT_PK_AOT", "0")
    monkeypatch.setattr(pbatch, "DEVICE_IMPL", "pk")
    seen = []
    real_call = K._call

    def spy(kernel, name, *a, n_live, **kw):
        seen.append(name)
        return real_call(kernel, name, *a, n_live=n_live, **kw)

    monkeypatch.setattr(K, "_call", spy)
    res = ana.revalidate(path, params, views, backend="device",
                         validate_all="stream", max_batch=16)
    assert res.error is None and res.n_valid == n and res.era == 2
    assert "finish_pbft" in seen and "finish_tp" in seen
    assert "finish" in seen
    # a window of Byron's runs no KES or VRF kernel
    first = seen[:seen.index("finish_pbft") + 1]
    assert first == ["ed_points", "finish_pbft"]


@pytest.mark.parametrize("case", ["hit", "stale", "torn", "kill-switch"])
def test_era_sidecar_is_the_parse(chain, tmp_path, monkeypatch, case):
    """The forge seals each era-tagged chunk's columns (the sidecar's
    era format, storage/sidecar.load_era_sidecar): a fresh seal gives the
    parse's pieces array for array; a stale chunk, a torn sidecar and
    OCT_SIDECAR=0 fall back to the parse; the replay agrees with the
    reference every way."""
    import numpy as np

    from ouroboros_consensus_tpu.storage import sidecar
    from ouroboros_consensus_tpu.storage.immutable import (ImmutableDB,
                                                           _chunk_name,
                                                           _cols_name)

    path, n = chain
    work = str(tmp_path / "db")
    shutil.copytree(path, work)
    imm_dir = os.path.join(work, "immutable")
    imm = ImmutableDB(imm_dir, repair=False)
    chunk = imm._chunks[0]
    data = imm.fs.read_bytes(os.path.join(imm_dir, _chunk_name(chunk)))
    entries = imm._entries[chunk]
    sc, outcome = sidecar.load_era_sidecar(imm.fs, imm_dir, chunk, data,
                                           len(entries))
    assert outcome == "hit" and sc.walked
    pieces, spans = cardano.parse_chunk(data, entries.offset)
    assert [p.era for p in sc.pieces] == [p.era for p in pieces]
    for a, b in zip(sc.pieces, pieces):
        assert type(a.cols) is type(b.cols)
        for f in sidecar._piece_arrays(b.cols):
            assert np.array_equal(getattr(a.cols, f), getattr(b.cols, f))
    for x, y in zip(sc.spans, spans):
        assert np.array_equal(x, y)
    want = {"hit": "hit", "stale": "stale", "torn": "torn",
            "kill-switch": None}[case]
    if case == "stale":  # sealed for other chunk bytes than these
        sc_path = os.path.join(imm_dir, _cols_name(chunk))
        blob = bytearray(open(sc_path, "rb").read())
        blob[20] ^= 1  # the sealed chunk length
        open(sc_path, "wb").write(bytes(blob))
    elif case == "torn":
        sc_path = os.path.join(imm_dir, _cols_name(chunk))
        blob = open(sc_path, "rb").read()
        open(sc_path, "wb").write(blob[:len(blob) // 2])
    elif case == "kill-switch":
        monkeypatch.setenv("OCT_SIDECAR", "0")
    sidecar.reset_counters()
    params, _creds, views = _deployment()
    res = ana.revalidate(work, params, views, backend="native",
                         validate_all="stream")
    walked = ref.replay(_rparams(views), _pool_distr(views),
                        ref.read_chain(work))
    _agree(res, walked)
    counts = sidecar.counters()
    if want is None:
        assert sum(counts.values()) == 0
    else:
        assert counts[want] >= 1
