"""The columnar-sidecar plane (PR 17), differentially: a sidecar-fed
replay must be verdict-, error-taxonomy- and nonce-carry-IDENTICAL to
the parse path on clean, corrupted, and mixed draft-03/batch-compatible
chains — the sidecar is a cache of the parse, never an authority.

The suite covers the probe's outcome vocabulary (hit/miss/stale/torn),
the writer-only backfill contract (a read-only open never writes), the
hot-path honesty invariant (the sidecar's body-hash columns equal the
exact host digests — a wrong column would silently arbitrate every
block onto the slow path without failing a verdict), resume across a
sidecared/un-sidecared chunk boundary, and the device-hash lever."""

from __future__ import annotations

import os
import shutil
from fractions import Fraction

import numpy as np
import pytest

from ouroboros_consensus_tpu import native_loader, obs
from ouroboros_consensus_tpu.obs import recovery
from ouroboros_consensus_tpu.obs.warmup import WARMUP
from ouroboros_consensus_tpu.ops import blake2b as b2
from ouroboros_consensus_tpu.protocol import praos
from ouroboros_consensus_tpu.protocol.views import ViewColumns
from ouroboros_consensus_tpu.storage import sidecar as sc_mod
from ouroboros_consensus_tpu.storage.immutable import _chunk_name
from ouroboros_consensus_tpu.testing import chaos, fixtures
from ouroboros_consensus_tpu.tools import db_analyser as ana
from ouroboros_consensus_tpu.tools import db_synthesizer as synth

PARAMS = praos.PraosParams(
    slots_per_kes_period=100,
    max_kes_evolutions=62,
    security_param=4,
    active_slot_coeff=Fraction(1, 2),
    epoch_length=60,
    kes_depth=3,
)
POOL = fixtures.make_pool(11, kes_depth=3)
LVIEW = fixtures.make_ledger_view([POOL])
N_BLOCKS = 40


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    WARMUP.reset()
    obs.reset_for_tests()
    recovery.reset_for_tests()
    for var in ("OCT_CHAOS", "OCT_CHAOS_SEED", "OCT_CHECKPOINT",
                "OCT_RESUME", "OCT_SIDECAR", "OCT_SIDECAR_DEVICE_HASH",
                "OCT_COLUMNAR", "OCT_VRF_BATCH", "OCT_TRACE"):
        monkeypatch.delenv(var, raising=False)
    chaos.reset()
    sc_mod.reset_counters()
    yield
    WARMUP.reset()
    obs.reset_for_tests()
    recovery.reset_for_tests()
    chaos.reset()
    sc_mod.reset_counters()


def _need_native():
    if native_loader.load() is None:
        pytest.skip("native loader unavailable: the sidecar plane is "
                    "parse-path-only on this box")


def _forge(path, blocks=N_BLOCKS, resume=False):
    synth.synthesize(path, PARAMS, [POOL], LVIEW,
                     synth.ForgeLimit(blocks=blocks),
                     chunk_size=PARAMS.epoch_length, resume=resume)


@pytest.fixture(scope="module")
def pristine(tmp_path_factory):
    if native_loader.load() is None:
        pytest.skip("native loader unavailable")
    path = str(tmp_path_factory.mktemp("sidecar") / "pristine")
    shutil.rmtree(path, ignore_errors=True)
    _forge(path)
    return path


def _reval(path, **kw):
    kw.setdefault("backend", "host")
    kw.setdefault("validate_all", "stream")
    return ana.revalidate(path, PARAMS, LVIEW, **kw)


def _copy(pristine, tmp_path):
    db = str(tmp_path / "db")
    shutil.copytree(pristine, db)
    return db


def _chunk_and_sidecar(db, chunk=0):
    """(chunk bytes, entries, loaded SidecarColumns, outcome) through
    the same fs seam the replay uses."""
    imm = ana.open_immutable(db)
    n = imm._chunks[chunk]
    entries = imm._entries[n]
    data = imm.fs.read_bytes(os.path.join(imm.path, _chunk_name(n)))
    sc, outcome = sc_mod.load_sidecar(imm.fs, imm.path, n, data,
                                      len(entries))
    return data, entries, sc, outcome


def _prefix_states(db):
    """Pristine-prefix oracle: final PraosState at every prefix length
    (same construction as tests/test_repair.pristine_states)."""
    states = {0: praos.PraosState()}
    st = praos.PraosState()
    res = ana.ValidationResult()
    i = 0
    imm = ana.open_immutable(db)
    for hv in ana._stream_views(imm, res):
        ticked = praos.tick(PARAMS, LVIEW, hv.slot, st)
        st = praos.update(PARAMS, hv, hv.slot, ticked)
        i += 1
        states[i] = st
    return states


# ---------------------------------------------------------------------------
# format + probe units
# ---------------------------------------------------------------------------


def test_forge_writes_sealed_sidecars(pristine):
    """db_synthesizer back-fills every chunk's sidecar at forge time;
    a fresh probe is a HIT whose lane count matches the index."""
    imm = ana.open_immutable(pristine)
    assert len(imm._chunks) == 2  # 40 blocks over 60-slot chunks
    for chunk in range(len(imm._chunks)):
        assert os.path.exists(
            sc_mod.sidecar_path(imm.path, imm._chunks[chunk])
        )
        _, entries, sc, outcome = _chunk_and_sidecar(pristine, chunk)
        assert outcome == "hit" and sc is not None
        assert sc.n == len(entries)


def test_probe_outcome_classification(pristine, tmp_path):
    """The probe's whole vocabulary, one manipulation per word:
    structural truncation is `torn`, any seal mismatch is `stale`, an
    absent file is `miss` — and NONE of them is ever a crash."""
    db = _copy(pristine, tmp_path)
    imm = ana.open_immutable(db)
    n = imm._chunks[0]
    path = sc_mod.sidecar_path(imm.path, n)
    data = imm.fs.read_bytes(os.path.join(imm.path, _chunk_name(n)))
    n_entries = len(imm._entries[n])
    pristine_cols = open(path, "rb").read()

    def probe(chunk_bytes=data, count=n_entries):
        sc, outcome = sc_mod.load_sidecar(imm.fs, imm.path, n,
                                          chunk_bytes, count)
        return outcome

    assert probe() == "hit"
    # torn: truncated inside the header, then inside the payload
    for cut in (0, 10, sc_mod.HEADER_SIZE + 7):
        with open(path, "wb") as f:
            f.write(pristine_cols[:cut])
        assert probe() == "torn", cut
    # torn: wrong magic (a foreign or half-written file)
    with open(path, "wb") as f:
        f.write(b"XXXXXXXX" + pristine_cols[8:])
    assert probe() == "torn"
    # stale: one flipped payload byte breaks the payload CRC seal
    flip = bytearray(pristine_cols)
    flip[sc_mod.HEADER_SIZE + 3] ^= 0x40
    with open(path, "wb") as f:
        f.write(bytes(flip))
    assert probe() == "stale"
    # restore the real file: remaining words are seal mismatches
    with open(path, "wb") as f:
        f.write(pristine_cols)
    assert probe(count=n_entries + 1) == "stale"  # index drifted
    assert probe(chunk_bytes=data + b"x") == "stale"  # chunk grew
    assert probe(chunk_bytes=data[:-1]) == "stale"  # chunk shrank
    os.unlink(path)
    assert probe() == "miss"


def test_hot_path_honesty_digests_match_exactly(pristine):
    """The sidecar's body-hash columns equal the exact host digests on
    a clean chunk. This is the anti-silent-degradation guard: a wrong
    `header_end`/`body_hash` column would not flip any verdict (the
    per-block arbitration re-checks on host), it would just quietly
    route EVERY block through the slow path — so the fast path must be
    proven exact, not merely verdict-preserving."""
    for chunk in range(2):
        data, entries, sc, outcome = _chunk_and_sidecar(pristine, chunk)
        assert outcome == "hit"
        starts = np.asarray(sc.arrays["header_end"], np.int64)
        ends = np.asarray([e.offset + e.size for e in entries], np.int64)
        digests = b2.hash_spans(data, starts, ends)
        assert np.array_equal(digests, sc.arrays["body_hash"])
        # and the integrity hook agrees: the whole chunk is good
        hook = sc_mod.integrity_batch_hook(sc)
        assert hook(data, entries) == len(entries)


def test_pieces_equivalent_to_parse(pristine):
    """`SidecarColumns.pieces` reconstructs the SAME ViewColumns the
    native parse builds — field for field, byte for byte."""
    for chunk in range(2):
        data, entries, sc, outcome = _chunk_and_sidecar(pristine, chunk)
        assert outcome == "hit"
        offsets = np.asarray([e.offset for e in entries], np.int64)
        hc = native_loader.extract_headers(data, offsets)
        want = ViewColumns.pieces_from_header_columns(hc)
        got = sc.pieces(data)
        assert want is not None and got is not None
        assert len(got) == len(want)
        from dataclasses import fields
        for gp, wp in zip(got, want):
            for f in fields(ViewColumns):
                assert np.array_equal(
                    np.asarray(getattr(gp, f.name)),
                    np.asarray(getattr(wp, f.name)),
                ), (chunk, f.name)


# ---------------------------------------------------------------------------
# the differential headline: sidecar replay == parse replay
# ---------------------------------------------------------------------------


def test_clean_replay_differential_and_killswitch(pristine, monkeypatch):
    """OCT_SIDECAR=0 is the acceptance kill-switch: verdict, block
    counts and final state (nonce carry included) are identical with
    the plane on (every chunk a HIT) and off (counters untouched)."""
    sc_mod.reset_counters()
    on = _reval(pristine)
    assert on.error is None and on.n_valid == N_BLOCKS
    assert sc_mod.counters()["hit"] == 2

    monkeypatch.setenv("OCT_SIDECAR", "0")
    sc_mod.reset_counters()
    off = _reval(pristine)
    assert sc_mod.counters() == {k: 0 for k in sc_mod.SIDECAR_OUTCOMES}
    assert (off.n_blocks, off.n_valid, off.error) == \
        (on.n_blocks, on.n_valid, on.error)
    assert off.final_state == on.final_state


def test_backfill_is_writer_only(pristine, tmp_path):
    """An un-sidecared store: the read-only replay parses (miss) and
    leaves the disk byte-untouched; the first WRITER open pays the
    parse once and back-fills; the next replay hits. All three runs
    verdict-identical."""
    db = _copy(pristine, tmp_path)
    imm_dir = os.path.join(db, "immutable")
    for f in list(os.listdir(imm_dir)):
        if f.endswith(".cols"):
            os.unlink(os.path.join(imm_dir, f))
    listing = sorted(os.listdir(imm_dir))

    sc_mod.reset_counters()
    ro = _reval(db)  # read-only analysis
    assert ro.error is None and ro.n_valid == N_BLOCKS
    c = sc_mod.counters()
    assert c["miss"] == 2 and c["rebuilt"] == 0
    assert sorted(os.listdir(imm_dir)) == listing  # wrote NOTHING

    sc_mod.reset_counters()
    wr = _reval(db, repair=True)  # writer open: backfill allowed
    c = sc_mod.counters()
    assert c["miss"] == 2 and c["rebuilt"] == 2
    assert all(
        os.path.exists(os.path.join(imm_dir, f"{n:05d}.cols"))
        for n in (0, 1)
    )

    sc_mod.reset_counters()
    hot = _reval(db)
    assert sc_mod.counters()["hit"] == 2
    for r in (wr, hot):
        assert r.error is None and r.n_valid == ro.n_valid
        assert r.final_state == ro.final_state


def test_corrupted_chain_differential(pristine, tmp_path, monkeypatch):
    """A sidecar whose seal covers ROTTEN chunk bytes (rot landed
    before the rebuild, so every seal matches) must not launder them:
    the probe hits, the integrity sweep catches the rot, the anomaly
    path re-runs the exact host walk — and the truncation point, the
    replay verdict and the nonce carry equal both the kill-switch
    replay and the pristine prefix."""
    oracle = _prefix_states(pristine)
    db = _copy(pristine, tmp_path)
    imm_dir = os.path.join(db, "immutable")

    # corrupt one BODY byte of block 5 in chunk 0 (first byte past the
    # header: the sidecar's own header_end column says where that is)
    data, entries, sc, outcome = _chunk_and_sidecar(db, 0)
    assert outcome == "hit"
    rot_at = int(sc.arrays["header_end"][5])
    chunk_file = os.path.join(imm_dir, _chunk_name(0))
    blob = bytearray(open(chunk_file, "rb").read())
    blob[rot_at] ^= 0xA5
    with open(chunk_file, "wb") as f:
        f.write(bytes(blob))

    # rebuild chunk 0's sidecar OVER the rotten bytes — seals now match
    os.unlink(os.path.join(imm_dir, "00000.cols"))
    imm = ana.open_immutable(db)
    assert sc_mod.backfill_store(imm) == 1
    _, _, sc2, outcome2 = _chunk_and_sidecar(db, 0)
    assert outcome2 == "hit"  # the trap is armed: a hit over rot

    sc_mod.reset_counters()
    r_on = _reval(db)
    assert sc_mod.counters()["hit"] >= 1
    assert r_on.error is None and r_on.n_valid == 5
    assert r_on.final_state == oracle[5]
    assert r_on.repairs is None  # read-only: verdict-only truncation

    monkeypatch.setenv("OCT_SIDECAR", "0")
    r_off = _reval(db)
    assert (r_off.n_blocks, r_off.n_valid, r_off.error) == \
        (r_on.n_blocks, r_on.n_valid, r_on.error)
    assert r_off.final_state == r_on.final_state


def test_mixed_proof_format_store_differential(tmp_path, monkeypatch):
    """A store forged across an OCT_VRF_BATCH flip (20 batch-compatible
    128-byte proofs, then draft-03 80-byte ones) has ragged signed-body
    widths: the sidecar drops UNIFORM and serves the span-gather
    fallback, splitting pieces at the format boundary exactly like
    `pieces_from_header_columns` — and the replay still equals the
    kill-switch replay."""
    _need_native()
    db = str(tmp_path / "mixed")
    monkeypatch.setenv("OCT_VRF_BATCH", "1")
    _forge(db, blocks=20)
    monkeypatch.setenv("OCT_VRF_BATCH", "0")
    _forge(db, blocks=N_BLOCKS, resume=True)
    monkeypatch.delenv("OCT_VRF_BATCH")

    # the flip landed mid-store: both formats present
    imm = ana.open_immutable(db)
    lens = set()
    for chunk in range(len(imm._chunks)):
        data, entries, sc, outcome = _chunk_and_sidecar(db, chunk)
        assert outcome == "hit"
        lens |= set(np.asarray(sc.arrays["vrf_proof_len"]).tolist())
        pieces = sc.pieces(data)
        assert pieces is not None
        if not sc.uniform:
            assert len(pieces) > 1  # split at the width step
    assert lens == {80, 128}

    sc_mod.reset_counters()
    on = _reval(db)
    assert on.error is None and on.n_valid == N_BLOCKS
    assert sc_mod.counters()["hit"] == len(imm._chunks)
    monkeypatch.setenv("OCT_SIDECAR", "0")
    off = _reval(db)
    assert (off.n_blocks, off.n_valid, off.error) == \
        (on.n_blocks, on.n_valid, on.error)
    assert off.final_state == on.final_state


def test_resume_across_sidecar_boundary(pristine, tmp_path, monkeypatch):
    """A checkpointed replay resuming from the chunk-0 boundary into a
    store where chunk 0 is UN-sidecared and chunk 1 is sidecared (the
    mixed-generation disk a mid-backfill crash leaves behind) is
    verdict-identical to the uninterrupted run."""
    db = _copy(pristine, tmp_path)
    os.unlink(os.path.join(db, "immutable", "00000.cols"))
    full = _reval(db)
    assert full.error is None and full.n_valid == N_BLOCKS

    imm = ana.open_immutable(db)
    n0 = len(imm._entries[imm._chunks[0]])
    oracle = _prefix_states(db)

    ck = str(tmp_path / "ckpt.json")
    w = recovery.ProgressWriter(ck, recovery.chain_tag(db, PARAMS))
    w.note(oracle[n0], n0)
    monkeypatch.setenv("OCT_CHECKPOINT", ck)
    sc_mod.reset_counters()
    res = ana.revalidate(db, PARAMS, LVIEW, backend="native",
                         validate_all=False, resume=True)
    assert res.resumed_headers == n0
    assert res.error is None and res.n_valid == N_BLOCKS
    assert res.final_state == full.final_state
    c = sc_mod.counters()
    assert c["miss"] >= 1 and c["hit"] >= 1  # crossed the boundary


# ---------------------------------------------------------------------------
# the device-hash lever
# ---------------------------------------------------------------------------


def test_device_hash_spans_matches_host(monkeypatch):
    """OCT_SIDECAR_DEVICE_HASH=1 routes the body-hash batch through the
    Blake2b device kernel (bucket-padded shapes); digests must equal
    hashlib's bit-for-bit, pad lanes dropped."""
    rng = np.random.default_rng(17)
    data = rng.integers(0, 256, size=4096, dtype=np.uint8).tobytes()
    starts = np.asarray([0, 100, 500, 900, 1000], np.int64)
    ends = np.asarray([90, 400, 740, 999, 3000], np.int64)
    host = b2.hash_spans(data, starts, ends)
    monkeypatch.setenv("OCT_SIDECAR_DEVICE_HASH", "1")
    dev = b2.hash_spans(data, starts, ends)
    assert np.array_equal(host, dev)
    assert b2.hash_spans(data, starts[:0], ends[:0]).shape == (0, 32)


# ---------------------------------------------------------------------------
# the native probe primitives + the WALKED seal bit
# ---------------------------------------------------------------------------


def test_native_crc32_matches_zlib():
    """The PCLMULQDQ probe CRC must be bit-identical to ``zlib.crc32``
    on every length class (sub-word tails, the 64-byte fold threshold,
    fold-multiple boundaries) and under chained init values: seals on
    disk may have been written by either implementation and must keep
    verifying under the other."""
    import zlib

    if native_loader.load_crypto() is None:
        pytest.skip("native host-crypto unavailable")
    rng = np.random.default_rng(23)
    for ln in (0, 1, 7, 15, 16, 63, 64, 65, 255, 4096, 70001):
        d = rng.integers(0, 256, size=ln, dtype=np.uint8).tobytes()
        assert native_loader.native_crc32(d) == (zlib.crc32(d) & 0xFFFFFFFF)
    a, b = b"seal " * 31, b"check" * 77
    assert native_loader.native_crc32(b, native_loader.native_crc32(a)) \
        == (zlib.crc32(b, zlib.crc32(a)) & 0xFFFFFFFF)


def test_native_hash_spans_matches_hashlib():
    """``ops/blake2b.hash_spans``' native batch (``oc_blake2b_spans``)
    equals the hashlib loop digest-for-digest — it IS the hot path's
    body-hash compare, so a divergence would silently truncate intact
    chains (or worse, pass rotten ones)."""
    import hashlib

    rng = np.random.default_rng(29)
    data = rng.integers(0, 256, size=65536, dtype=np.uint8).tobytes()
    starts = np.asarray([0, 1, 777, 4000, 65535, 128], np.int64)
    ends = np.asarray([0, 513, 4000, 65536, 65536, 131], np.int64)
    got = b2.hash_spans(data, starts, ends)
    exp = np.stack([
        np.frombuffer(
            hashlib.blake2b(data[s:e], digest_size=32).digest(), np.uint8
        )
        for s, e in zip(starts, ends)
    ])
    assert np.array_equal(got, exp)


def test_walked_seal_provenance_and_differential(pristine, tmp_path):
    """FLAG_WALKED provenance: forge-time seals are WALKED (integrity
    by construction — the replay may skip the per-blob CRC sweep), a
    bare ``backfill_store`` reseal is NOT (no walk backs it, the full
    sweep stays). Both replay to the identical verdict and nonce
    carry."""
    db = _copy(pristine, tmp_path)
    imm_dir = os.path.join(db, "immutable")

    _, _, sc, outcome = _chunk_and_sidecar(db, 0)
    assert outcome == "hit" and sc.walked  # forge-time: by construction

    r_walked = _reval(db)
    assert r_walked.error is None and r_walked.n_valid == N_BLOCKS

    # strip the seal and reseal through a bare writer open: same
    # columns, but nothing walked these bytes — the flag must be OFF
    os.unlink(os.path.join(imm_dir, "00000.cols"))
    imm = ana.open_immutable(db)
    assert sc_mod.backfill_store(imm) == 1
    _, _, sc2, outcome2 = _chunk_and_sidecar(db, 0)
    assert outcome2 == "hit" and not sc2.walked

    r_unwalked = _reval(db)
    assert r_unwalked.error is None
    assert r_unwalked.n_valid == r_walked.n_valid
    assert r_unwalked.final_state == r_walked.final_state


@pytest.mark.parametrize("seal", ["walked", "unwalked", "none"])
def test_stream_replay_builds_no_index_entry(pristine, tmp_path,
                                             index_entries_built, seal):
    """The guard on PR 38's mechanism: a chunk's index stays columns
    from the native parse to its last reader, so a stream replay
    constructs ZERO `IndexEntry` objects (counted, not timed), whichever
    way a chunk's integrity is owed: a walked seal (the body-hash
    columns alone), an unwalked one (the CRC sweep too), no sidecar (the
    native parse and `default_check_integrity_batch`)."""
    db = _copy(pristine, tmp_path)
    if seal != "walked":
        for n in range(2):
            os.unlink(os.path.join(db, "immutable", "%05d.cols" % n))
    if seal == "unwalked":
        assert sc_mod.backfill_store(ana.open_immutable(db)) == 2
    want = {"walked": "hit", "unwalked": "hit", "none": "miss"}[seal]
    index_entries_built.clear()  # the backfill above is a writer's
    sc_mod.reset_counters()
    res = _reval(db, backend="native", collect_phases=True)
    assert res.error is None and res.n_valid == N_BLOCKS
    assert sc_mod.counters()[want] == 2
    assert index_entries_built == []
    # the mechanism's own reading: one `open.index` span a chunk,
    # inside `open`
    assert 0 < res.phases["open.index"] < res.phases["open"]
