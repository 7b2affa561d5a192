"""Storage engine tests: ImmutableDB, VolatileDB, LedgerDB, ChainDB.

Mirrors the reference's model-based storage tests (SURVEY.md §4 tier 2) in
spirit: every property is phrased against expected chain/store contents,
including corruption-and-truncate recovery.
"""

import os
from dataclasses import replace
from fractions import Fraction

import pytest

from ouroboros_consensus_tpu.block import Block, Point, forge_block
from ouroboros_consensus_tpu.block.abstract import block_point
from ouroboros_consensus_tpu.ledger import ExtLedger
from ouroboros_consensus_tpu.ledger import mock as mock_ledger
from ouroboros_consensus_tpu.protocol import praos
from ouroboros_consensus_tpu.protocol.instances import PraosProtocol
from ouroboros_consensus_tpu.storage import (
    ChainDB,
    ImmutableDB,
    LedgerDB,
    VolatileDB,
)
from ouroboros_consensus_tpu.storage.open import open_chaindb
from ouroboros_consensus_tpu.testing import fixtures

PARAMS = praos.PraosParams(
    slots_per_kes_period=100,
    max_kes_evolutions=62,
    security_param=3,  # tiny k: exercises copy-to-immutable quickly
    active_slot_coeff=Fraction(1),
    epoch_length=10_000,
    kes_depth=3,
)
POOLS = [fixtures.make_pool(i, kes_depth=PARAMS.kes_depth) for i in range(2)]
LVIEW = fixtures.make_ledger_view(POOLS)
ETA0 = b"\x22" * 32


def mk_ext(use_device_batch=False):
    ledger = mock_ledger.MockLedger(
        mock_ledger.MockConfig(LVIEW, PARAMS.stability_window)
    )
    protocol = PraosProtocol(PARAMS, use_device_batch=use_device_batch)
    return ExtLedger(ledger, protocol)


def genesis_state(ext):
    st = ext.genesis(ext.ledger.genesis_state([]))
    return replace(
        st,
        header_state=replace(
            st.header_state,
            chain_dep_state=replace(st.header_state.chain_dep_state, epoch_nonce=ETA0),
        ),
    )


def forge_chain(n, start_slot=1, start_bno=0, prev=None, pool_ix=0, slot_step=1):
    blocks = []
    for i in range(n):
        b = forge_block(
            PARAMS, POOLS[(pool_ix + i) % len(POOLS)],
            slot=start_slot + i * slot_step, block_no=start_bno + i,
            prev_hash=prev, epoch_nonce=ETA0,
        )
        blocks.append(b)
        prev = b.hash_
    return blocks


# -- ImmutableDB -------------------------------------------------------------


def test_immutable_roundtrip(tmp_path):
    db = ImmutableDB(str(tmp_path / "imm"), chunk_size=4)
    blocks = forge_chain(10)
    for b in blocks:
        db.append_block(b.slot, b.block_no, b.hash_, b.bytes_)
    assert db.n_blocks() == 10
    assert db.tip().slot == blocks[-1].slot

    # reopen: indices reload, tail chunk revalidated
    db2 = ImmutableDB(str(tmp_path / "imm"), chunk_size=4)
    assert db2.n_blocks() == 10
    streamed = [Block.from_bytes(raw) for _, raw in db2.stream_all()]
    assert streamed == blocks
    assert db2.get_block_bytes(blocks[3].point) == blocks[3].bytes_


def test_immutable_corrupt_tail_truncates(tmp_path):
    db = ImmutableDB(str(tmp_path / "imm"), chunk_size=100)
    blocks = forge_chain(6)
    for b in blocks:
        db.append_block(b.slot, b.block_no, b.hash_, b.bytes_)
    # corrupt the last block's bytes in the chunk file
    chunk = tmp_path / "imm" / "00000.chunk"
    data = bytearray(chunk.read_bytes())
    data[-3] ^= 0xFF
    chunk.write_bytes(bytes(data))

    db2 = ImmutableDB(str(tmp_path / "imm"), chunk_size=100)
    assert db2.n_blocks() == 5  # corrupted tail dropped
    assert db2.tip().slot == blocks[4].slot


def test_immutable_orphan_index_swept_on_open():
    """Crash recipe from the ImmutableModel: the chunk file's creation was
    never synced (vanishes on crash) but a reparse had atomically written
    the index (durable). Reopening over the orphan index must remove it —
    otherwise a later append extends the stale index and the same block
    appears twice."""
    from ouroboros_consensus_tpu.utils.fs import MockFS

    fs = MockFS()
    b = forge_chain(1)[0]
    db = ImmutableDB("imm", chunk_size=4, fs=fs)
    db.append_block(b.slot, b.block_no, b.hash_, b.bytes_)
    # index damage + reopen: reparse rebuilds the index (atomic => durable)
    fs.truncate_file("imm/00000.index", 0)
    db = ImmutableDB("imm", chunk_size=4, validate_all=True, fs=fs)
    assert db.n_blocks() == 1
    # crash: unsynced chunk file vanishes, durable index survives alone
    fs.crash(0.0)
    assert not fs.exists("imm/00000.chunk")
    db = ImmutableDB("imm", chunk_size=4, validate_all=True, fs=fs)
    assert db.is_empty
    assert not fs.exists("imm/00000.index")  # orphan swept
    # re-appending the block after recovery must not duplicate it
    db.append_block(b.slot, b.block_no, b.hash_, b.bytes_)
    db = ImmutableDB("imm", chunk_size=4, validate_all=True, fs=fs)
    assert [(e.slot, raw) for e, raw in db.stream_all()] == [(b.slot, b.bytes_)]


def test_immutable_truncate_after(tmp_path):
    db = ImmutableDB(str(tmp_path / "imm"), chunk_size=4)
    blocks = forge_chain(10)
    for b in blocks:
        db.append_block(b.slot, b.block_no, b.hash_, b.bytes_)
    db.truncate_after(blocks[6].point)
    assert db.n_blocks() == 7
    db2 = ImmutableDB(str(tmp_path / "imm"), chunk_size=4)
    assert db2.n_blocks() == 7


# -- VolatileDB --------------------------------------------------------------


def test_volatile_roundtrip_and_gc(tmp_path):
    db = VolatileDB(str(tmp_path / "vol"), max_blocks_per_file=3)
    blocks = forge_chain(8)
    for b in blocks:
        db.put_block(b)
        db.put_block(b)  # idempotent
    assert db.get_block_bytes(blocks[2].hash_) == blocks[2].bytes_
    assert db.filter_by_predecessor(None) == {blocks[0].hash_}
    assert db.filter_by_predecessor(blocks[0].hash_) == {blocks[1].hash_}

    # reopen rebuilds the in-memory maps
    db2 = VolatileDB(str(tmp_path / "vol"), max_blocks_per_file=3)
    assert set(db2.all_hashes()) == {b.hash_ for b in blocks}

    # GC removes whole files of old blocks (3 per file)
    db2.garbage_collect(blocks[5].slot + 1)
    remaining = set(db2.all_hashes())
    assert {b.hash_ for b in blocks[6:]} <= remaining
    assert blocks[0].hash_ not in remaining


def test_volatile_torn_write_truncates(tmp_path):
    db = VolatileDB(str(tmp_path / "vol"), max_blocks_per_file=100)
    blocks = forge_chain(3)
    for b in blocks:
        db.put_block(b)
    f = tmp_path / "vol" / "blocks-0000.dat"
    data = f.read_bytes()
    f.write_bytes(data[:-5])  # torn tail
    db2 = VolatileDB(str(tmp_path / "vol"), max_blocks_per_file=100)
    assert set(db2.all_hashes()) == {b.hash_ for b in blocks[:2]}


# -- LedgerDB ----------------------------------------------------------------


def test_ledgerdb_push_rollback_snapshots(tmp_path):
    ext = mk_ext()
    gen = genesis_state(ext)
    db = LedgerDB(ext, k=PARAMS.security_param, anchor=gen)
    blocks = forge_chain(5)
    for b in blocks:
        db.push(b)
    assert db.volatile_length() == 3  # pruned to k
    assert db.tip_point() == blocks[-1].point

    assert db.rollback(2)
    assert db.tip_point() == blocks[2].point
    assert not db.rollback(5)  # beyond k

    # switch to a fork from block 2
    fork = forge_chain(3, start_slot=20, start_bno=3, prev=blocks[2].hash_, pool_ix=1)
    assert db.switch(0, fork)
    assert db.tip_point() == fork[-1].point

    # snapshots
    snap = tmp_path / "snaps"
    name = db.take_snapshot(str(snap))
    assert name is not None
    assert LedgerDB.list_snapshots(str(snap))


def test_ledgerdb_init_replay(tmp_path):
    ext = mk_ext()
    gen = genesis_state(ext)
    imm = ImmutableDB(str(tmp_path / "imm"), chunk_size=100)
    blocks = forge_chain(6)
    for b in blocks:
        imm.append_block(b.slot, b.block_no, b.hash_, b.bytes_)
    db = LedgerDB.init_from_snapshots(
        ext, PARAMS.security_param, str(tmp_path / "snaps"), gen, imm
    )
    assert ext.tip_slot(db.current()) == blocks[-1].slot
    # header states replayed without crypto: tip matches
    assert db.current().header_state.tip.block_no == 5


# -- ChainDB + ChainSel ------------------------------------------------------


def open_db(tmp_path, name="db"):
    ext = mk_ext()
    gen = genesis_state(ext)
    return open_chaindb(
        str(tmp_path / name), ext, gen, k=PARAMS.security_param, chunk_size=100
    ), ext


def test_chaindb_linear_growth(tmp_path):
    db, _ = open_db(tmp_path)
    blocks = forge_chain(7)
    for b in blocks:
        r = db.add_block(b)
        assert r.selected
    assert db.tip_point() == blocks[-1].point
    # k=3: 4 blocks copied to immutable
    assert db.immutable.n_blocks() == 4
    assert len(db.current_chain) == 3
    # full chain streams in order
    assert [b.hash_ for b in db.stream_all()] == [b.hash_ for b in blocks]


def test_chaindb_prefers_longer_fork(tmp_path):
    db, _ = open_db(tmp_path)
    main = forge_chain(4)
    for b in main:
        db.add_block(b)
    # fork from block 1 with more blocks (longer chain wins)
    fork = forge_chain(
        5, start_slot=main[1].slot + 1, start_bno=2, prev=main[1].hash_, pool_ix=1,
        slot_step=2,
    )
    for b in fork:
        db.add_block(b)
    assert db.tip_point() == fork[-1].point


def test_chaindb_out_of_order_arrival(tmp_path):
    db, _ = open_db(tmp_path)
    blocks = forge_chain(5)
    # arrive newest-first: nothing selectable until the chain connects
    for b in reversed(blocks[1:]):
        r = db.add_block(b)
        assert not r.selected
    r = db.add_block(blocks[0])
    assert r.selected
    assert db.tip_point() == blocks[-1].point


def test_chaindb_invalid_block_marked(tmp_path):
    db, _ = open_db(tmp_path)
    blocks = forge_chain(4)
    bad_body = Block(blocks[2].header, (b"not-a-valid-tx-cbor",))
    for b in [blocks[0], blocks[1], bad_body]:
        db.add_block(b)
    # invalid block rejected, prefix adopted
    assert db.tip_point() == blocks[1].point
    assert db.get_is_invalid_block(bad_body.hash_) is not None
    # adding the valid block with the same header hash is now impossible
    # (same hash marked invalid) — extension continues on valid prefix
    more = forge_chain(2, start_slot=10, start_bno=2, prev=blocks[1].hash_, pool_ix=1)
    for b in more:
        db.add_block(b)
    assert db.tip_point() == more[-1].point


def test_chaindb_restart_recovers(tmp_path):
    db, _ = open_db(tmp_path)
    blocks = forge_chain(7)
    for b in blocks:
        db.add_block(b)
    tip = db.tip_point()
    # reopen from disk (snapshot + immutable + volatile reparse)
    db2, _ = open_db(tmp_path)
    assert db2.tip_point() == tip
    assert [b.hash_ for b in db2.stream_all()] == [b.hash_ for b in blocks]


def test_chaindb_follower_updates(tmp_path):
    db, _ = open_db(tmp_path)
    f = db.new_follower()
    blocks = forge_chain(3)
    for b in blocks:
        db.add_block(b)
    ups = f.take_updates()
    added = [u[1].hash_ for u in ups if u[0] == "addblock"]
    assert added == [b.hash_ for b in blocks]


class _CountingVerifier:
    """CryptoVerifier wrapper counting verify calls (for Apply-vs-Reapply
    assertions, Impl/LgrDB.hs:330)."""

    def __init__(self, inner):
        self.inner = inner
        self.calls = 0

    def verify_dsign(self, *a):
        self.calls += 1
        return self.inner.verify_dsign(*a)

    def verify_kes(self, *a):
        self.calls += 1
        return self.inner.verify_kes(*a)

    def verify_vrf(self, *a):
        self.calls += 1
        return self.inner.verify_vrf(*a)


def test_chaindb_fork_switch_reapplies_prev_validated(tmp_path):
    """A fork switch crossing blocks validated earlier must NOT re-run
    their header crypto: LgrDB's prev-applied set chooses Reapply
    (LgrDB.hs:86,330)."""
    counting = _CountingVerifier(praos.HOST_VERIFIER)
    ledger = mock_ledger.MockLedger(
        mock_ledger.MockConfig(LVIEW, PARAMS.stability_window)
    )
    protocol = PraosProtocol(PARAMS, use_device_batch=False, crypto=counting)
    ext = ExtLedger(ledger, protocol)
    gen = genesis_state(ext)
    db = open_chaindb(str(tmp_path / "db"), ext, gen, k=PARAMS.security_param,
                      chunk_size=100)

    # chain A: 2 blocks (pool 0 at even slots)
    chain_a = forge_chain(2, start_slot=2, slot_step=2)
    for b in chain_a:
        assert db.add_block(b).selected
    # chain B: 3 blocks from genesis (odd slots) — longer, switch to it
    chain_b = forge_chain(3, start_slot=1, pool_ix=1, slot_step=2)
    for b in chain_b:
        db.add_block(b)
    assert db.tip_point().hash_ == chain_b[-1].hash_

    # extend A to 4 blocks: switch back crosses A's 2 OLD blocks
    chain_a_ext = forge_chain(
        2, start_slot=chain_a[-1].slot + 2, start_bno=2,
        prev=chain_a[-1].hash_, slot_step=2,
    )
    calls_before = counting.calls
    for b in chain_a_ext:
        db.add_block(b)
    assert db.tip_point().hash_ == chain_a_ext[-1].hash_
    # only the 2 NEW blocks paid crypto (3 verifies each: dsign+kes+vrf);
    # the 2 previously-validated A blocks were reapplied for free
    assert counting.calls - calls_before == 2 * 3, (
        f"expected 6 verifies for the 2 fresh blocks, "
        f"saw {counting.calls - calls_before}"
    )


def test_chaindb_ranged_stream_gc_safe(tmp_path):
    """ChainDB.stream (API.hs:274, Impl/Iterator.hs): ranged streaming
    across the Immutable/Volatile boundary, robust to blocks MOVING
    between the stores mid-iteration (background copy + GC)."""
    from ouroboros_consensus_tpu.storage.chaindb import MissingBlockError

    db, _ = open_db(tmp_path)
    blocks = forge_chain(8)  # k=3: 5 blocks copied to immutable
    for b in blocks:
        db.add_block(b)
    # full stream == stream_all
    assert [b.hash_ for b in db.stream()] == [b.hash_ for b in blocks]
    # ranged: after blocks[1] up to blocks[5]
    got = list(db.stream(blocks[1].point, blocks[5].point))
    assert [b.hash_ for b in got] == [b.hash_ for b in blocks[2:6]]
    # plan pinned, bodies resolved lazily: blocks copied+GC'd between
    # creation and consumption are found in the ImmutableDB
    it = db.stream(blocks[1].point, blocks[5].point)
    for b in forge_chain(3, start_slot=9, start_bno=8, prev=blocks[-1].hash_):
        db.add_block(b)  # advances immutable tip; GCs volatile files
    assert [b.hash_ for b in it] == [b.hash_ for b in blocks[2:6]]
    # unknown bounds are reported (UnknownRange)
    import pytest as _pytest

    with _pytest.raises(MissingBlockError):
        db.stream(Point(999, b"x" * 32), None)


def test_init_chain_selection_not_shadowed_by_invalid_candidate(tmp_path):
    """Regression (found by TestChainDBModel): when the best-RANKED
    candidate contains an invalid block, selection must fall through to
    the next-best fully-valid candidate instead of settling on the
    truncated prefix — both at reopen (initialChainSelection) and in
    chainSelectionForBlock's loop."""
    from ouroboros_consensus_tpu.block.praos_block import Block as PB
    from ouroboros_consensus_tpu.block.praos_block import Header as PH

    db, ext = open_db(tmp_path)
    main = forge_chain(2)
    db.add_block(main[0])
    db.add_block(main[1])
    # a corrupted-signature SIBLING of main[1] whose tip deterministically
    # OUTRANKS it (same length -> VRF tie-break; grind slots until the
    # tie-break favors the bad block), so selection tries it first and
    # truncates to [main0]
    proto = ext.protocol
    bad = None
    for slot in range(3, 40, 2):
        cand = forge_chain(1, start_slot=slot, start_bno=1,
                           prev=main[0].hash_, pool_ix=1)[0]
        if proto.compare_candidates(
            proto.select_view(main[1].header), proto.select_view(cand.header)
        ) > 0:
            bad = PB(
                PH(cand.header.body,
                   bytes([cand.header.kes_sig[0] ^ 0xFF]) + cand.header.kes_sig[1:]),
                cand.txs,
            )
            break
    assert bad is not None, "no outranking slot found"
    db.add_block(bad)
    assert db.tip_point().hash_ == main[1].hash_, "valid chain shadowed"

    # reopen (in-memory invalid set wiped): initial selection must again
    # end on the fully-valid chain, not the bad candidate's prefix
    db.close()
    db2, _ = open_db(tmp_path)
    assert db2.tip_point().hash_ == main[1].hash_


def test_async_mode_equals_sync_mode(tmp_path):
    """The decoupled add-block queue + background copy/GC must produce
    EXACTLY the chain the synchronous path produces for the same add
    sequence (ChainSel.hs:217-246 decoupling is an execution detail,
    not a semantics change)."""
    from ouroboros_consensus_tpu.utils.sim import Sim

    blocks = forge_chain(8)
    fork = forge_chain(3, start_slot=2, start_bno=3,
                       prev=blocks[2].hash_, pool_ix=1, slot_step=7)
    sequence = blocks[:4] + fork + blocks[4:]

    db_sync, _ = open_db(tmp_path, "sync")
    for b in sequence:
        db_sync.add_block(b)

    db_async, _ = open_db(tmp_path, "async")
    sim = Sim()
    runners = db_async.start_decoupled(sim)
    for i, r in enumerate(runners):
        sim.spawn(r, f"runner{i}")

    def feeder():
        from ouroboros_consensus_tpu.utils.sim import Sleep, Wait

        for b in sequence:
            p = db_async.add_block_async(b)
            if p.result is None:
                yield Wait(p.processed)
            yield Sleep(0.01)

    sim.spawn(feeder(), "feeder")
    sim.run(until=60.0)

    assert [b.hash_ for b in db_sync.stream_all()] == [
        b.hash_ for b in db_async.stream_all()
    ]
    assert db_sync.tip_point() == db_async.tip_point()


# -- DiskPolicy (Storage/LedgerDB/DiskPolicy.hs:87-108) ----------------------


def test_disk_policy_fresh_run_snapshots_at_k():
    from ouroboros_consensus_tpu.storage.chaindb import DiskPolicy

    p = DiskPolicy(k=2160)
    assert p.interval_s == 4320.0  # k*2 seconds = 72 min at k=2160
    # NoSnapshotTakenYet: only the k-block rule applies, time irrelevant
    assert not p.should_take_snapshot(2159, now_s=1e9)
    assert p.should_take_snapshot(2160, now_s=0.0)


def test_disk_policy_time_interval_and_burst():
    from ouroboros_consensus_tpu.storage.chaindb import DiskPolicy

    p = DiskPolicy(k=2160)
    p.snapshot_taken(1000.0)
    # below the interval with few blocks: no
    assert not p.should_take_snapshot(10, now_s=1000.0 + 4319.0)
    # interval reached: yes, regardless of block count
    assert p.should_take_snapshot(0, now_s=1000.0 + 4320.0)
    # burst rule: >= 50k blocks AND >= 6 min
    assert not p.should_take_snapshot(50_000, now_s=1000.0 + 359.0)
    assert p.should_take_snapshot(50_000, now_s=1000.0 + 360.0)
    assert not p.should_take_snapshot(49_999, now_s=1000.0 + 360.0)
    # explicit requested interval overrides the default
    q = DiskPolicy(k=4, requested_interval_s=100.0)
    q.snapshot_taken(0.0)
    assert q.should_take_snapshot(1, now_s=100.0)
    assert not q.should_take_snapshot(1, now_s=99.0)


def test_chaindb_time_based_snapshots_on_sim_clock(tmp_path):
    """The ChainDB honors the time-based DiskPolicy against the node's
    VIRTUAL clock: advancing sim time past the interval triggers exactly
    the expected snapshots as blocks are copied to the immutable tier."""
    from ouroboros_consensus_tpu.storage.chaindb import DiskPolicy
    from ouroboros_consensus_tpu.storage.ledgerdb import LedgerDB

    class FakeRuntime:
        now = 0.0

        def fire(self, ev):
            pass

    ext = mk_ext()
    gen = genesis_state(ext)
    db = open_chaindb(str(tmp_path / "db"), ext, gen, k=PARAMS.security_param)
    db.runtime = FakeRuntime()
    db.disk_policy = DiskPolicy(k=PARAMS.security_param,
                                requested_interval_s=60.0)
    snap_dir = db.snap_dir
    blocks = forge_chain(20)
    # fresh run: first snapshot once k (=3) blocks were copied
    for b in blocks[:8]:
        db.add_block(b)
    first = LedgerDB.list_snapshots(snap_dir)
    assert first, "fresh-run k-block snapshot missing"
    n0 = len(first)

    # time below interval: copying more blocks must NOT snapshot
    db.runtime.now = 30.0
    for b in blocks[8:14]:
        db.add_block(b)
    assert len(LedgerDB.list_snapshots(snap_dir)) == n0 or \
        LedgerDB.list_snapshots(snap_dir) == first

    # past the interval: next copy takes a snapshot
    db.runtime.now = 100.0
    for b in blocks[14:]:
        db.add_block(b)
    after = LedgerDB.list_snapshots(snap_dir)
    assert after != first


def _index_rows(path):
    from ouroboros_consensus_tpu.utils import cbor

    idata, rows, off = path.read_bytes(), [], 0
    while off < len(idata):
        obj, off = cbor.decode_prefix(idata, off)
        rows.append(list(obj))
    return rows


def _write_index_rows(path, rows, tail=b""):
    from ouroboros_consensus_tpu.utils import cbor

    path.write_bytes(b"".join(cbor.encode(r) for r in rows) + tail)


def _fix_index_crc(dirpath, chunk_name, index_name, entry_ix):
    """Recompute the stored CRC of entry `entry_ix` from the (corrupted)
    chunk bytes, so the CRC walk passes and only deeper checks can
    catch the corruption."""
    import zlib

    rows = _index_rows(dirpath / index_name)
    data = (dirpath / chunk_name).read_bytes()
    e_off, e_size = rows[entry_ix][3], rows[entry_ix][4]
    rows[entry_ix][5] = zlib.crc32(data[e_off : e_off + e_size])
    _write_index_rows(dirpath / index_name, rows)
    return e_off, e_size


def test_integrity_bad_before_crc_bad_truncates_earlier(tmp_path):
    """Deep validation order (round-5 review finding): a written-corrupt
    block (CRC consistent, body hash wrong) EARLIER in the chunk must
    truncate before a bit-rotted (CRC-bad) block later — the fast
    native path must match the per-blob reference loop."""
    from ouroboros_consensus_tpu.storage.open import (
        default_check_integrity, default_check_integrity_batch,
    )

    db = ImmutableDB(str(tmp_path / "imm"), chunk_size=100)
    blocks = forge_chain(8)
    for b in blocks:
        db.append_block(b.slot, b.block_no, b.hash_, b.bytes_)
    chunk = tmp_path / "imm" / "00000.chunk"
    data = bytearray(chunk.read_bytes())
    # block 2: flip a byte of the DECLARED body hash, keep CRC consistent
    e2 = db._entries[0][2]
    span = bytes(data[e2.offset : e2.offset + e2.size])
    bh = blocks[2].header.body.body_hash
    ix = span.index(bh)
    data[e2.offset + ix] ^= 0xFF
    # block 5: plain bit-rot (CRC now mismatches)
    e5 = db._entries[0][5]
    data[e5.offset + e5.size - 2] ^= 0xFF
    chunk.write_bytes(bytes(data))
    _fix_index_crc(tmp_path / "imm", "00000.chunk", "00000.index", 2)

    db2 = ImmutableDB(
        str(tmp_path / "imm"), chunk_size=100,
        check_integrity=default_check_integrity, validate_all=True,
        check_integrity_batch=default_check_integrity_batch,
    )
    assert db2.n_blocks() == 2  # truncated at the WRITTEN-corrupt block


def test_body_hash_bad_before_malformed_truncates_earlier(tmp_path):
    """Companion ordering case: body-hash corruption at block 1, an
    unparseable block at 4 — truncation lands on block 1."""
    from ouroboros_consensus_tpu.storage.open import (
        default_check_integrity, default_check_integrity_batch,
    )

    db = ImmutableDB(str(tmp_path / "imm"), chunk_size=100)
    blocks = forge_chain(6)
    for b in blocks:
        db.append_block(b.slot, b.block_no, b.hash_, b.bytes_)
    chunk = tmp_path / "imm" / "00000.chunk"
    data = bytearray(chunk.read_bytes())
    e1 = db._entries[0][1]
    span = bytes(data[e1.offset : e1.offset + e1.size])
    ix = span.index(blocks[1].header.body.body_hash)
    data[e1.offset + ix] ^= 0xFF
    e4 = db._entries[0][4]
    data[e4.offset] = 0xFF  # no longer a CBOR array head: unparseable
    chunk.write_bytes(bytes(data))
    _fix_index_crc(tmp_path / "imm", "00000.chunk", "00000.index", 1)
    _fix_index_crc(tmp_path / "imm", "00000.chunk", "00000.index", 4)

    db2 = ImmutableDB(
        str(tmp_path / "imm"), chunk_size=100,
        check_integrity=default_check_integrity, validate_all=True,
        check_integrity_batch=default_check_integrity_batch,
    )
    assert db2.n_blocks() == 1


# -- the columnar chunk index (PR 38) ----------------------------------------


def _damage_clean(rows, ipath):
    pass


def _damage_torn_final_entry(rows, ipath):
    from ouroboros_consensus_tpu.utils import cbor

    _write_index_rows(ipath, rows[:-1], cbor.encode(rows[-1])[:-7])


def _damage_tiling_break(rows, ipath):
    rows[4][3] += 1  # entry 4 no longer starts where entry 3 ends
    _write_index_rows(ipath, rows)


def _damage_zero_size(rows, ipath):
    rows[3][4] = 0
    _write_index_rows(ipath, rows)


def _damage_oversized_size(rows, ipath):
    rows[5][4] = (1 << 40) + 1
    _write_index_rows(ipath, rows)


def _damage_field_past_int64(rows, ipath):
    rows[6][0] = (1 << 63) + 5  # a slot no int64 column holds
    _write_index_rows(ipath, rows)


def _damage_lagging_index(rows, ipath):
    _write_index_rows(ipath, rows[:5])  # the chunk holds 8 blocks


def _damage_empty_index(rows, ipath):
    ipath.write_bytes(b"")


_INDEX_DAMAGE = {
    f.__name__[len("_damage_"):]: f
    for f in (_damage_clean, _damage_torn_final_entry, _damage_tiling_break,
              _damage_zero_size, _damage_oversized_size,
              _damage_field_past_int64, _damage_lagging_index,
              _damage_empty_index)
}


@pytest.mark.parametrize("damage", sorted(_INDEX_DAMAGE))
def test_columnar_index_load_equals_cbor_loop(tmp_path, monkeypatch, damage):
    """The columnar load of an index (native parse, vectorised tiling
    check) against the Python CBOR loop, which stays the reference:
    the same entries, entry for entry, and the same repair rows from
    the open that follows, whatever is wrong with the index."""
    from ouroboros_consensus_tpu import native_loader
    from ouroboros_consensus_tpu.storage.immutable import ChunkIndex

    if native_loader.load() is None:
        pytest.skip("native loader unavailable: one load path only")
    root = tmp_path / "imm"
    db = ImmutableDB(str(root), chunk_size=100)
    for b in forge_chain(8):
        db.append_block(b.slot, b.block_no, b.hash_, b.bytes_)
    ipath = root / "00000.index"
    _INDEX_DAMAGE[damage](_index_rows(ipath), ipath)

    def load_and_open():
        idx = db._load_index(str(ipath))
        opened = ImmutableDB(str(root), chunk_size=100, repair=False)
        return idx, opened

    cols, db_cols = load_and_open()
    monkeypatch.setattr(ImmutableDB, "_load_index_native",
                        lambda self, data: None)
    loop, db_loop = load_and_open()
    assert isinstance(cols, ChunkIndex) and isinstance(loop, ChunkIndex)
    assert list(cols) == list(loop)
    assert cols == loop and cols == list(loop)
    want = {"clean": 8, "torn_final_entry": 7, "tiling_break": 4,
            "zero_size": 3, "oversized_size": 5, "field_past_int64": 6,
            "lagging_index": 5,
            "empty_index": 0}[damage]
    assert len(cols) == want
    assert db_cols.repairs == db_loop.repairs
    assert (damage == "clean") == (db_cols.repairs == [])
    assert db_cols._entries == db_loop._entries
    assert db_cols.n_blocks() == db_loop.n_blocks() == 8  # the chunk is whole


def _model_store(tmp_path, n=11, chunk_size=4):
    """A store written, closed and reopened, beside its model: one list
    of `IndexEntry` a chunk, reckoned from the blocks alone."""
    path = str(tmp_path / "imm")
    db = ImmutableDB(path, chunk_size=chunk_size)
    blocks = forge_chain(n)
    model: dict[int, list] = {}
    for b in blocks:
        db.append_block(b.slot, b.block_no, b.hash_, b.bytes_)
        _model_append(model, b, chunk_size)
    db.flush()
    return ImmutableDB(path, chunk_size=chunk_size), blocks, model, path


def _model_append(model, b, chunk_size=4):
    import zlib

    from ouroboros_consensus_tpu.storage.immutable import IndexEntry

    rows = model.setdefault(b.slot // chunk_size, [])
    off = rows[-1].offset + rows[-1].size if rows else 0
    rows.append(IndexEntry(b.slot, b.block_no, b.hash_, off,
                           len(b.bytes_), zlib.crc32(b.bytes_)))
    return rows[-1]


def _flat(model):
    return [e for n in sorted(model) for e in model[n]]


def _check_slices(db, blocks, model, path):
    for n, rows in model.items():
        idx = db._entries[n]
        assert idx == rows and len(idx) == len(rows)
        assert [idx[i] for i in range(len(rows))] == rows
        assert idx[-1] == rows[-1]
        for cut in (slice(0, 2), slice(1, None), slice(None, -1),
                    slice(2, 2), slice(None, 99)):
            assert idx[cut] == rows[cut], cut
            assert list(idx[cut]) == rows[cut]
        assert idx[:2].end == rows[1].offset + rows[1].size
        assert idx[:0].end == 0 and idx[:0] == []
        assert idx.ends.tolist() == [e.offset + e.size for e in rows]
        with pytest.raises(IndexError):
            idx[len(rows)]


def _check_tip(db, blocks, model, path):
    assert db.tip() == _flat(model)[-1]
    assert db.tip_point() == blocks[-1].point
    assert db.n_blocks() == len(blocks) and not db.is_empty
    assert list(db.iter_entries()) == _flat(model)
    assert list(db.iter_points()) == [b.point for b in blocks]
    empty = ImmutableDB(path + "-none", chunk_size=4, repair=False)
    assert empty.tip() is None and empty.is_empty and empty.n_blocks() == 0


def _check_get_block_bytes(db, blocks, model, path):
    from ouroboros_consensus_tpu.storage.immutable import MissingBlock

    for b in blocks:
        assert db.get_block_bytes(b.point) == b.bytes_
    wrong_hash = Point(blocks[5].slot, blocks[4].hash_)
    absent = [Point(0, blocks[0].hash_),  # before the first block
              Point(blocks[-1].slot + 1, blocks[-1].hash_),  # past the tip
              Point(10_000, blocks[0].hash_)]  # a chunk that is not there
    for p in [wrong_hash] + absent:
        with pytest.raises(MissingBlock):
            db.get_block_bytes(p)


def _check_stream_from(db, blocks, model, path):
    by_hash = {b.hash_: b.bytes_ for b in blocks}
    for after in (-1, 0, 3, 4, 7, blocks[-1].slot - 1, blocks[-1].slot, 99):
        want = [(e, by_hash[e.hash_]) for e in _flat(model)
                if e.slot > after]
        assert list(db.stream_from(after)) == want, after
    assert list(db.stream_all()) == list(db.stream_from(-1))


def _check_truncate_after(db, blocks, model, path):
    kept_view = db._entries[1][:2]
    db.truncate_after(blocks[5].point)  # slot 6: inside chunk 1
    want = [e for e in _flat(model) if e.slot <= blocks[5].slot]
    assert list(db.iter_entries()) == want and db.tip() == want[-1]
    assert kept_view == model[1][:2]
    assert list(ImmutableDB(path, chunk_size=4).iter_entries()) == want
    db.truncate_after(None)
    assert db.is_empty and db.tip() is None
    assert ImmutableDB(path, chunk_size=4).is_empty


def _check_append_after_reopen(db, blocks, model, path):
    n_last = max(model)
    before = db._entries[n_last]
    view, old = before[:], list(model[n_last])
    more = forge_chain(70, start_slot=blocks[-1].slot + 1,
                       start_bno=len(blocks), prev=blocks[-1].hash_)
    for b in more:
        db.append_block(b.slot, b.block_no, b.hash_, b.bytes_)
        assert db.tip() == _model_append(model, b)
    # a view cut before the appends still says what it said
    assert view == old and db._entries[n_last][: len(old)] == old
    assert {n: list(v) for n, v in db._entries.items()} == model
    with pytest.raises(Exception, match="out of order"):
        db.append_block(more[-1].slot, 0, more[-1].hash_, b"x")
    db.flush()
    again = ImmutableDB(path, chunk_size=4)
    assert list(again.iter_entries()) == _flat(model)
    assert again.get_block_bytes(more[33].point) == more[33].bytes_


@pytest.mark.parametrize("check", [
    _check_slices, _check_tip, _check_get_block_bytes, _check_stream_from,
    _check_truncate_after, _check_append_after_reopen,
], ids=lambda f: f.__name__[len("_check_"):])
def test_chunk_index_agrees_with_entry_list_model(tmp_path, check):
    """The columns behind `_entries` answer every per-entry question as
    a list of `IndexEntry` would: the model is such a list."""
    check(*_model_store(tmp_path))


def test_append_block_builds_no_entry_list(tmp_path, index_entries_built):
    """The writer stays O(1) a block: 2,000 appends over several chunks
    build at most the one `IndexEntry` a `tip()` hands back each, and
    grow the open chunk's columns by doubling."""
    from ouroboros_consensus_tpu.storage import immutable as imm_mod

    built = index_entries_built
    path = str(tmp_path / "imm")
    db = ImmutableDB(path, chunk_size=1500)
    raw = os.urandom(64)
    per_quarter = []
    for q in range(4):
        built.clear()
        for i in range(500 * q, 500 * q + 500):
            db.append_block(3 * i, i, i.to_bytes(32, "big"), raw)
        per_quarter.append(len(built))
    assert per_quarter == [499, 500, 500, 500]  # its own tip() alone
    assert db.n_blocks() == 2000 and db._chunks == [0, 1, 2, 3]
    assert all(isinstance(v, imm_mod.ChunkIndex)
               for v in db._entries.values())
    assert len(db._entries[0]._cols[0]) == 512  # 64, doubled three times
    built.clear()
    again = ImmutableDB(path, chunk_size=1500)
    assert built == [] and again._entries == db._entries
    assert again.tip().block_no == 1999
