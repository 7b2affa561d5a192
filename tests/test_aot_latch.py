"""The build-pinned AOT artifact store (ops/pk/aot.py) — round 10.

Round-8 pinned the latch-and-skip contract; round 10 REPLACES it with
the store: entries keyed (build_id, src_digest, stage, tile) under
per-build directories with a provenance manifest. A stored executable
of another runtime build (~15 s per doomed deserialize when loaded
blind) cannot cost that: `load` checks the manifest's build_id BEFORE
touching the artifact, a format rejection condemns only PRE-rejection
entries (marker mtime), and the write-back re-serializes the fallback
compile so the next process loads warm. These tests pin that contract:
real save/load roundtrips on XLA:CPU executables, the zero-deserialize
wrong_build skip, rejection -> write-back -> warm reload, manifest
integrity under concurrent writers, and `aot_precompile --check`'s
store verification."""

import os
import threading
import time

import numpy as np
import pytest

import jax

from ouroboros_consensus_tpu.ops.pk import aot


@pytest.fixture
def fresh_store(tmp_path, monkeypatch):
    """Isolated store state: private dir, un-latched globals."""
    monkeypatch.setenv("OCT_PK_AOT_DIR", str(tmp_path))
    monkeypatch.delenv("OCT_PK_AOT", raising=False)
    monkeypatch.delenv("OCT_PK_AOT_WRITEBACK", raising=False)
    monkeypatch.delenv("OCT_AOT_BUILD_ID", raising=False)
    _fresh_process(monkeypatch)
    return tmp_path


def _fresh_process(monkeypatch):
    """Reset the in-memory state as a new process would start."""
    monkeypatch.setattr(aot, "_RUNTIME_REJECTED", False)
    monkeypatch.setattr(aot, "_MARKER_CHECKED", False)
    monkeypatch.setattr(aot, "_MARKER_TIME", None)
    monkeypatch.setattr(aot, "_LOADED", {})
    monkeypatch.setattr(aot, "_MANIFEST_CACHE", {})


ARGS = (np.ones((4,), np.float32),)


def _compiled(mult=2.0):
    return jax.jit(lambda x: x * mult + 1).trace(*ARGS).lower().compile()


# ---------------------------------------------------------------------------
# save/load roundtrip + provenance
# ---------------------------------------------------------------------------


def test_save_load_roundtrip_and_manifest(fresh_store):
    sig = aot.sig_of(ARGS)
    path = aot.save("ed", 4, 3, 128, sig, _compiled(), {"via": "test"})
    assert path.startswith(str(fresh_store))
    assert aot._build_slug() in path  # per-build subdirectory
    (meta,) = aot.read_manifest().values()
    assert meta["build_id"] == aot.build_id()
    assert meta["src_digest"] == aot._src_digest()
    assert meta["via"] == "test"
    ex = aot.load("ed", 4, 3, 128, sig)
    assert ex is not None
    np.testing.assert_allclose(np.asarray(ex(*ARGS)),
                               np.asarray(ARGS[0]) * 2 + 1)


def test_wrong_build_skips_without_deserialize(fresh_store, monkeypatch,
                                               capsys):
    """An entry pinned to ANOTHER build is a zero-cost skip: the
    manifest check happens BEFORE the artifact file is ever opened —
    the structural fix for the ~15 s doomed deserializes."""
    import builtins

    sig = aot.sig_of(ARGS)
    aot.save("kes", 4, 3, 128, sig, _compiled(), {})
    _fresh_process(monkeypatch)
    # the runtime moved on: same slug dir on disk, new platform_version
    monkeypatch.setattr(aot, "_BUILD_ID", "tpu v99 (future runtime)")
    real_open = builtins.open

    def guarded(path, *a, **k):
        assert not str(path).endswith(".jaxexec"), \
            "wrong_build entry was deserialized"
        return real_open(path, *a, **k)

    monkeypatch.setattr(builtins, "open", guarded)
    assert aot.load("kes", 4, 3, 128, sig) is None
    # memoized: the second probe does not even re-read the manifest row
    assert aot.load("kes", 4, 3, 128, sig) is None


def test_missing_entry_is_cheap(fresh_store, monkeypatch):
    import builtins

    real_open = builtins.open

    def guarded(path, *a, **k):
        assert not str(path).endswith(".jaxexec")
        return real_open(path, *a, **k)

    monkeypatch.setattr(builtins, "open", guarded)
    assert aot.load("vrf", 8, 3, 128, "deadbeef") is None


# ---------------------------------------------------------------------------
# format rejection -> write-back -> next process warm
# ---------------------------------------------------------------------------


def _poison(name: str, sig: str, saved_at: float):
    """A manifest entry that CLAIMS the current build but whose
    artifact the runtime rejects (the mislabeled-entry hazard the
    marker still defends against)."""
    import pickle

    path = aot.stage_path(name, 4, 3, 128, sig)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        f.write(pickle.dumps({"ser": b"junk", "in_tree": None,
                              "out_tree": None, "meta": {}}))
    aot._manifest_update(
        aot.entry_key(name, 4, 3, 128, sig),
        {"build_id": aot.build_id(), "src_digest": aot._src_digest(),
         "saved_at": saved_at},
    )


def test_rejection_writeback_heals_next_process(fresh_store, monkeypatch):
    """The round-10 contract: format rejection -> the fallback compile
    is re-serialized for the current build -> the NEXT process loads
    warm, and the other pre-rejection entries are marker-skipped with
    zero deserializes."""
    from jax.experimental import serialize_executable as se

    sig = aot.sig_of(ARGS)
    _poison("vrf", sig, saved_at=time.time())
    _poison("finish", "aaaaaaaa", saved_at=time.time())
    real_deser = se.deserialize_and_load
    monkeypatch.setattr(
        se, "deserialize_and_load",
        lambda *a, **k: (_ for _ in ()).throw(RuntimeError(
            "serialized executable is incompatible with this build"
        )),
    )
    assert aot.load("vrf", 4, 3, 128, sig) is None  # ONE rejected deserialize
    assert aot._RUNTIME_REJECTED
    assert os.path.exists(aot._reject_marker())
    # the sibling pre-rejection entry is condemned WITHOUT a deserialize
    deser_calls = []
    monkeypatch.setattr(
        se, "deserialize_and_load",
        lambda *a, **k: deser_calls.append(1) or real_deser(*a, **k),
    )
    assert aot.load("finish", 4, 3, 128, "aaaaaaaa") is None
    assert deser_calls == []
    # the write-back: the stage compiles through the fallback anyway —
    # compile_and_store re-serializes it for the current build
    monkeypatch.setenv("OCT_PK_AOT_WRITEBACK", "1")
    time.sleep(0.05)  # saved_at must post-date the marker mtime
    ex = aot.compile_and_store("vrf", 4, 3, 128,
                               jax.jit(lambda x: x * 3.0), ARGS)
    assert ex is not None
    np.testing.assert_allclose(np.asarray(ex(*ARGS)),
                               np.asarray(ARGS[0]) * 3.0)
    # NEXT PROCESS on the same build: the fresh entry loads warm, the
    # stale sibling is still a zero-deserialize marker_skip
    _fresh_process(monkeypatch)
    deser_calls.clear()
    ex2 = aot.load("vrf", 4, 3, 128, sig)
    assert ex2 is not None
    assert len(deser_calls) == 1  # exactly the healed entry
    np.testing.assert_allclose(np.asarray(ex2(*ARGS)),
                               np.asarray(ARGS[0]) * 3.0)
    assert aot.load("finish", 4, 3, 128, "aaaaaaaa") is None
    assert len(deser_calls) == 1


def test_non_format_failures_do_not_latch(fresh_store):
    assert not aot.note_failure(TypeError(
        "deserialize_and_load() got an unexpected keyword argument"
    ))
    assert not aot._RUNTIME_REJECTED


def test_clear_rejection_unlatches(fresh_store, monkeypatch):
    aot.note_failure(RuntimeError("serialized executable is incompatible"))
    assert aot._RUNTIME_REJECTED and os.path.exists(aot._reject_marker())
    aot.clear_rejection()  # aot_precompile after an ALL-fresh run
    assert not aot._RUNTIME_REJECTED
    assert not os.path.exists(aot._reject_marker())


def test_env_disable_still_wins(fresh_store, monkeypatch):
    monkeypatch.setenv("OCT_PK_AOT", "0")
    assert not aot.enabled()
    assert not aot.writeback_enabled()
    sig = aot.sig_of(ARGS)
    aot.save("ed", 4, 3, 128, sig, _compiled(), {})
    monkeypatch.setattr(aot, "_LOADED", {})
    assert aot.load("ed", 4, 3, 128, sig) is None


# ---------------------------------------------------------------------------
# manifest integrity under concurrent writers
# ---------------------------------------------------------------------------


def test_manifest_concurrent_writers(fresh_store):
    """N threads saving distinct entries concurrently: every entry
    lands in the manifest (locked read-modify-write), the JSON never
    tears, and every artifact loads."""
    compiled = _compiled(5.0)
    n = 6
    errs: list = []

    def worker(i):
        try:
            aot.save(f"s{i}", 4, 3, 128, f"si{i:06x}", compiled, {})
        except Exception as e:  # noqa: BLE001 — surfaced below
            errs.append(e)

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(n)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert errs == []
    man = aot.read_manifest()
    assert len(man) == n
    for i in range(n):
        assert aot.entry_key(f"s{i}", 4, 3, 128, f"si{i:06x}") in man
    ok, problems = aot.check_store()
    assert problems == [] and ok == n


# ---------------------------------------------------------------------------
# store queries: status + aot_precompile --check
# ---------------------------------------------------------------------------


def test_store_status_counts_matching(fresh_store, monkeypatch):
    sig = aot.sig_of(ARGS)
    aot.save("ed", 4, 3, 128, sig, _compiled(), {})
    monkeypatch.setenv("OCT_AOT_BUILD_ID", "other-runtime v7")
    aot.save("ed", 4, 3, 128, sig, _compiled(), {})
    monkeypatch.delenv("OCT_AOT_BUILD_ID")
    st = aot.store_status()
    assert st["entries"] == 2
    assert st["matching"] == 1
    assert st["build_id"] == aot.build_id()


def test_check_store_reports_problems(fresh_store, monkeypatch):
    """aot_precompile --check: every manifest entry must deserialize
    under the current build — corrupt artifacts, missing files and
    foreign-build pins are each named."""
    sig = aot.sig_of(ARGS)
    aot.save("good", 4, 3, 128, sig, _compiled(), {})
    _poison("bad", "bbbbbbbb", saved_at=time.time())
    aot._manifest_update(
        aot.entry_key("ghost", 4, 3, 128, "cccccccc"),
        {"build_id": aot.build_id(), "saved_at": time.time()},
    )
    aot._manifest_update(
        aot.entry_key("foreign", 4, 3, 128, "dddddddd"),
        {"build_id": "some other runtime", "saved_at": time.time()},
    )
    (fresh_store / aot._build_slug() /
     "foreign_b4_d3_t128_dddddddd.jaxexec").write_bytes(b"x")
    ok, problems = aot.check_store()
    assert ok == 1
    assert len(problems) == 3
    joined = "\n".join(problems)
    assert "bad_b4_d3_t128_bbbbbbbb" in joined
    assert "no artifact file" in joined
    assert "pinned to build" in joined


# ---------------------------------------------------------------------------
# the _stage_call write-back integration (ops/pk/kernels)
# ---------------------------------------------------------------------------


def test_stage_call_writeback_then_warm_reload(fresh_store, monkeypatch):
    """_stage_call with write-back on: the cold call compiles
    explicitly, stores the executable, and a fresh process's first
    _stage_call LOADS it (aot outcome `loaded`, no compile)."""
    from ouroboros_consensus_tpu.obs.warmup import WARMUP
    from ouroboros_consensus_tpu.ops.pk import kernels as K

    monkeypatch.setenv("OCT_PK_AOT_WRITEBACK", "1")
    monkeypatch.setattr(K, "_FIRST_EXEC", set())
    monkeypatch.setattr(K, "_AOT_WARM", set())
    WARMUP.reset()
    fn = jax.jit(lambda x: x + 7.0)
    out = K._stage_call("tst", fn, 4, 3, *ARGS)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ARGS[0]) + 7.0)
    rep = WARMUP.report()
    assert rep["aot"].get("saved", 0) == 1
    assert rep["stages"]["tst@b4"]["via"] == "jit"
    # fresh process: the stored executable serves the stage
    _fresh_process(monkeypatch)
    monkeypatch.setattr(K, "_FIRST_EXEC", set())
    monkeypatch.setattr(K, "_AOT_WARM", set())
    WARMUP.reset()
    out2 = K._stage_call("tst", fn, 4, 3, *ARGS)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ARGS[0]) + 7.0)
    rep2 = WARMUP.report()
    assert rep2["aot"].get("loaded", 0) == 1
    assert rep2["stages"]["tst@b4"]["via"] == "aot"
    WARMUP.reset()
