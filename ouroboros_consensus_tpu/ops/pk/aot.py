"""Build-pinned AOT artifact store for the stage programs.

Round-10 redesign of the deviceless-AOT cache: artifacts are keyed by
``(build_id, src_digest, stage, tile)`` and live under one directory
PER RUNTIME BUILD (``<aot_dir>/<build-slug>/``) with a provenance
manifest beside them.  An executable serialized by another runtime
build costs a doomed deserialize when it is loaded blind; against the
store that cannot happen: ``load`` consults the manifest's ``build_id``
BEFORE touching the artifact, so a build change turns every stale entry
into a zero-cost ``wrong_build`` skip instead of a rejected deserialize.

Artifacts enter the store two ways:

  * ``scripts/aot_precompile.py`` — the deviceless artifact BUILDER:
    compiles every stage against a TPU ``TopologyDescription`` on the
    build box and saves under the target build id (``OCT_AOT_BUILD_ID``
    — take it from a previous round's banked ``build_id``); its
    ``--check`` flag re-deserializes every manifest entry under the
    current runtime.
  * WRITE-BACK (``OCT_PK_AOT_WRITEBACK=1``, exported by bench.py to its
    device child): when a stage compiles through the jit path, the
    freshly compiled executable is re-serialized into the store for the
    CURRENT build — so after a format rejection the store heals itself
    and the next attempt/round loads warm instead of recompiling.  This
    replaces the old latch-and-skip behavior: a rejection still latches
    the remaining doomed loads of PRE-rejection entries, but the fresh
    re-serializations (saved after the rejection marker) load normally.

The reference ships pre-linked native crypto (libsodium ``.so``s
resolved at node start); the tpu-native analog of "crypto compiled
before the node runs" is PJRT executable serialization.

Everything here is fail-soft: any load/deserialize/run/save error falls
back to the per-stage jit (persistent compilation cache), which is
never worse than round 4's behavior.
"""

from __future__ import annotations

import json
import os
import pickle
import threading
import time


def _note_aot(stage: str, outcome: str, wall_s: float = 0.0,
              detail: str = "") -> None:
    """Warmup-forensics breadcrumb (obs/warmup.py): every load outcome —
    loaded / missing / wrong_build / failed / rejected / marker_skip /
    run_failed / saved — is attributed per stage, so a bench attempt
    that dies on the wall still shows which cache path ate it.
    Best-effort by contract."""
    try:
        from ...obs.warmup import WARMUP

        WARMUP.note_aot(stage, outcome, wall_s, detail)
    except Exception:
        pass

_DIR_ENV = "OCT_PK_AOT_DIR"
_ENABLE_ENV = "OCT_PK_AOT"  # "0" disables AOT dispatch (default: on —
# a missing/foreign-build store entry is a zero-cost skip, so the
# driver's bench.py run picks the executables up with no env plumbing)
_WRITEBACK_ENV = "OCT_PK_AOT_WRITEBACK"  # "1" = re-serialize freshly
# compiled stage programs into the store for the current build (bench.py
# exports it to the device child; default off so unit tests never write
# executables into the repo)
_BUILD_ENV = "OCT_AOT_BUILD_ID"  # provenance override for the
# deviceless builder: stamp artifacts with the TARGET runtime's
# platform_version (from a previous round's banked build_id) instead of
# the build box's own


def aot_dir() -> str:
    d = os.environ.get(_DIR_ENV, "")
    if d:
        return d
    here = os.path.dirname(os.path.abspath(__file__))
    repo = os.path.dirname(os.path.dirname(os.path.dirname(here)))
    return os.path.join(repo, "scripts", "aot_cache")


# Error substrings that mean the RUNTIME rejects an executable format
# wholesale. With the build-pinned store these should only ever fire on
# an entry whose manifest LIED about its build (platform_version is a
# proxy, not a proof) — one rejection still predicts the same failure
# for every other pre-rejection entry, so it latches the remaining
# loads of those and persists a marker whose mtime separates doomed
# old entries from the write-back re-serializations that heal the store.
INCOMPATIBLE_PATTERNS = (
    "serialized executable is incompatible",
    "deserialize failed",
)

_RUNTIME_REJECTED = False
_MARKER_CHECKED = False
_MARKER_TIME: float | None = None
_LOAD_LOCK = threading.Lock()
_BUILD_SLUG: str | None = None
_BUILD_ID: str | None = None


def build_id() -> str:
    """The full runtime build string (PJRT platform_version) artifacts
    are pinned to — overridable via $OCT_AOT_BUILD_ID for the
    deviceless builder."""
    global _BUILD_ID
    env = os.environ.get(_BUILD_ENV)
    if env:
        return env
    if _BUILD_ID is None:
        try:
            import jax

            _BUILD_ID = str(jax.devices()[0].client.platform_version)
        except Exception:
            import jax

            _BUILD_ID = f"jax-{jax.__version__}"
    return _BUILD_ID


def _build_slug() -> str:
    """Stable slug of the pinned build id: the store subdirectory name
    (and the keying the bench child uses for its per-build jax cache)."""
    global _BUILD_SLUG
    if os.environ.get(_BUILD_ENV):
        import hashlib

        return hashlib.blake2s(
            build_id().encode(), digest_size=6
        ).hexdigest()
    if _BUILD_SLUG is None:
        import hashlib

        _BUILD_SLUG = hashlib.blake2s(
            build_id().encode(), digest_size=6
        ).hexdigest()
    return _BUILD_SLUG


def store_dir(slug: str | None = None) -> str:
    """The per-build artifact directory."""
    return os.path.join(aot_dir(), slug or _build_slug())


def manifest_path(slug: str | None = None) -> str:
    return os.path.join(store_dir(slug), "MANIFEST.json")


def entry_key(name: str, b: int, kes_depth: int, tile: int,
              sig: str) -> str:
    return f"{name}_b{b}_d{kes_depth}_t{tile}_{sig}"


def read_manifest(slug: str | None = None) -> dict:
    """{entry_key: meta} for one build's store (empty on any problem —
    a corrupt manifest degrades to 'no artifacts', never a crash)."""
    try:
        with open(manifest_path(slug), encoding="utf-8") as f:
            doc = json.load(f)
        if not isinstance(doc, dict):
            return {}  # legacy list-format / hand-edited manifest
        entries = doc.get("entries", {})
        return entries if isinstance(entries, dict) else {}
    except (OSError, json.JSONDecodeError, ValueError):
        return {}


_MANIFEST_CACHE: dict[str, dict] = {}


def _cached_manifest(slug: str | None = None) -> dict:
    """Manifest read once per (process, build): load() consults it per
    stage miss, and per-key memoization bounds everything else. Saves
    refresh the cache in place."""
    s = slug or _build_slug()
    if s not in _MANIFEST_CACHE:
        _MANIFEST_CACHE[s] = read_manifest(s)
    return _MANIFEST_CACHE[s]


def _manifest_update(key: str, meta: dict, slug: str | None = None) -> None:
    """Read-modify-write one manifest entry under an exclusive file
    lock + atomic replace: concurrent writers (parallel precompile
    shards, the write-back racing a second replay thread) each land
    their entry without tearing the JSON."""
    import fcntl

    d = store_dir(slug)
    os.makedirs(d, exist_ok=True)
    lock_path = os.path.join(d, "MANIFEST.lock")
    with open(lock_path, "w") as lock_f:
        fcntl.flock(lock_f, fcntl.LOCK_EX)
        try:
            entries = read_manifest(slug)
            entries[key] = meta
            payload = {
                "comment": "build-pinned AOT artifact store "
                           "(ops/pk/aot.py); entries keyed "
                           "name_b{lanes}_d{depth}_t{tile}_{sig}",
                "entries": entries,
            }
            tmp = manifest_path(slug) + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
            os.replace(tmp, manifest_path(slug))
        finally:
            fcntl.flock(lock_f, fcntl.LOCK_UN)
    _MANIFEST_CACHE.setdefault(slug or _build_slug(), {})[key] = meta


def _reject_marker() -> str:
    return os.path.join(aot_dir(), f"REJECTED.{_build_slug()}")


def _check_marker() -> None:
    """Pick up a rejection persisted by an earlier PROCESS on the same
    build. Unlike the pre-round-10 latch this does NOT disable the load
    path outright: entries saved AFTER the marker (the write-back
    re-serializations that heal the store) still load; only entries the
    rejection already condemned are skipped."""
    global _RUNTIME_REJECTED, _MARKER_CHECKED, _MARKER_TIME
    if _MARKER_CHECKED:
        return
    _MARKER_CHECKED = True
    try:
        _MARKER_TIME = os.path.getmtime(_reject_marker())
    except OSError:
        _MARKER_TIME = None


def clear_rejection() -> None:
    """Drop the persisted per-build rejection (a FULL fresh store was
    written for this build — scripts/aot_precompile after an all-fresh
    run)."""
    global _RUNTIME_REJECTED, _MARKER_CHECKED, _MARKER_TIME
    try:
        os.remove(_reject_marker())
    except OSError:
        pass
    _RUNTIME_REJECTED = False
    _MARKER_CHECKED = True
    _MARKER_TIME = None


def note_failure(exc: BaseException) -> bool:
    """Record an AOT load/run failure; latches the in-process skip of
    PRE-rejection entries when the error says the runtime rejects the
    executable FORMAT, and persists a per-build marker whose mtime
    separates condemned entries from later write-back re-serializations
    (which load normally — the store heals instead of staying dark).
    Returns the latch state."""
    global _RUNTIME_REJECTED, _MARKER_TIME
    msg = str(exc).lower()
    if not _RUNTIME_REJECTED and any(p in msg for p in INCOMPATIBLE_PATTERNS):
        import sys

        print(
            "# pk-aot: runtime rejects this executable format — skipping "
            "the remaining pre-rejection store entries (write-back will "
            "re-serialize fresh ones for this build)",
            file=sys.stderr,
        )
        _RUNTIME_REJECTED = True
        try:
            os.makedirs(aot_dir(), exist_ok=True)
            # tmp -> fsync -> rename: the marker's mtime is load-bearing
            # (it separates condemned entries from post-rejection
            # write-backs), so a torn half-written marker after a crash
            # must be impossible
            tmp = _reject_marker() + ".tmp"
            with open(tmp, "w") as f:
                f.write(str(exc)[:500])
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, _reject_marker())
            _MARKER_TIME = os.path.getmtime(_reject_marker())
        except Exception:
            _MARKER_TIME = time.time()  # in-process latch still holds
    return _RUNTIME_REJECTED


def enabled() -> bool:
    """The AOT LOAD path lever (env only — a format rejection no longer
    disables the whole path, it only condemns pre-rejection entries;
    see note_failure)."""
    return os.environ.get(_ENABLE_ENV, "1") != "0"


def writeback_enabled() -> bool:
    """Re-serialize freshly compiled stage programs into the store for
    the current build (bench.py exports OCT_PK_AOT_WRITEBACK=1 to its
    device child; default off so unit runs never write executables)."""
    return enabled() and os.environ.get(_WRITEBACK_ENV, "0") == "1"


_SRC_DIGEST: str | None = None


def _src_digest() -> str:
    """Digest of the kernel source modules. Executables are compiled
    CODE: a cache entry keyed on shapes alone would silently run stale
    kernels after an ops/pk change (the persistent jit cache keys on
    the HLO hash and does not have this hazard)."""
    global _SRC_DIGEST
    if _SRC_DIGEST is None:
        import hashlib

        here = os.path.dirname(os.path.abspath(__file__))
        ops = os.path.dirname(here)
        h = hashlib.blake2s(digest_size=4)
        for mod in ("limbs.py", "hashes.py", "curve.py", "verify.py",
                    "kernels.py"):
            with open(os.path.join(here, mod), "rb") as f:
                h.update(f.read())
        # the pk modules build on these: a hash-core or limb-constant
        # edit there with unchanged shapes must also invalidate the
        # serialized executables
        for mod in ("field.py", "curve.py", "sha512.py", "blake2b.py",
                    "u64.py", os.path.join("host", "ed25519.py")):
            with open(os.path.join(ops, mod), "rb") as f:
                h.update(f.read())
        _SRC_DIGEST = h.hexdigest()
    return _SRC_DIGEST


def sig_of(args) -> str:
    """8-hex-char signature of the argument shapes+dtypes plus the
    kernel source digest. Executables are shape-exact, and the KES
    hash-block count varies per batch (it tracks the longest signed
    header bytes in the batch), so the signature — not just
    (batch, depth, tile) — keys the store entry."""
    import hashlib

    parts = [f"{tuple(a.shape)}:{a.dtype}" for a in args]
    parts.append(_src_digest())
    return hashlib.blake2s(
        "|".join(parts).encode(), digest_size=4
    ).hexdigest()


def stage_path(name: str, b: int, kes_depth: int, tile: int, sig: str,
               slug: str | None = None) -> str:
    return os.path.join(
        store_dir(slug), f"{entry_key(name, b, kes_depth, tile, sig)}.jaxexec"
    )


def save(name: str, b: int, kes_depth: int, tile: int, sig: str, compiled,
         meta: dict) -> str:
    """Serialize a jax.stages.Compiled into the store for the pinned
    build (atomic artifact write + locked manifest update). The
    manifest row carries the provenance every later `load` checks
    BEFORE deserializing: build_id, src_digest, saved_at."""
    from ...testing import chaos
    from jax.experimental import serialize_executable as se

    # chaos seam (aot-reject@stage:<name> against the STORE side): the
    # write-back caller's fail-soft contract absorbs it — a failed save
    # costs the artifact, never the replay
    chaos.fire("aot", stage=name)

    ser, in_tree, out_tree = se.serialize(compiled)
    path = stage_path(name, b, kes_depth, tile, sig)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    blob = pickle.dumps(
        {"ser": ser, "in_tree": in_tree, "out_tree": out_tree, "meta": meta}
    )
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(blob)
    os.replace(tmp, path)
    row = dict(meta)
    row.update({
        "stage": name, "b": b, "kes_depth": kes_depth, "tile": tile,
        "sig": sig, "build_id": build_id(), "src_digest": _src_digest(),
        "saved_at": time.time(), "bytes": len(blob),
    })
    _manifest_update(entry_key(name, b, kes_depth, tile, sig), row)
    return path


# negative results included; writes hold _LOAD_LOCK (the bare `key in
# _LOADED` fast-path read is GIL-atomic on a monotonic memo)
_LOADED: dict = {}  # guarded-by: _LOAD_LOCK


def _deserialize(path: str):
    """One artifact file -> a loaded executable on the device it was
    compiled for. Every stored program is a one-device program built
    for the process's first device, so that is where it is loaded:
    without `execution_devices` JAX spreads it over every local device
    and its first call dies ("expected N shards"). A sharded program
    would have to carry its own device list in the artifact."""
    import jax
    from jax.experimental import serialize_executable as se

    with open(path, "rb") as f:
        blob = pickle.load(f)
    return se.deserialize_and_load(
        blob["ser"], blob["in_tree"], blob["out_tree"],
        execution_devices=jax.devices()[:1],
    )


def load(name: str, b: int, kes_depth: int, tile: int, sig: str):
    """Deserialize-and-load a store entry onto the live backend.

    Returns a callable with the stage fn's signature, or None. The
    manifest gates every deserialize: no entry -> `missing`; an entry
    pinned to a DIFFERENT build -> `wrong_build` (zero-cost — this is
    what replaces the ~15 s doomed deserializes of r02-r05); an entry
    condemned by an earlier format rejection (saved before the
    REJECTED marker) -> `marker_skip`. Memoized — including negative
    results, so a failing stage is probed once. Deserializes run
    one-at-a-time under a lock with the latch re-checked inside it:
    concurrent callers (the main dispatch thread and the materialize
    worker's aggregate re-dispatch) can never stack a second doomed
    deserialize behind the first one's rejection."""
    key = (name, b, kes_depth, tile, sig)
    # lock-free memo probe BY DESIGN: a hit is immutable once written,
    # the read is GIL-atomic, and taking _LOAD_LOCK here would park a
    # warm caller behind a concurrent multi-second deserialize; misses
    # re-check under the lock below.
    if key in _LOADED:
        return _LOADED[key]
    if not enabled():
        return None
    from ...testing import chaos

    if chaos.armed():
        try:
            chaos.fire("aot", stage=name)
        except chaos.AotRejectChaos as e:
            # the injected message matches INCOMPATIBLE_PATTERNS, so
            # this is the r04 failure shape end to end — but the
            # process-wide latch/marker stay untouched: chaos faults
            # are transient by contract, a persisted marker would
            # outlive the injection and condemn real entries
            _note_aot(name, "rejected", detail=repr(e))
            with _LOAD_LOCK:
                _LOADED.setdefault(key, None)
            return None
    meta = _cached_manifest().get(entry_key(name, b, kes_depth, tile, sig))
    if meta is None:
        _note_aot(name, "missing")
        with _LOAD_LOCK:
            _LOADED.setdefault(key, None)
        return None
    if meta.get("build_id") != build_id():
        _note_aot(name, "wrong_build",
                  detail=f"artifact build {meta.get('build_id')!r}")
        with _LOAD_LOCK:
            _LOADED.setdefault(key, None)
        return None

    def _condemned() -> bool:
        _check_marker()
        if not (_RUNTIME_REJECTED or _MARKER_TIME is not None):
            return False
        saved_at = float(meta.get("saved_at") or 0.0)
        marker = _MARKER_TIME if _MARKER_TIME is not None else time.time()
        return saved_at <= marker

    if _condemned():
        _note_aot(name, "marker_skip", detail=_reject_marker())
        with _LOAD_LOCK:
            _LOADED.setdefault(key, None)
        return None
    result = None
    path = stage_path(name, b, kes_depth, tile, sig)
    with _LOAD_LOCK:
        if key in _LOADED:
            return _LOADED[key]
        if _condemned():  # a racing load latched while we waited
            _note_aot(name, "marker_skip", detail=_reject_marker())
            _LOADED[key] = None
            return None
        t0 = time.monotonic()
        try:
            result = _deserialize(path)
            _note_aot(name, "loaded", time.monotonic() - t0)
        except Exception as e:  # noqa: BLE001 — fail-soft by contract
            import sys

            print(f"# pk-aot: load {key} failed: {e!r}", file=sys.stderr)
            rejected = note_failure(e)
            _note_aot(
                name, "rejected" if rejected else "failed",
                time.monotonic() - t0, repr(e),
            )
            result = None
        # memoize INSIDE the lock: a racing caller must see the
        # entry the moment the lock frees, not re-deserialize
        _LOADED[key] = result
    return result


def compile_and_store(name: str, b: int, kes_depth: int, tile: int,
                      jitted_fn, args, via: str = "writeback"):
    """The write-back path: explicitly lower+compile a cold stage jit,
    re-serialize the executable into the store for the CURRENT build,
    and memoize it so later dispatches (and, through the store, later
    PROCESSES on this build) go straight to the warm executable. This
    is how a format rejection heals: the fallback compile that
    was always going to happen anyway now leaves a loadable artifact
    behind instead of only a process-local jit cache entry.

    Fail-soft: any trace/lower/compile/serialize problem returns None
    and the caller dispatches the plain jit exactly as before."""
    sig = sig_of(args)
    key = (name, b, kes_depth, tile, sig)
    try:
        if not hasattr(jitted_fn, "trace"):
            import jax

            jitted_fn = jax.jit(jitted_fn)
        compiled = jitted_fn.trace(*args).lower().compile()
    except Exception as e:  # noqa: BLE001 — never worse than the jit path
        import sys

        print(f"# pk-aot: write-back compile for {key} failed, "
              f"using the jit path: {e!r}", file=sys.stderr)
        return None
    t0 = time.monotonic()
    try:
        path = save(name, b, kes_depth, tile, sig, compiled, {"via": via})
        _note_aot(name, "saved", time.monotonic() - t0, path)
    except Exception as e:  # noqa: BLE001 — the compile still serves
        import sys

        print(f"# pk-aot: write-back save for {key} failed: {e!r}",
              file=sys.stderr)
    with _LOAD_LOCK:
        _LOADED[key] = compiled
    return compiled


def store_status() -> dict:
    """One store query replacing the bench child's old BUILD_ID-marker
    heuristics: how many artifacts exist, and how many are loadable by
    THIS runtime (manifest build_id + src_digest both current)."""
    total = matching = stale_src = 0
    try:
        slugs = [e for e in os.listdir(aot_dir())
                 if os.path.isdir(os.path.join(aot_dir(), e))]
    except OSError:
        slugs = []
    for slug in slugs:
        for meta in read_manifest(slug).values():
            total += 1
            if meta.get("build_id") == build_id():
                if meta.get("src_digest") == _src_digest():
                    matching += 1
                else:
                    stale_src += 1
    return {
        "build_id": build_id(), "slug": _build_slug(),
        "entries": total, "matching": matching, "stale_src": stale_src,
    }


def check_store(slug: str | None = None) -> tuple[int, list[str]]:
    """`aot_precompile.py --check`: verify every manifest entry of one
    build's store deserializes under the CURRENT build id. Returns
    (ok_count, problems) — problems name the entry and why (missing
    artifact, build mismatch, failed deserialize)."""
    problems: list[str] = []
    ok = 0
    entries = read_manifest(slug)
    if not entries:
        return 0, [f"no manifest entries under {store_dir(slug)}"]
    for key, meta in sorted(entries.items()):
        path = os.path.join(store_dir(slug), f"{key}.jaxexec")
        if not os.path.exists(path):
            problems.append(f"{key}: manifest entry with no artifact file")
            continue
        if meta.get("build_id") != build_id():
            problems.append(
                f"{key}: pinned to build {meta.get('build_id')!r}, "
                f"runtime is {build_id()!r}"
            )
            continue
        try:
            _deserialize(path)
            ok += 1
        except Exception as e:  # noqa: BLE001 — report, don't crash
            problems.append(f"{key}: deserialize failed: {e!r}")
    return ok, problems
