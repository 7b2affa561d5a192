"""Hash-only crypto stubs for pipeline tests and the profiling twin.

The full curve graphs take minutes to compile on XLA:CPU; these stubs
keep every NON-crypto part of the batched pipeline byte-exact — packed
staging, device unpack, verdict bitmasks, the eta column, epilogue —
while replacing the three verifier subgraphs with
an all-valid verdict plus the REAL eta / leader-value range extensions
(the Blake2b tail the nonce fold and leader compare consume). The
differential suites (tests/test_packed_batch.py, test_columnar.py,
test_warm_ladder.py) and the `scripts/profile_replay.py --overlap-ab`
stubbed-crypto device twin share this one implementation.

`stub_agg_program` additionally stands in for the aggregated
(RLC/MSM) window program with the SAME output contract as
protocol/batch._jitted_packed_agg — limb-first eta/leader-value
handles, verdict_pack outputs — wrapped in `_warm_timed` so the
warm-ladder machinery (first-execute labels, background compile,
swap) exercises its real code path. An optional per-lane-count delay
simulates a compile wall (the slow-compile stub of the ladder tests
and the cold-cache harness)."""

from __future__ import annotations

import time

from jax import numpy as jnp

from ..ops import blake2b


def stub_verify_tpraos(*cols):
    """`stub_verify` for a TPraos window (batch.verify_tpraos's
    columns): eta is Blake2b-256 of the nonce output, the leader value
    the raw leader output."""
    from ..protocol import batch as pbatch

    *_, beta_eta, beta_l, _thr_lo, _thr_hi, _overlay = cols
    be = jnp.asarray(beta_eta).astype(jnp.int32)
    ones = jnp.ones((be.shape[0],), bool)
    return pbatch.TPraosVerdicts(
        ones, ones, ones, ones, ~ones,
        blake2b.blake2b_fixed(be, 64, 32),
        jnp.asarray(beta_l).astype(jnp.int32), ones,
    )


def stub_verify_pbft(layout, signed, sig, vk):
    """`pbft.verify_xla` with the curve arithmetic left to the host: the
    window's Ed25519 verdicts come back through `jax.pure_callback` from
    the native verifier (exact verdicts, no curve graph to compile), the
    rest of the program — the packed wire, the flag rows, `reduce` — as
    the device runs it."""
    import jax
    import numpy as np

    from .. import native_loader

    def host(signed, sig, vk):
        signed, sig, vk = (np.asarray(x, np.uint8) for x in (signed, sig, vk))
        return np.array([
            native_loader.native_ed25519_verify(
                vk[i].tobytes(), sig[i].tobytes(), signed[i].tobytes())
            for i in range(sig.shape[0])], np.bool_)

    b = sig.shape[0]
    ok = jax.pure_callback(host, jax.ShapeDtypeStruct((b,), jnp.bool_),
                           signed, sig, vk)
    one = jnp.ones((b,), jnp.int32)
    flags = jnp.stack([ok.astype(jnp.int32), one, one, one, 0 * one])
    return flags, jnp.zeros((b, 32), jnp.int32)


def stub_verify(*cols):
    """All-valid crypto stub with the real eta / leader-value range
    extensions. Arity-generic (21 draft-03 / 22 batch-compatible
    columns): beta_decl is always the third-from-last column."""
    from ..protocol import batch as pbatch

    beta_decl = cols[-3]
    bd = jnp.asarray(beta_decl).astype(jnp.int32)
    b = bd.shape[0]
    tag_l = jnp.broadcast_to(jnp.asarray([ord("L")], jnp.int32), (b, 1))
    lv = blake2b.blake2b_fixed(jnp.concatenate([tag_l, bd], axis=-1), 65, 32)
    tag_n = jnp.broadcast_to(jnp.asarray([ord("N")], jnp.int32), (b, 1))
    eta1 = blake2b.blake2b_fixed(jnp.concatenate([tag_n, bd], axis=-1), 65, 32)
    eta = blake2b.blake2b_fixed(eta1, 32, 32)
    ones = jnp.ones((b,), bool)
    return pbatch.Verdicts(ones, ones, ones, ones, jnp.zeros((b,), bool),
                           eta, lv)


def _first_exec_delay(delay_s, seen: set):
    """Host-side sleep on the FIRST call per argument lane count — the
    simulated compile wall (sleep releases the GIL, so a background
    'compile' overlaps the foreground replay exactly like XLA does)."""

    def maybe_sleep(lanes: int) -> None:
        if not delay_s:
            return
        if lanes in seen:
            return
        seen.add(lanes)
        d = delay_s(lanes) if callable(delay_s) else float(delay_s)
        if d > 0:
            time.sleep(d)

    return maybe_sleep


def stub_agg_program_builder(delay_s=None):
    """A drop-in for protocol/batch._jitted_packed_agg: same output
    contract (verdict_pack outputs + limb-first flags/eta/lv
    handles), crypto stubbed, `_warm_timed`-wrapped so first-execute
    labels and the store see the real machinery. `delay_s` (float or callable(lanes)->float) injects a
    simulated compile wall on the first execute per lane count."""
    import jax

    from ..protocol import batch as pbatch

    seen: set = set()
    sleep = _first_exec_delay(delay_s, seen)

    def builder(layout, mode="all"):
        key = ("stub-agg", layout, bool(delay_s))
        if key not in pbatch._JIT:

            def fn(body, kes_rs, kt_idx, kt_tab, slot, counter, c0,
                   thr_idx, thr_tab, nonce, body_layout, body_tab):
                cols = pbatch.unpack_packed(
                    layout, body, kes_rs, kt_idx, kt_tab, slot, counter,
                    c0, thr_idx, thr_tab, nonce, body_layout, body_tab,
                )
                v = stub_verify(*cols)
                flags = jnp.stack(
                    [v.ok_ocert_sig, v.ok_kes_sig, v.ok_vrf, v.ok_leader,
                     v.leader_ambiguous]
                ).astype(jnp.int32)
                return (pbatch.verdict_pack(flags, v.eta), flags, jnp.transpose(v.eta),
                        jnp.transpose(v.leader_value))

            jitted = jax.jit(fn)

            class _SlowJit:
                """Delegates to the jit but sleeps on the first touch
                per lane count — through EITHER the call path or the
                write-back's explicit trace/lower/compile path, so the
                simulated wall lands wherever the real compile would."""

                def __call__(self, *a):
                    sleep(int(a[0].shape[0]))
                    return jitted(*a)

                def trace(self, *a):
                    sleep(int(a[0].shape[0]))
                    return jitted.trace(*a)

            pbatch._JIT[key] = pbatch._warm_timed(
                f"agg-packed:{layout.body_len}b", _SlowJit(),
            )
        return pbatch._JIT[key]

    return builder


def _expand_host(tag: int, data: bytes, n: int) -> bytes:
    """Counter-mode Blake2b expansion — the host half of the stub
    forge-crypto family. MUST stay byte-identical to `_expand_dev`."""
    import hashlib

    out = b""
    i = 0
    while len(out) < n:
        out += hashlib.blake2b(
            bytes([tag, i]) + data, digest_size=32
        ).digest()
        i += 1
    return out[:n]


def _expand_dev(tag: int, data, data_len: int, n: int):
    """The device twin of `_expand_host` on [..., L] int32 byte rows."""
    parts = []
    for i in range((n + 31) // 32):
        pre = jnp.broadcast_to(
            jnp.asarray([tag, i], jnp.int32), (*data.shape[:-1], 2)
        )
        parts.append(
            blake2b.blake2b_fixed(
                jnp.concatenate([pre, data], axis=-1), data_len + 2, 32
            )
        )
    return jnp.concatenate(parts, axis=-1)[..., :n]


def make_stub_forge_sweep(plen: int):
    """Build a hash-twin of protocol/forge.forge_sweep: the VRF prove
    is replaced by the counter-mode expansion (compiles in seconds on
    XLA:CPU) while the alpha derivation, leader-value tail and
    threshold bracket stay REAL — so the election scatter, ambiguity
    split and proof-column splice are exercised end to end. Must agree
    byte-for-byte with the host stubs install_stub_forge patches into
    ops/host/fast.

    The proof length is captured HERE, at build time, and each call
    returns a fresh function object: jax's tracing cache keys on the
    function identity plus argument avals, and both formats present
    identical avals — a shared module-level sweep traced under one
    format would silently serve the other format's calls with the
    first trace's proof layout baked in."""

    def stub_forge_sweep(x, prefix, pk, slots, nonce, thr_lo, thr_hi):
        from ..ops import ecvrf_batch
        from ..protocol.batch import _lt_be

        x = jnp.asarray(x).astype(jnp.int32)
        alpha = ecvrf_batch.alpha_from_slots(
            jnp.asarray(slots).astype(jnp.int32), nonce
        )
        xa = jnp.concatenate([x, alpha], axis=-1)
        proof = _expand_dev(ord("p"), xa, 64, plen)
        p32 = blake2b.blake2b_fixed(proof, plen, 32)
        beta = _expand_dev(ord("b"), p32, 32, 64)
        tag_l = jnp.broadcast_to(
            jnp.asarray([ord("L")], jnp.int32), (*beta.shape[:-1], 1)
        )
        lv = blake2b.blake2b_fixed(
            jnp.concatenate([tag_l, beta], axis=-1), 65, 32
        )
        thr_lo = jnp.asarray(thr_lo).astype(jnp.int32)
        thr_hi = jnp.asarray(thr_hi).astype(jnp.int32)
        win = _lt_be(lv, thr_lo)
        ambiguous = ~win & _lt_be(lv, thr_hi)
        if plen == 128:
            g_enc, u_enc, v_enc, s32 = (
                proof[..., :32], proof[..., 32:64],
                proof[..., 64:96], proof[..., 96:128],
            )
            c16 = proof[..., :16]
        else:
            g_enc, c16, s32 = (
                proof[..., :32], proof[..., 32:48], proof[..., 48:80],
            )
            u_enc, v_enc = g_enc, g_enc
        return g_enc, c16, u_enc, v_enc, s32, beta, win, ambiguous

    return stub_forge_sweep


def make_stub_leader_sweep(plen: int):
    """The hash-twin of ops/pk/elect.leader_sweep (the chip's
    leader-value election): same arguments, same packed bitmaps, the
    pair's beta from the stub family of `make_stub_forge_sweep`, the
    leader-value tail and the bracket REAL. A new function an install,
    for the reason given there."""

    def stub_leader_sweep(x_tab, pk_tab, lo_tab, hi_tab, alpha):
        from ..protocol.batch import _lt_be

        p, s = x_tab.shape[0], alpha.shape[0]

        def grid(tab, by_pool):  # [S * P, 32], slot-major
            rows = jnp.asarray(tab).astype(jnp.int32)
            return (jnp.tile(rows, (s, 1)) if by_pool
                    else jnp.repeat(rows, p, axis=0))

        xa = jnp.concatenate([grid(x_tab, True), grid(alpha, False)], axis=-1)
        proof = _expand_dev(ord("p"), xa, 64, plen)
        beta = _expand_dev(ord("b"), blake2b.blake2b_fixed(proof, plen, 32),
                           32, 64)
        tag_l = jnp.broadcast_to(jnp.asarray([ord("L")], jnp.int32),
                                 (s * p, 1))
        lv = blake2b.blake2b_fixed(
            jnp.concatenate([tag_l, beta], axis=-1), 65, 32)
        win = _lt_be(lv, grid(lo_tab, True))
        amb = ~win & _lt_be(lv, grid(hi_tab, True))
        return tuple(jnp.packbits(b.reshape(s, p).astype(jnp.uint8), axis=1)
                     for b in (win, amb))

    return stub_leader_sweep


def install_stub_forge(monkeypatch, bucket: int = 256):
    """Stub the forge-side crypto for the tier-1 device differential:
    `fast.ecvrf_prove` / `ecvrf_proof_to_hash` / `ed25519_sign` become
    the counter-mode expansion family and the device sweep becomes
    `stub_forge_sweep` — every engine (loop / host / device) then
    forges the SAME bytes, at stub speed. `fast.ed25519_public` is
    deliberately NOT patched: ops/host/kes.derive_vk lru-caches vks
    derived through it, and a poisoned cache would outlive the patch.
    The device OCert batch-sign is rerouted through the (patched) host
    signer so no real ed25519 device graph compiles under the stub —
    the real forge_sign kernel is octrange-certified byte-identical to
    the host signer and exercised by the slow-tier differential."""
    from ..ops.host import ed25519 as he
    from ..ops.host import fast
    from ..protocol import forge as forge_mod
    from ..protocol.views import OCert

    # the proof length is pinned ONCE, at install time, and threaded
    # into a freshly built device sweep: see make_stub_forge_sweep on
    # why the sweep must be a new function object per install
    plen = 128 if fast.vrf_batch_compat() else 80

    def stub_prove(seed: bytes, alpha: bytes) -> bytes:
        x_bytes, _pref, _pk = he.expand_for_staging(seed)
        return _expand_host(ord("p"), x_bytes + alpha, plen)

    def stub_proof_to_hash(pi: bytes) -> bytes:
        # the proof is hashed to 32 bytes first: the device twin's
        # single-block blake2b_fixed cannot absorb tag+proof (130B bc)
        import hashlib

        p32 = hashlib.blake2b(pi, digest_size=32).digest()
        return _expand_host(ord("b"), p32, 64)

    def stub_sign(seed: bytes, msg: bytes) -> bytes:
        x_bytes, _pref, _pk = he.expand_for_staging(seed)
        return _expand_host(ord("s"), x_bytes + msg, 64)

    def stub_sign_ocerts(pools, triples) -> dict:
        out = {}
        for pool_i, counter, kp0 in sorted(triples):
            pool = pools[pool_i]
            oc = OCert(pool.kes_vk, counter, kp0, b"")
            sig = stub_sign(pool.cold_seed, oc.signable())
            out[(pool_i, counter, kp0)] = OCert(
                oc.vk_hot, oc.counter, oc.kes_period, sig
            )
        return out

    monkeypatch.setattr(fast, "ecvrf_prove", stub_prove)
    monkeypatch.setattr(fast, "ecvrf_proof_to_hash", stub_proof_to_hash)
    monkeypatch.setattr(fast, "ed25519_sign", stub_sign)
    monkeypatch.setattr(forge_mod, "_SWEEP_FN", make_stub_forge_sweep(plen))
    monkeypatch.setattr(forge_mod, "_LEADER_FN", make_stub_leader_sweep(plen))
    monkeypatch.setattr(forge_mod, "SWEEP_LANES", bucket)
    monkeypatch.setattr(forge_mod, "sign_ocerts_batch", stub_sign_ocerts)
    monkeypatch.setattr(forge_mod, "_JITS", {})
    monkeypatch.setattr(forge_mod, "FORGE_BUCKET", bucket)


def install_stub_crypto(monkeypatch=None, agg_delay_s=None):
    """Patch the crypto entry points of protocol/batch with the stubs.
    With a pytest `monkeypatch` the patches auto-revert; without one
    (profile_replay — a one-shot script process) they are applied
    directly. Covers the generic fused path, the packed xla path and
    the aggregated path; the pk split path routes through
    verify_praos_any inside the packed xla program."""
    import jax

    from ..protocol import batch as pbatch

    def setattr_(name, value):
        if monkeypatch is not None:
            monkeypatch.setattr(pbatch, name, value)
        else:
            setattr(pbatch, name, value)

    from ..protocol import pbft

    if monkeypatch is not None:
        monkeypatch.setattr(pbft, "verify_xla", stub_verify_pbft)
    else:
        pbft.verify_xla = stub_verify_pbft
    setattr_("verify_praos", stub_verify)
    setattr_("verify_praos_bc", stub_verify)
    setattr_("verify_praos_any", stub_verify)
    setattr_("verify_tpraos", stub_verify_tpraos)

    def patched_jv(bc=False):
        key = ("fn-stub", bc)
        if key not in pbatch._JIT:
            pbatch._JIT[key] = jax.jit(stub_verify)
        return pbatch._JIT[key]

    setattr_("_jitted_verify", patched_jv)
    setattr_("_jitted_packed_agg", stub_agg_program_builder(agg_delay_s))
