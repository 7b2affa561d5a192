"""Fast host sign-side dispatch: native C++ when available, pure Python
otherwise — byte-identical either way (both are the deterministic
RFC 8032 / ECVRF-draft-03 constructions; differential test:
tests/test_native_crypto.py).

The pure modules (ed25519.py, ecvrf.py, kes.py) stay untouched as the
REFERENCE implementations; forging-side callers (fixtures, forge,
hotkey, db_synthesizer) route through here so benchmark chains and
ThreadNet nodes sign at C speed.
"""

from __future__ import annotations

from . import ecvrf as _ecvrf
from . import ed25519 as _ed25519


def _lib():
    from ... import native_loader

    return native_loader.load_crypto()


def ed25519_sign(seed: bytes, msg: bytes) -> bytes:
    if _lib() is not None:
        from ... import native_loader

        return native_loader.native_ed25519_sign(seed, msg)
    return _ed25519.sign(seed, msg)


def ed25519_public(seed: bytes) -> bytes:
    if _lib() is not None:
        from ... import native_loader

        return native_loader.native_ed25519_public(seed)
    return _ed25519.secret_to_public(seed)


def vrf_batch_compat() -> bool:
    """OCT_VRF_BATCH (default 1): forge batch-compatible 128-byte ECVRF
    proofs (Gamma ‖ U ‖ V ‖ s — the aggregatable PraosBatchCompat shape).
    =0 restores draft-03 80-byte proofs end to end. Read per call so
    tests can toggle both formats in one process."""
    import os

    return os.environ.get("OCT_VRF_BATCH", "1") != "0"


def ecvrf_prove(seed: bytes, alpha: bytes) -> bytes:
    """Proof in the configured format (vrf_batch_compat)."""
    if vrf_batch_compat():
        if _lib() is not None:
            from ... import native_loader

            return native_loader.native_ecvrf_prove_bc(seed, alpha)
        return _ecvrf.prove_batch_compat(seed, alpha)
    if _lib() is not None:
        from ... import native_loader

        return native_loader.native_ecvrf_prove(seed, alpha)
    return _ecvrf.prove(seed, alpha)


def ecvrf_prove_draft03(seed: bytes, alpha: bytes) -> bytes:
    """An 80-byte draft-03 proof whatever `vrf_batch_compat` says: the
    one format a TPraos header's two certificates have."""
    if _lib() is not None:
        from ... import native_loader

        return native_loader.native_ecvrf_prove(seed, alpha)
    return _ecvrf.prove(seed, alpha)


def ecvrf_proof_to_hash(pi: bytes) -> bytes:
    lib = _lib()
    if lib is not None:
        import ctypes

        out = ctypes.create_string_buffer(64)
        if lib.oc_ecvrf_proof_to_hash(pi, out):
            return out.raw
    return _ecvrf.proof_to_hash(pi)
