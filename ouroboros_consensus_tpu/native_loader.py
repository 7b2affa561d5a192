"""ctypes bindings for the native chunk scanner (native/headerscan.cpp).

Builds the shared library on first use with g++ (cached next to the
source; rebuilt when the source is newer). Falls back gracefully — every
caller treats `load() is None` as "use the pure-Python path", so the
framework works without a toolchain.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from dataclasses import dataclass
from functools import cached_property

import numpy as np

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "native", "headerscan.cpp")
_SO = os.path.join(os.path.dirname(_SRC), "libheaderscan.so")
_CSRC = os.path.join(os.path.dirname(_SRC), "hostcrypto.cpp")
_CSO = os.path.join(os.path.dirname(_SRC), "libhostcrypto.so")

_lib = None
_tried = False
_clib = None
_ctried = False


def load():
    """The loaded library, building if needed; None if unavailable."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    try:
        if not os.path.exists(_SO) or os.path.getmtime(_SO) < os.path.getmtime(_SRC):
            subprocess.run(
                ["g++", "-O2", "-shared", "-fPIC", "-o", _SO, _SRC],
                check=True,
                capture_output=True,
            )
        lib = ctypes.CDLL(_SO)
    except Exception:
        return None
    lib.ocx_scan_items.restype = ctypes.c_int
    lib.ocx_scan_items.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int, ctypes.POINTER(ctypes.c_int64),
    ]
    lib.ocx_extract_headers.restype = ctypes.c_int
    lib.ocx_extract_headers.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t,  # buf, len
        ctypes.c_void_p, ctypes.c_int,  # offsets, n
        *([ctypes.c_void_p] * 25),
    ]
    lib.ocx_scan_eras.restype = ctypes.c_int
    lib.ocx_scan_eras.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t, ctypes.c_void_p, ctypes.c_int,
        *([ctypes.c_void_p] * 3),
    ]
    lib.ocx_extract_byron.restype = ctypes.c_int
    lib.ocx_extract_byron.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t, ctypes.c_void_p, ctypes.c_int,
        *([ctypes.c_void_p] * 13),
    ]
    lib.ocx_crc32_first_bad.restype = ctypes.c_int64
    lib.ocx_crc32_first_bad.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
    ]
    lib.ocx_parse_index.restype = ctypes.c_int64
    lib.ocx_parse_index.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t, ctypes.c_int64,
        *([ctypes.c_void_p] * 6),
    ]
    _lib = lib
    return _lib


def parse_index(buf: bytes):
    """Columnar parse of a concatenated-CBOR ImmutableDB index:
    (slots, block_nos, hashes[n,32], offsets, sizes, crcs) up to the
    first torn/malformed entry; None when the library is unavailable
    (callers fall back to the per-entry Python decode)."""
    lib = load()
    if lib is None:
        return None
    # true CBOR minimum is 40 bytes/entry (1-byte heads + 34-byte hash
    # item + four 1-byte uints + 1-5 byte crc); capacity at that bound
    # can never be hit by a well-formed index
    cap = max(1, len(buf) // 40 + 1)
    slots = np.zeros(cap, np.int64)
    block_nos = np.zeros(cap, np.int64)
    hashes = np.zeros((cap, 32), np.uint8)
    offsets = np.zeros(cap, np.int64)
    sizes = np.zeros(cap, np.int64)
    crcs = np.zeros(cap, np.int64)

    def ptr(a):
        return a.ctypes.data_as(ctypes.c_void_p)

    n = int(lib.ocx_parse_index(
        buf, len(buf), cap, ptr(slots), ptr(block_nos), ptr(hashes),
        ptr(offsets), ptr(sizes), ptr(crcs),
    ))
    if n >= cap:
        # capacity hit (cannot distinguish from a torn entry): let the
        # Python decode loop decide rather than silently truncating
        return None
    return (slots[:n], block_nos[:n], hashes[:n], offsets[:n], sizes[:n],
            crcs[:n])


def crc32_first_bad(buf: bytes, offsets, sizes, expected) -> int | None:
    """0-based index of the first span whose zlib.crc32 mismatches
    `expected`, -1 if all match; None when the library is unavailable
    (callers fall back to the per-span Python loop)."""
    lib = load()
    if lib is None:
        return None
    offs = np.ascontiguousarray(offsets, np.int64)
    szs = np.ascontiguousarray(sizes, np.int64)
    exp = np.ascontiguousarray(expected, np.int64)

    def ptr(a):
        return a.ctypes.data_as(ctypes.c_void_p)

    return int(
        lib.ocx_crc32_first_bad(buf, len(buf), ptr(offs), ptr(szs), ptr(exp), len(offs))
    )


def scan_items(buf: bytes, max_items: int = 1 << 20):
    """(offsets, sizes, end) of the complete top-level CBOR items in
    `buf`. `end` is where the well-formed prefix stops — == len(buf)
    iff the whole buffer parses; anything past `end` is a torn tail to
    truncate. None if the native library is unavailable."""
    lib = load()
    if lib is None:
        return None
    offsets = np.zeros(max_items, np.int64)
    sizes = np.zeros(max_items, np.int64)
    bad = ctypes.c_int64(0)
    n = lib.ocx_scan_items(
        buf, len(buf),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        sizes.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        max_items, ctypes.byref(bad),
    )
    return offsets[:n].copy(), sizes[:n].copy(), int(bad.value)


def load_crypto():
    """The native host-crypto library (native/hostcrypto.cpp), building
    on first use; None if unavailable. This is the libsodium-class
    single-core verification path — the measured CPU baseline of
    bench.py and db_analyser --backend native."""
    global _clib, _ctried
    if _clib is not None or _ctried:
        return _clib
    _ctried = True
    try:
        if not os.path.exists(_CSO) or os.path.getmtime(_CSO) < os.path.getmtime(_CSRC):
            subprocess.run(
                ["g++", "-O3", "-shared", "-fPIC", "-o", _CSO, _CSRC],
                check=True,
                capture_output=True,
            )
        lib = ctypes.CDLL(_CSO)
    except Exception:
        return None
    lib.oc_ed25519_verify.restype = ctypes.c_int
    lib.oc_ed25519_verify.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
    ]
    lib.oc_ecvrf_verify.restype = ctypes.c_int
    lib.oc_ecvrf_verify.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
        ctypes.c_char_p,
    ]
    lib.oc_kes_verify.restype = ctypes.c_int
    lib.oc_kes_verify.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_uint64,
        ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p, ctypes.c_size_t,
    ]
    lib.oc_sha512.restype = None
    lib.oc_sha512.argtypes = [ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p]
    lib.oc_blake2b.restype = None
    lib.oc_blake2b.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p, ctypes.c_int,
    ]
    lib.oc_crc32.restype = ctypes.c_uint32
    lib.oc_crc32.argtypes = [ctypes.c_void_p, ctypes.c_size_t, ctypes.c_uint32]
    lib.oc_blake2b_spans.restype = None
    lib.oc_blake2b_spans.argtypes = [
        ctypes.c_void_p, ctypes.c_long, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_int,
    ]
    lib.oc_validate_praos.restype = ctypes.c_long
    lib.oc_validate_praos.argtypes = (
        [ctypes.c_long] + [ctypes.c_void_p] * 6 + [ctypes.c_long]
        + [ctypes.c_void_p] * 8 + [ctypes.POINTER(ctypes.c_long)]
    )
    lib.oc_validate_praos2.restype = ctypes.c_long
    lib.oc_validate_praos2.argtypes = (
        [ctypes.c_long] + [ctypes.c_void_p] * 6 + [ctypes.c_long]
        + [ctypes.c_void_p] * 4 + [ctypes.c_long]
        + [ctypes.c_void_p] * 4 + [ctypes.POINTER(ctypes.c_long)]
    )
    lib.oc_validate_tpraos.restype = ctypes.c_long
    lib.oc_validate_tpraos.argtypes = (
        [ctypes.c_long] + [ctypes.c_void_p] * 6 + [ctypes.c_long]
        + [ctypes.c_void_p] * 10 + [ctypes.POINTER(ctypes.c_long)]
    )
    lib.oc_ecvrf_verify_bc.restype = ctypes.c_int
    lib.oc_ecvrf_verify_bc.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
        ctypes.c_char_p,
    ]
    lib.oc_ecvrf_prove_bc.restype = None
    lib.oc_ecvrf_prove_bc.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p,
    ]
    lib.oc_ed25519_public.restype = None
    lib.oc_ed25519_public.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
    lib.oc_ed25519_sign.restype = None
    lib.oc_ed25519_sign.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p,
    ]
    lib.oc_ecvrf_prove.restype = None
    lib.oc_ecvrf_prove.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p,
    ]
    _clib = lib
    return _clib


def native_crc32(data, value: int = 0):
    """CRC32 (zlib polynomial) via the native library — PCLMULQDQ
    folding on CPUs that have it, bit-identical to ``zlib.crc32``.
    None when the library is unavailable (callers fall back to zlib)."""
    lib = load_crypto()
    if lib is None or not hasattr(lib, "oc_crc32"):
        return None
    buf = np.frombuffer(data, np.uint8)
    return int(lib.oc_crc32(buf.ctypes.data, buf.size, value & 0xFFFFFFFF))


def native_blake2b_spans(data, starts, ends, digest_size: int = 32):
    """Batch blake2b over ``data[starts[i]:ends[i])`` via one C call →
    [n, digest_size] uint8, or None when the library is unavailable
    (callers fall back to the hashlib loop). `data` may be bytes, a
    memoryview, or an mmap — anything the buffer protocol exposes
    contiguously."""
    lib = load_crypto()
    if lib is None or not hasattr(lib, "oc_blake2b_spans"):
        return None
    buf = np.frombuffer(data, np.uint8)
    s = np.ascontiguousarray(starts, np.int64)
    e = np.ascontiguousarray(ends, np.int64)
    n = len(s)
    out = np.empty((n, digest_size), np.uint8)
    if n:
        lib.oc_blake2b_spans(
            buf.ctypes.data, n, s.ctypes.data, e.ctypes.data,
            out.ctypes.data, digest_size,
        )
    return out


def native_ed25519_sign(seed: bytes, msg: bytes) -> bytes | None:
    """Deterministic RFC 8032 signature via the C library, or None when
    the library is unavailable (callers fall back to pure Python)."""
    lib = load_crypto()
    if lib is None:
        return None
    out = ctypes.create_string_buffer(64)
    lib.oc_ed25519_sign(seed, msg, len(msg), out)
    return out.raw


def native_ed25519_public(seed: bytes) -> bytes | None:
    lib = load_crypto()
    if lib is None:
        return None
    out = ctypes.create_string_buffer(32)
    lib.oc_ed25519_public(seed, out)
    return out.raw


def native_ecvrf_prove(seed: bytes, alpha: bytes) -> bytes | None:
    """Deterministic draft-03 ECVRF proof via the C library, or None."""
    lib = load_crypto()
    if lib is None:
        return None
    out = ctypes.create_string_buffer(80)
    lib.oc_ecvrf_prove(seed, alpha, len(alpha), out)
    return out.raw


def native_ecvrf_prove_bc(seed: bytes, alpha: bytes) -> bytes | None:
    """128-byte batch-compatible proof (Gamma ‖ U ‖ V ‖ s), or None."""
    lib = load_crypto()
    if lib is None:
        return None
    out = ctypes.create_string_buffer(128)
    lib.oc_ecvrf_prove_bc(seed, alpha, len(alpha), out)
    return out.raw


def native_ed25519_verify(pk: bytes, sig: bytes, msg: bytes) -> bool:
    lib = load_crypto()
    assert lib is not None
    return bool(lib.oc_ed25519_verify(pk, sig, msg, len(msg)))


def native_ecvrf_verify(pk: bytes, pi: bytes, alpha: bytes):
    """beta bytes or None; proof format discriminated by length."""
    lib = load_crypto()
    assert lib is not None
    beta = ctypes.create_string_buffer(64)
    if len(pi) == 128:
        ok = lib.oc_ecvrf_verify_bc(pk, pi, alpha, len(alpha), beta)
    else:
        ok = lib.oc_ecvrf_verify(pk, pi, alpha, len(alpha), beta)
    return beta.raw if ok else None


def native_kes_verify(vk: bytes, depth: int, period: int, msg: bytes, sig: bytes) -> bool:
    lib = load_crypto()
    assert lib is not None
    return bool(lib.oc_kes_verify(vk, depth, period, msg, len(msg), sig, len(sig)))


def native_validate_praos(
    cold_vk: np.ndarray,    # [n, 32] uint8
    ocert_sig: np.ndarray,  # [n, 64]
    ocert_msg: np.ndarray,  # [n, 48]
    kes_vk: np.ndarray,     # [n, 32]
    kes_t: np.ndarray,      # [n] int64
    kes_sig: np.ndarray,    # [n, 96+32*depth]
    kes_depth: int,
    body: bytes,            # flattened signed_bytes
    body_off: np.ndarray,   # [n+1] int64
    vrf_vk: np.ndarray,     # [n, 32]
    vrf_proof: np.ndarray,  # [n, 80] draft-03 or [n, 128] batch-compatible
    vrf_alpha: np.ndarray,  # [n, 32]
    vrf_output: np.ndarray, # [n, 64]
    want_leader_values: bool = True,
):
    """(first_bad_index or -1, fail_kind 0|1:ocert|2:kes|3:vrf,
    leader_values [n, 32] or None, etas [n, 32] or None). The VRF proof
    format is discriminated by the column width."""
    lib = load_crypto()
    assert lib is not None
    n = len(cold_vk)
    lv = np.zeros((n, 32), np.uint8) if want_leader_values else None
    eta = np.zeros((n, 32), np.uint8) if want_leader_values else None

    def ptr(a):
        return a.ctypes.data_as(ctypes.c_void_p) if a is not None else None

    arrs = [
        np.ascontiguousarray(cold_vk, np.uint8),
        np.ascontiguousarray(ocert_sig, np.uint8),
        np.ascontiguousarray(ocert_msg, np.uint8),
        np.ascontiguousarray(kes_vk, np.uint8),
        np.ascontiguousarray(kes_t, np.int64),
        np.ascontiguousarray(kes_sig, np.uint8),
    ]
    proof = np.ascontiguousarray(vrf_proof, np.uint8)
    proof_len = int(proof.shape[-1]) if proof.ndim == 2 else 80
    tail = [
        np.ascontiguousarray(vrf_vk, np.uint8),
        proof,
    ]
    tail2 = [
        np.ascontiguousarray(vrf_alpha, np.uint8),
        np.ascontiguousarray(vrf_output, np.uint8),
    ]
    boff = np.ascontiguousarray(body_off, np.int64)
    body_arr = np.frombuffer(body, np.uint8) if body else np.zeros(1, np.uint8)
    kind = ctypes.c_long(0)
    rc = lib.oc_validate_praos2(
        n, *[ptr(a) for a in arrs], kes_depth,
        ptr(body_arr), ptr(boff), *[ptr(a) for a in tail], proof_len,
        *[ptr(a) for a in tail2], ptr(lv), ptr(eta),
        ctypes.byref(kind),
    )
    return int(rc), int(kind.value), lv, eta


def native_validate_tpraos(
    cold_vk, ocert_sig, ocert_msg, kes_vk, kes_t, kes_sig, kes_depth: int,
    body: bytes, body_off,
    vrf_vk, eta_proof, eta_alpha, eta_output, l_proof, l_alpha, l_output,
):
    """`native_validate_praos` for two-certificate (TPraos) headers:
    (first_bad_index or -1, fail_kind 0|1:ocert|2:kes|3:nonce proof|
    4:leader proof, etas [n, 32] = Blake2b-256 of each nonce output)."""
    lib = load_crypto()
    assert lib is not None
    n = len(cold_vk)
    eta = np.zeros((n, 32), np.uint8)

    def u8(a):
        return np.ascontiguousarray(a, np.uint8)

    body_arr = np.frombuffer(body, np.uint8) if body else np.zeros(1, np.uint8)
    arrs = [u8(cold_vk), u8(ocert_sig), u8(ocert_msg), u8(kes_vk),
            np.ascontiguousarray(kes_t, np.int64), u8(kes_sig)]
    tail = [body_arr, np.ascontiguousarray(body_off, np.int64), u8(vrf_vk),
            u8(eta_proof), u8(eta_alpha), u8(eta_output),
            u8(l_proof), u8(l_alpha), u8(l_output), eta]
    kind = ctypes.c_long(0)
    rc = lib.oc_validate_tpraos(
        n, *[a.ctypes.data_as(ctypes.c_void_p) for a in arrs], kes_depth,
        *[a.ctypes.data_as(ctypes.c_void_p) for a in tail],
        ctypes.byref(kind),
    )
    return int(rc), int(kind.value), eta


class MalformedBlock(ValueError):
    """extract_headers hit an unparseable block; `.index` is its
    position in the offsets array (blocks before it parsed clean)."""

    def __init__(self, index: int):
        super().__init__(f"malformed block at index {index}")
        self.index = index


def _span_matrix(buf_u8: np.ndarray, off: np.ndarray, ln: np.ndarray):
    """[n, w] uint8 matrix over the (offset, length) spans of the chunk
    buffer, or None when the spans are not uniform width (the columnar
    pipeline requires row-major rectangular columns; callers fall back
    to the per-row bytes list).

    Uniform-STRIDE spans (the common case: a chunk of equal-size
    blocks) come back as a ZERO-COPY strided view into the buffer;
    anything else is one vectorized fancy-index gather (int32 indices —
    chunk files are far under 2 GiB)."""
    n = len(off)
    if n == 0:
        return np.zeros((0, 0), np.uint8)
    w = int(ln[0])
    if not (ln == w).all():
        return None
    if n > 1:
        d = np.diff(off)
        d0 = int(d[0])
        if d0 > 0 and (d == d0).all():
            return np.lib.stride_tricks.as_strided(
                buf_u8[int(off[0]) :], shape=(n, w), strides=(d0, 1),
            )
    idx = off.astype(np.int32)[:, None] + np.arange(w, dtype=np.int32)
    return buf_u8[idx]


@dataclass
class HeaderColumns:
    """SoA header columns straight from chunk bytes — the zero-object
    fast path feeding protocol/batch.stage.

    The three variable-width fields (`ocert_sigma` / `kes_sig` /
    `signed_bytes`) are stored as (offset, length) spans into the chunk
    buffer: the per-row `bytes`-list views are built LAZILY on first
    access (the per-row slicing loop is exactly the object tax the
    columnar pipeline avoids), and the `*_mat` properties expose them as
    row-major uint8 matrices via one vectorized gather when the spans
    are uniform width (always, on real chains)."""

    n: int
    block_no: np.ndarray  # [n] int64
    slot: np.ndarray  # [n] int64
    prev_hash: np.ndarray  # [n, 32] uint8
    has_prev: np.ndarray  # [n] uint8
    issuer_vk: np.ndarray  # [n, 32]
    vrf_vk: np.ndarray  # [n, 32]
    vrf_output: np.ndarray  # [n, 64]
    vrf_proof: np.ndarray  # [n, 128] zero-padded to the widest format
    vrf_proof_len: np.ndarray  # [n] int64 — 80 (draft-03) or 128 (bc)
    body_size: np.ndarray  # [n] int64
    body_hash: np.ndarray  # [n, 32]
    ocert_vk: np.ndarray  # [n, 32]
    ocert_counter: np.ndarray  # [n] int64
    ocert_kes_period: np.ndarray  # [n] int64
    pv_major: np.ndarray
    pv_minor: np.ndarray
    header_end: np.ndarray  # [n] int64 — buf offset just past the header item
    raw: bytes  # the chunk buffer the spans point into
    sig_off: np.ndarray  # [n] int64 — OCert sigma span
    sig_len: np.ndarray  # [n] int64
    kes_off: np.ndarray  # [n] int64 — KES signature span
    kes_len: np.ndarray  # [n] int64
    sgn_off: np.ndarray  # [n] int64 — KES-signed body span
    sgn_len: np.ndarray  # [n] int64
    # TPraos bodies (11 fields): the leader certificate beside the nonce
    # certificate that `vrf_output` / `vrf_proof` then hold
    vrf_two: np.ndarray = None  # [n] uint8 — 1 = two certificates
    vrf_leader_output: np.ndarray = None  # [n, 64]
    vrf_leader_proof: np.ndarray = None  # [n, 80]

    def _span_list(self, off, ln) -> list:
        buf = self.raw
        return [
            buf[o : o + l]
            for o, l in zip(off.tolist(), ln.tolist())
        ]

    @cached_property
    def _buf_u8(self) -> np.ndarray:
        return np.frombuffer(self.raw, np.uint8)

    @cached_property
    def ocert_sigma(self) -> list:  # [n] bytes
        return self._span_list(self.sig_off, self.sig_len)

    @cached_property
    def kes_sig(self) -> list:  # [n] bytes
        return self._span_list(self.kes_off, self.kes_len)

    @cached_property
    def signed_bytes(self) -> list:  # [n] bytes — the KES-signed body span
        return self._span_list(self.sgn_off, self.sgn_len)

    @cached_property
    def ocert_sigma_mat(self):  # [n, 64] uint8 | None
        return _span_matrix(self._buf_u8, self.sig_off, self.sig_len)

    @cached_property
    def kes_sig_mat(self):  # [n, 96 + 32*depth] uint8 | None
        return _span_matrix(self._buf_u8, self.kes_off, self.kes_len)

    @cached_property
    def signed_bytes_mat(self):  # [n, body_len] uint8 | None
        return _span_matrix(self._buf_u8, self.sgn_off, self.sgn_len)


def _ptr(a):
    return a.ctypes.data_as(ctypes.c_void_p)


def scan_eras(buf: bytes, offsets: np.ndarray):
    """The era tag and the inner block's span of each era-tagged block
    ([era, inner], hardfork/combinator.HardForkBlock) at `offsets`:
    -> (era, inner_off, inner_len) int64 columns; None if the native
    library is unavailable. Raises MalformedBlock at the first block
    that is not era-tagged."""
    lib = load()
    if lib is None:
        return None
    n = len(offsets)
    offs = np.ascontiguousarray(offsets, np.int64)
    era, off, ln = (np.zeros(n, np.int64) for _ in range(3))
    rc = lib.ocx_scan_eras(buf, len(buf), _ptr(offs), n, _ptr(era),
                           _ptr(off), _ptr(ln))
    if rc != 0:
        raise MalformedBlock(rc - 1)
    return era, off, ln


def extract_byron(buf: bytes, offsets: np.ndarray) -> dict | None:
    """The columns of the Byron blocks (hardfork/byron_mock.py) at
    `offsets`: kind (1 main, 0 EBB), slot, block_no, prev_hash,
    has_prev, delegate_vk, sig, genesis_vk, tx_root, and the (offset,
    length) spans of the signed bytes and of the txs. None if the native
    library is unavailable; MalformedBlock at the first bad block."""
    lib = load()
    if lib is None:
        return None
    n = len(offsets)
    offs = np.ascontiguousarray(offsets, np.int64)
    i64 = lambda: np.zeros(n, np.int64)  # noqa: E731
    u8 = lambda w: np.zeros((n, w), np.uint8)  # noqa: E731
    cols = dict(kind=i64(), slot=i64(), block_no=i64(), prev_hash=u8(32),
                has_prev=np.zeros(n, np.uint8), delegate_vk=u8(32),
                sig=u8(64), genesis_vk=u8(32), tx_root=u8(32),
                signed_off=i64(), signed_len=i64(), txs_off=i64(),
                txs_len=i64())
    rc = lib.ocx_extract_byron(buf, len(buf), _ptr(offs), n,
                               *(_ptr(a) for a in cols.values()))
    if rc != 0:
        raise MalformedBlock(rc - 1)
    return cols


def extract_headers(buf: bytes, offsets: np.ndarray) -> HeaderColumns | None:
    """Parse the blocks at `offsets` into columns. None if the native
    library is unavailable. Raises ValueError on malformed blocks."""
    lib = load()
    if lib is None:
        return None
    n = len(offsets)
    offs = np.ascontiguousarray(offsets, np.int64)
    i64 = lambda: np.zeros(n, np.int64)
    u8 = lambda w: np.zeros((n, w), np.uint8)
    cols = dict(
        block_no=i64(), slot=i64(), prev_hash=u8(32),
        has_prev=np.zeros(n, np.uint8), issuer_vk=u8(32), vrf_vk=u8(32),
        vrf_output=u8(64), vrf_proof=u8(128), vrf_proof_len=i64(),
        body_size=i64(),
        body_hash=u8(32), ocert_vk=u8(32), ocert_counter=i64(),
        ocert_kes_period=i64(),
    )
    sig_off, sig_len = i64(), i64()
    pv_major, pv_minor = i64(), i64()
    kes_off, kes_len = i64(), i64()
    sgn_off, sgn_len = i64(), i64()
    vrf_two = np.zeros(n, np.uint8)
    leader_out, leader_proof = u8(64), u8(80)

    def ptr(a):
        return a.ctypes.data_as(ctypes.c_void_p)

    rc = lib.ocx_extract_headers(
        buf, len(buf), ptr(offs), n,
        ptr(cols["block_no"]), ptr(cols["slot"]),
        ptr(cols["prev_hash"]), ptr(cols["has_prev"]),
        ptr(cols["issuer_vk"]), ptr(cols["vrf_vk"]),
        ptr(cols["vrf_output"]), ptr(cols["vrf_proof"]),
        ptr(cols["vrf_proof_len"]),
        ptr(cols["body_size"]), ptr(cols["body_hash"]),
        ptr(cols["ocert_vk"]), ptr(cols["ocert_counter"]),
        ptr(cols["ocert_kes_period"]), ptr(sig_off), ptr(sig_len),
        ptr(pv_major), ptr(pv_minor),
        ptr(kes_off), ptr(kes_len), ptr(sgn_off), ptr(sgn_len),
        ptr(vrf_two), ptr(leader_out), ptr(leader_proof),
    )
    if rc != 0:
        raise MalformedBlock(rc - 1)
    return HeaderColumns(
        n=n,
        pv_major=pv_major,
        pv_minor=pv_minor,
        header_end=kes_off + kes_len,
        raw=buf,
        sig_off=sig_off, sig_len=sig_len,
        kes_off=kes_off, kes_len=kes_len,
        sgn_off=sgn_off, sgn_len=sgn_len,
        vrf_two=vrf_two, vrf_leader_output=leader_out,
        vrf_leader_proof=leader_proof,
        **cols,
    )
