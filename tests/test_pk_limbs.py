"""Differential tests: ops/pk/limbs (limb-first) vs ops/field + host ints.

Everything runs on CPU under plain jit — the pk functions are pure jnp,
so correctness established here carries to the Pallas kernels that call
them (same trace).
"""

import numpy as np
import pytest

import jax
from jax import numpy as jnp

from ouroboros_consensus_tpu.ops import field as fe
from ouroboros_consensus_tpu.ops.pk import limbs as pk

B = 64
rng = np.random.default_rng(42)


def rand_fe_cols(b=B):
    """[20, b] nearly-normalized random elements + their int values."""
    arr = rng.integers(0, fe.B_MAX, size=(fe.NLIMBS, b), dtype=np.int32)
    vals = [fe.limbs_to_int_np(arr[:, i]) for i in range(b)]
    return jnp.asarray(arr), vals


def col_ints(x):
    x = np.asarray(x)
    return [fe.limbs_to_int_np(x[:, i]) for i in range(x.shape[1])]


@pytest.fixture(scope="module")
def ab():
    a, av = rand_fe_cols()
    b, bv = rand_fe_cols()
    return a, av, b, bv


def test_mul_sqr_add_sub(ab):
    a, av, b, bv = ab
    got = col_ints(jax.jit(pk.mul)(a, b))
    assert [g % fe.P_INT for g in got] == [
        (x * y) % fe.P_INT for x, y in zip(av, bv)
    ]
    got = col_ints(jax.jit(pk.sqr)(a))
    assert [g % fe.P_INT for g in got] == [x * x % fe.P_INT for x in av]
    got = col_ints(jax.jit(pk.add)(a, b))
    assert [g % fe.P_INT for g in got] == [(x + y) % fe.P_INT for x, y in zip(av, bv)]
    got = col_ints(jax.jit(pk.sub)(a, b))
    assert [g % fe.P_INT for g in got] == [(x - y) % fe.P_INT for x, y in zip(av, bv)]


def _term_by_term_mul(a, b):
    """The reference formulation of limbs.mul: each of the 20 terms
    a * b[i] zero-padded to its own row i of a [41, T] accumulator and
    added there, then the same carries and folds."""
    t = max(a.shape[-1], b.shape[-1])
    ztail = jnp.zeros((21, t), jnp.int32)
    first = jnp.broadcast_to(a * b[0:1], (pk.NLIMBS, t))
    acc = jnp.concatenate([first, ztail], axis=0)
    for i in range(1, pk.NLIMBS):
        term = a * b[i : i + 1]
        acc = acc + jnp.concatenate(
            [jnp.zeros((i, t), jnp.int32), term, ztail[: 21 - i]], axis=0
        )
    for _ in range(2):
        c = acc >> pk.BITS
        acc = (acc & pk.MASK) + jnp.concatenate(
            [jnp.zeros((1, t), jnp.int32), c[:-1]], axis=0
        )
    lo, hi, top = acc[: pk.NLIMBS], acc[pk.NLIMBS : 40], acc[40:]
    lo = lo + hi * pk.FOLD
    row0 = lo[:1] + top * (pk.FOLD * pk.FOLD)
    lo = jnp.concatenate([row0, lo[1:]], axis=0)
    return pk.weak_reduce(lo, passes=2)


TILE = 128
_C = 0x5DEECE66D * fe.P_INT // 7919  # an arbitrary field constant


def _operands(case):
    """(a, b, which operand is the constant _C or None) for one case,
    [20, TILE] nearly-normalized limbs."""
    g = np.random.default_rng(7)
    a = g.integers(0, fe.B_MAX + 1, size=(fe.NLIMBS, TILE), dtype=np.int32)
    b = g.integers(0, fe.B_MAX + 1, size=(fe.NLIMBS, TILE), dtype=np.int32)
    if case == "b-max":
        a[:] = b[:] = fe.B_MAX
    elif case == "zeros":
        a[:] = b[:] = 0
    elif case.startswith("one-hot-"):
        # lane j: a one-hot at row j, b one-hot at row i: the product
        # term lands at row i + j, for every j at this i
        i = int(case.rsplit("-", 1)[1])
        a[:, : fe.NLIMBS] = fe.B_MAX * np.eye(fe.NLIMBS, dtype=np.int32)
        b[:, : fe.NLIMBS] = 0
        b[i, : fe.NLIMBS] = fe.B_MAX
    return a, b, case[-1] if case.startswith("const-") else None


def _both(a, b, const):
    """mul and sqr by limbs.mul and by the reference, stacked [80, T]."""
    if const == "a":
        a = pk.constant(_C)
    elif const == "b":
        b = pk.constant(_C)
    outs = [pk.mul(a, b), pk.sqr(a), _term_by_term_mul(a, b),
            _term_by_term_mul(a, a)]
    return jnp.concatenate(
        [jnp.broadcast_to(o, (fe.NLIMBS, TILE)) for o in outs], axis=0)


@pytest.mark.parametrize("where", ["outside-kernel", "in-kernel"])
@pytest.mark.parametrize(
    "case",
    ["random", "b-max", "zeros", "const-a", "const-b"]
    + [f"one-hot-{i}" for i in range(fe.NLIMBS)],
)
def test_mul_is_the_term_by_term_product_bit_for_bit(case, where):
    """limbs.mul adds each term at a vreg-aligned row from row-offset
    copies of a: the same integers in every accumulator row, so the same
    bits out, outside a kernel (constants [20, 1]) and inside one under
    kernel_consts (constants [20, T] fills), in Pallas interpret mode."""
    from jax.experimental import pallas as pl

    a, b, const = _operands(case)
    if where == "outside-kernel":
        out = jax.jit(lambda x, y: _both(x, y, const))(a, b)
    else:

        def kernel(a_ref, b_ref, o_ref):
            with pk.kernel_consts(TILE):
                o_ref[...] = _both(a_ref[...], b_ref[...], const)

        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((4 * fe.NLIMBS, TILE), jnp.int32),
            interpret=True,
        )(a, b)
    out = np.asarray(out)
    got, want = out[: 2 * fe.NLIMBS], out[2 * fe.NLIMBS :]
    np.testing.assert_array_equal(got, want)
    if case == "zeros":
        assert not got.any()
    x = _C if const == "a" else None
    y = _C if const == "b" else None
    for k in (0, 19, TILE - 1):
        av = x if x is not None else fe.limbs_to_int_np(a[:, k])
        bv = y if y is not None else fe.limbs_to_int_np(b[:, k])
        assert fe.limbs_to_int_np(got[: fe.NLIMBS, k]) % fe.P_INT == (
            av * bv % fe.P_INT)


def test_canonical_parity_eq(ab):
    a, av, b, bv = ab
    got = col_ints(jax.jit(pk.canonical)(a))
    assert got == [x % fe.P_INT for x in av]
    par = np.asarray(jax.jit(pk.parity)(a))
    assert list(par) == [(x % fe.P_INT) & 1 for x in av]
    assert not np.asarray(jax.jit(pk.eq)(a, b)).any()
    assert np.asarray(jax.jit(pk.eq)(a, a)).all()


def test_inv_legendre_sqrt(ab):
    a, av, b, bv = ab
    got = col_ints(jax.jit(pk.inv)(a))
    assert [g % fe.P_INT for g in got] == [
        pow(x % fe.P_INT, fe.P_INT - 2, fe.P_INT) for x in av
    ]
    leg = col_ints(jax.jit(pk.legendre)(a))
    assert [g % fe.P_INT for g in leg] == [
        pow(x % fe.P_INT, (fe.P_INT - 1) // 2, fe.P_INT) for x in av
    ]
    # sqrt of squares round-trips
    sq = jax.jit(pk.sqr)(a)
    ok, r = jax.jit(pk.sqrt)(sq)
    assert np.asarray(ok).all()
    r2 = col_ints(jax.jit(pk.sqr)(r))
    assert [g % fe.P_INT for g in r2] == [x * x % fe.P_INT for x in av]


def test_bytes_roundtrip(ab):
    a, av, _, _ = ab
    by = jax.jit(pk.to_bytes)(a)
    by_np = np.asarray(by)
    for i in range(B):
        want = (av[i] % fe.P_INT).to_bytes(32, "little")
        assert bytes(by_np[:, i].astype(np.uint8)) == want
    back = col_ints(jax.jit(pk.from_bytes32)(by))
    assert back == [x % fe.P_INT for x in av]


def test_scalar_reduce512_and_canonical():
    raw = rng.integers(0, 256, size=(64, B), dtype=np.int32)
    got = col_ints(jax.jit(pk.reduce512)(jnp.asarray(raw)))
    for i in range(B):
        v = int.from_bytes(bytes(raw[:, i].astype(np.uint8)), "little")
        assert got[i] == v % pk.L_INT

    s = rng.integers(0, 256, size=(32, B), dtype=np.int32)
    s[:, 0] = 0
    s[:, 1] = 255  # 2^256-1 > L
    canon = np.asarray(jax.jit(pk.is_canonical_scalar)(jnp.asarray(s)))
    for i in range(B):
        v = int.from_bytes(bytes(s[:, i].astype(np.uint8)), "little")
        assert canon[i] == (v < pk.L_INT)


def test_windows():
    s = rng.integers(0, 256, size=(32, B), dtype=np.int32)
    w4 = np.asarray(jax.jit(lambda x: pk.windows4_from_bytes(x, 256))(jnp.asarray(s)))
    w8 = np.asarray(jax.jit(lambda x: pk.windows8_from_bytes(x, 256))(jnp.asarray(s)))
    for i in range(B):
        v = int.from_bytes(bytes(s[:, i].astype(np.uint8)), "little")
        assert [int(d) for d in w4[:, i]] == [(v >> (4 * k)) & 0xF for k in range(64)]
        assert [int(d) for d in w8[:, i]] == [(v >> (8 * k)) & 0xFF for k in range(32)]

    a, av = rand_fe_cols()
    ac = jax.jit(pk.canonical)(a)
    w4l = np.asarray(jax.jit(lambda x: pk.windows4_from_limbs(x, 256))(ac))
    w8l = np.asarray(jax.jit(lambda x: pk.windows8_from_limbs(x, 256))(ac))
    for i in range(B):
        v = av[i] % fe.P_INT
        assert [int(d) for d in w4l[:, i]] == [(v >> (4 * k)) & 0xF for k in range(64)]
        assert [int(d) for d in w8l[:, i]] == [(v >> (8 * k)) & 0xFF for k in range(32)]
