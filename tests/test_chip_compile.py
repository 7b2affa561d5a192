"""Kept rehearsal: the per-lane stage programs of the chip path compile
for a v5e at the production window width, WITHOUT a chip.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (`jax.experimental.topologies`). Interpret-mode
tests cannot see what it refuses — a slice off the tiling, more fast
memory than a kernel may use — so the stages `chip_smoke.py` dispatches
on the chip (ops/pk/kernels.verify_praos_packed_split) are compiled
here at 8192 lanes, KES depth 7, 128-byte proofs. A compile that passes
is not a chip run.

Tier-1: `unpack` (with and without an epoch nonce), `reduce`, `finish`.
Marked slow (25–90 s each): `ed`, `kes`, `vrf_bc`, `vrf`, and the forge's
leader sweep (`ops/pk/elect.py`, 262,144 lanes).

The topology is described inside a module-scoped fixture, never at
import: only one process may hold the TPU library, and every xdist
worker imports every test file. Keep these tests in this one file.
"""

import functools
from fractions import Fraction

import numpy as np
import pytest

import jax
from jax.sharding import SingleDeviceSharding

from ouroboros_consensus_tpu.block.forge import forge_block
from ouroboros_consensus_tpu.ops.pk import hashes
from ouroboros_consensus_tpu.ops.pk import kernels as K
from ouroboros_consensus_tpu.protocol import batch as pbatch
from ouroboros_consensus_tpu.protocol import praos
from ouroboros_consensus_tpu.testing import fixtures

LANES = 8192
KES_DEPTH = 7
V5E_HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_seams(monkeypatch):
    """Steer the program's CPU/TPU seams onto their chip branch for the
    duration of one test, and keep the persistent cache out of it (a
    deviceless executable is written to the cache but cannot be read
    back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setenv("OCT_PK_INTERPRET", "0")  # real Mosaic lowering
    monkeypatch.setattr(hashes, "FORCE_IMPL", "unrolled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _params():
    return praos.PraosParams(
        slots_per_kes_period=3600, max_kes_evolutions=62,
        security_param=2160, active_slot_coeff=Fraction(1, 2),
        epoch_length=43200, kes_depth=KES_DEPTH,
    )


@functools.lru_cache(maxsize=None)
def _packed(has_nonce: bool):
    """(layout, unpack-argument arrays padded to LANES) of a real
    default-forged window: 128-byte proofs, real CBOR bodies."""
    params = _params()
    pool = fixtures.make_pool(0, kes_depth=KES_DEPTH)
    lview = fixtures.make_ledger_view([pool])
    nonce = b"\x07" * 32 if has_nonce else None
    hvs, prev = [], b"\xaa" * 32
    for i in range(8):
        blk = forge_block(params, pool, slot=1000 + i, block_no=500 + i,
                          prev_hash=prev, epoch_nonce=nonce)
        hvs.append(blk.header.to_view())
        prev = blk.header.hash_
    layout, parr = pbatch.stage_packed(params, lview, nonce, hvs)
    assert layout.vrf_proof_len == 128 and layout.has_nonce is has_nonce
    return layout, pbatch.pad_packed_to(parr, LANES)


def _sds(sharding, shape, dtype=np.int32):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _compile(fn, args):
    """Lower for the TPU and compile. `fn` is wrapped in a FRESH
    function so that no trace another test made of the same stage in
    interpret mode can be reused."""

    def fresh(*a):
        return fn(*a)

    lowered = jax.jit(fresh).trace(*args).lower(lowering_platforms=("tpu",))
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes + ma.generated_code_size_in_bytes)
    assert used < V5E_HBM_BYTES
    return compiled


def _n_live(one_chip):
    """The live-tile count, the bound of every stage kernel's grid
    (`verify_praos_packed_split` puts it on the device once a window)."""
    return _sds(one_chip, (1,))


def _limb(one_chip, has_nonce=True):
    """The 22 limb-first stage inputs `unpack` hands the crypto stages
    (shapes only — eval_shape of the cheap unpack program)."""
    layout, cols = _packed(has_nonce)
    out = jax.eval_shape(K._mk_packed_unpack(layout), *cols)
    assert len(out) == 22  # batch-compatible layout: announced U, V
    return [_sds(one_chip, s.shape, s.dtype) for s in out]


@pytest.mark.parametrize("has_nonce", [True, False],
                         ids=["epoch-nonce", "neutral-nonce"])
def test_unpack_compiles(one_chip, chip_seams, has_nonce):
    layout, cols = _packed(has_nonce)
    args = [_sds(one_chip, c.shape, c.dtype) for c in map(np.asarray, cols)]
    _compile(K._mk_packed_unpack(layout), args)


def test_reduce_compiles(one_chip, chip_seams):
    """Bit packing and a cast: the compiled program holds no loop (the
    round-6 nonce scan, a `while` of one trip a lane, ran 3.3 s a
    window on the chip)."""
    s = functools.partial(_sds, one_chip)
    compiled = _compile(K.reduce_fn, [s((5, LANES)), s((32, LANES))])
    assert "while" not in compiled.as_text()


def test_finish_compiles_with_the_kernel(one_chip, chip_seams):
    # the lane count fixes finish's argument shapes: no eval_shape
    # through the three heavy kernels (that costs their whole trace)
    s = functools.partial(_sds, one_chip)
    args = [
        s((1, LANES)), s((80, LANES)), s((32, LANES)),  # ed ok, point, R
        s((1, LANES)), s((80, LANES)), s((32, LANES)),  # kes ok, point, R
        s((1, LANES)), s((400, LANES)), s((16, LANES)),  # vrf ok, points, c
        s((64, LANES)), s((32, LANES)), s((32, LANES)),  # beta, thr lo/hi
        _n_live(one_chip),
    ]
    compiled = _compile(K.finish, args)
    assert "tpu_custom_call" in compiled.as_text()


@functools.lru_cache(maxsize=None)
def _packed_tpraos():
    """(layout, unpack-argument arrays padded to LANES) of a real TPraos
    window: two 80-byte certificates a body, half its lanes overlay."""
    from ouroboros_consensus_tpu.protocol import tpraos
    from ouroboros_consensus_tpu.protocol.views import ViewColumns
    from ouroboros_consensus_tpu.tools import db_synthesizer as synth

    pool = fixtures.make_pool(0, kes_depth=KES_DEPTH)
    params, creds, lview = synth.make_tpraos(
        _params(), [pool], fixtures.make_ledger_view([pool]), 7,
        Fraction(1, 2))
    nonce = b"\x07" * 32
    hvs, prev = [], b"\xaa" * 32
    for i in range(8):
        slot = 1000 + i
        a = tpraos.overlay_slot_assignment(params, 7, slot)
        who = creds[1 + a[1]] if a and a[0] else pool
        blk = forge_block(
            params, who, slot=slot, block_no=500 + i, prev_hash=prev,
            epoch_nonce=nonce,
            is_leader=tpraos.prove_certificates(who.vrf_seed, slot, nonce))
        hvs.append(blk.header.to_view())
        prev = blk.header.hash_
    vc = ViewColumns.from_views(hvs)
    pre = tpraos.host_prechecks(params, lview, vc)
    layout, parr = pbatch.stage_packed_columns(params, lview, nonce, vc, pre)
    assert layout.proofs == 2 and parr.thr_tab.shape[1] == 128
    assert 0 < int(parr.overlay.sum()) < 8
    return layout, pbatch.pad_packed_to(parr, LANES)


def test_tpraos_unpack_compiles(one_chip, chip_seams):
    """The TPraos `unpack` layout: 27 limb-first arrays out (the second
    proof's columns, 64-byte threshold rows, the overlay row), of which
    the `vrf` stage's two operand sets have the draft-03 program's
    shapes: one stored executable serves both runs."""
    layout, cols = _packed_tpraos()
    args = [_sds(one_chip, c.shape, c.dtype) for c in map(np.asarray, cols)]
    _compile(K._mk_packed_unpack(layout), args)
    out = jax.eval_shape(K._mk_packed_unpack(layout), *cols)
    assert len(out) == 27
    n_live = jax.ShapeDtypeStruct((1,), np.int32)
    (_, _ed), (_, _kes), (n1, v1), (n2, v2) = K.stage_operands(
        out, n_live, layout.proofs)
    assert n1 == n2 == "vrf"
    assert [(a.shape, a.dtype) for a in v1] == [(a.shape, a.dtype)
                                               for a in v2]
    assert [a.shape for a in v1[:5]] == [
        (32, LANES), (32, LANES), (16, LANES), (32, LANES), (32, LANES)]


def test_finish_tp_compiles_with_the_kernel(one_chip, chip_seams):
    """`finish_tp` for the v5e at the production lane count: twelve
    points in the one inversion, four SHA-512 and one Blake2b a lane,
    the 64-byte compare; under its own kernel name (the trace reads it
    through `^jit_finish`)."""
    s = functools.partial(_sds, one_chip)
    args = [
        s((1, LANES)), s((80, LANES)), s((32, LANES)),  # ed ok, point, R
        s((1, LANES)), s((80, LANES)), s((32, LANES)),  # kes ok, point, R
        s((1, LANES)), s((400, LANES)), s((16, LANES)),  # nonce proof
        s((1, LANES)), s((400, LANES)), s((16, LANES)),  # leader proof
        s((64, LANES)), s((64, LANES)),  # both declared outputs
        s((64, LANES)), s((64, LANES)),  # 512-bit brackets
        s((1, LANES)),  # overlay
        _n_live(one_chip),
    ]
    compiled = _compile(K.finish_tp, args)
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "finish_tp" in text


def test_finish_lowers_with_its_kernel_name(one_chip, chip_seams):
    """What a device trace shows of a Pallas kernel is its `name` in the
    Mosaic custom call (tests/test_span_tree.py holds the module names).
    Lowered only, at one tile of lanes: the kernel body is traced
    whatever the lane count."""

    def fresh(*a):  # no interpret-mode trace of K.finish to reuse
        return K.finish(*a)

    s = functools.partial(_sds, one_chip)
    args = [s((p, K.TILE))
            for p in (1, 80, 32, 1, 80, 32, 1, 400, 16, 64, 32, 32)]
    args.append(_n_live(one_chip))
    text = jax.jit(fresh).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    assert 'kernel_name = "finish"' in text


@functools.lru_cache(maxsize=None)
def _packed_pbft():
    """(layout, unpack-argument arrays padded to LANES) of a real Byron
    window: an EBB, then main headers signed by three delegates."""
    from ouroboros_consensus_tpu.hardfork import byron_mock
    from ouroboros_consensus_tpu.protocol import pbft

    blocks = [byron_mock.forge_ebb(slot=0, block_no=0, prev_hash=None)]
    for i in range(1, 8):
        blocks.append(byron_mock.forge_block(
            bytes([i % 3 + 1]) * 32, slot=i, block_no=i - 1,
            prev_hash=blocks[-1].hash_))
    cols = pbft.ByronColumns.from_headers([b.header for b in blocks])
    layout, parr = pbft.stage_packed(cols)
    assert layout.body_len == byron_mock.BYRON_SIGNED_LEN
    return layout, pbatch.pad_packed_to(parr, LANES)


def test_pbft_unpack_compiles(one_chip, chip_seams):
    """A Byron window's `unpack`: R ‖ A ‖ M in THREE SHA-512 blocks a
    lane (an OCert's message takes one), the `ed` operands limb-first."""
    layout, cols = _packed_pbft()
    args = [_sds(one_chip, c.shape, c.dtype) for c in map(np.asarray, cols)]
    _compile(K._mk_pbft_unpack(layout), args)
    out = jax.eval_shape(K._mk_pbft_unpack(layout), *cols)
    assert [o.shape for o in out] == [(32, LANES)] * 3 + [
        (3, 128, LANES), (1, LANES)]


def test_finish_pbft_compiles_with_the_kernel(one_chip, chip_seams):
    """`finish_pbft`: one point a lane compressed, the R compare, the
    verdict rows `reduce` takes; under a name `^jit_finish` matches."""
    s = functools.partial(_sds, one_chip)
    args = [s((1, LANES)), s((80, LANES)), s((32, LANES)),
            _n_live(one_chip)]
    compiled = _compile(K.finish_pbft, args)
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "finish_pbft" in text


@pytest.mark.slow
def test_pbft_ed_compiles(one_chip, chip_seams):
    """The `ed` program at Byron's width (three hash blocks a lane)."""
    layout, cols = _packed_pbft()
    out = jax.eval_shape(K._mk_pbft_unpack(layout), *cols)
    limb = [_sds(one_chip, o.shape, o.dtype) for o in out]
    compiled = _compile(
        K.ed_points, [limb[0], limb[2], limb[3], limb[4], _n_live(one_chip)])
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.slow
def test_ed_compiles(one_chip, chip_seams):
    limb = _limb(one_chip)
    compiled = _compile(
        K.ed_points,
        [limb[0], limb[2], limb[3], limb[4], _n_live(one_chip)],
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.slow
def test_kes_compiles(one_chip, chip_seams):
    limb = _limb(one_chip)
    compiled = _compile(
        K.kes_points_at(KES_DEPTH),
        [limb[5], limb[6], limb[8], limb[9], limb[10], limb[11], limb[12],
         _n_live(one_chip)],
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.slow
def test_vrf_bc_compiles(one_chip, chip_seams):
    compiled = _compile(
        K.vrf_points_bc, [*_limb(one_chip)[13:19], _n_live(one_chip)]
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.slow
def test_vrf_draft03_compiles(one_chip, chip_seams):
    s = functools.partial(_sds, one_chip)
    args = [s((32, LANES)), s((32, LANES)), s((16, LANES)),
            s((32, LANES)), s((32, LANES)), _n_live(one_chip)]
    compiled = _compile(K.vrf_points, args)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.slow
def test_leader_sweep_compiles(one_chip, chip_seams):
    """The forge's election (ops/pk/elect.py) at the grid a 512-pool
    chain dispatches: 512 pools x 512 slots, 262,144 lanes."""
    from ouroboros_consensus_tpu.ops.pk import elect

    s = functools.partial(_sds, one_chip)
    tab = s((512, 32), np.uint8)
    compiled = _compile(elect.leader_sweep, [tab, tab, tab, tab, tab])
    assert "tpu_custom_call" in compiled.as_text()
