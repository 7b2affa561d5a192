"""Multi-chip SPMD fan-out of the Praos validation hot path.

The reference's hot loop is one OS thread validating one header at a time
(SURVEY.md §2.6 "Sequential hot loop"; ledgerDbPushMany fold,
LedgerDB/Update.hs:302-312). The TPU-native design replaces it with
batch × device data parallelism over a `jax.sharding.Mesh`:

  * every column of the staged `PraosBatch` has leading batch dim B and
    per-lane-independent compute, so the natural sharding is P('batch')
    on axis 0 across all chips (ICI all the way — no host hops);
  * the only cross-device communication is the verdict reduction: a
    `psum` of the per-shard valid counts and a `pmin` of the global
    index of the first failing lane (SURVEY.md §5.8: "collectives only
    appear ... as psum/all_gather over verification verdict bitmaps");
  * the per-header nonce values (eta) stay device-resident sharded and
    are gathered once per batch for the tiny sequential host fold.

This module is exercised on a virtual 8-device CPU mesh in tests and by
the driver's `dryrun_multichip`; on real hardware the same code spans a
TPU pod slice (mesh axis over all chips of the slice).
"""

from __future__ import annotations

from functools import partial

import inspect

import jax
import numpy as np
from jax import numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..protocol import batch as pbatch

# shard_map moved from jax.experimental to the jax top level (and its
# replication-check kwarg was renamed check_rep -> check_vma) across
# the jax versions this repo must run under; resolve both at import
try:
    _shard_map = jax.shard_map
except AttributeError:
    from jax.experimental.shard_map import shard_map as _shard_map
_CHECK_KW = (
    {"check_vma": False}
    if "check_vma" in inspect.signature(_shard_map).parameters
    else {"check_rep": False}
)

BATCH_AXIS = "batch"


def make_mesh(devices=None) -> Mesh:
    """1-D device mesh over the batch axis.

    The validation workload has a single parallel dimension (chain
    position), so the mesh is 1-D; on a multi-host pod slice the same
    axis simply spans all global devices (jax.devices() is global under
    multi-host jax.distributed initialization).
    """
    if devices is None:
        devices = jax.devices()
    return Mesh(np.asarray(devices), (BATCH_AXIS,))


def pad_batch(batch: pbatch.PraosBatch, multiple: int):
    """Pad every column of `batch` to the next POWER-OF-TWO bucket that
    is divisible by `multiple`, returning (padded_batch, original_size).

    Bucketing (same rationale as pbatch.run_batch) keeps the
    jit-of-shard_map cache bounded: one compile per bucket shape, not
    one per epoch-segment length. Pad lanes replicate lane 0
    (guaranteed decodable inputs) — their verdicts are sliced off
    before the host epilogue, and the first-failure reduction masks
    them out by position.
    """
    b = batch.beta.shape[0]
    # floor of 32 lanes: small batches (tests, chain tails) all share
    # ONE compiled shard_map shape; production batches are far larger
    minimum = max(multiple, 32)
    target = pbatch.bucket_size(max(b, minimum), minimum=minimum)
    # power-of-two buckets are only divisible by power-of-two meshes;
    # round up for any other device count
    target += (-target) % multiple
    return pbatch.pad_batch_to(batch, target), b


@partial(jax.jit, static_argnames=("mesh",))
def _sharded_verify(mesh, n_real, *cols):
    """jit-of-shard_map: local fused verify + global verdict collectives.

    The valid-lane count forms on device: each shard bit-packs its ok
    lanes into u32 mask words (pbatch._pack_bits_u32, real positions
    only — `n_real` masks the bucket-pad lanes) and the `psum` of the
    per-shard mask popcounts yields n_ok, so ONE replicated scalar
    crosses the host boundary instead of the [B] ok column. (The mask
    words themselves stay shard-local — the same packed-verdict
    vocabulary as protocol/batch.verdict_reduce, reduced in place.)"""

    def local_step(n_real, *local_cols):
        v = pbatch.verify_praos_any(*local_cols)
        ok = v.ok_ocert_sig & v.ok_kes_sig & v.ok_vrf & (
            v.ok_leader | v.leader_ambiguous
        )
        # global chain positions of this shard's lanes
        shard = jax.lax.axis_index(BATCH_AXIS)
        n_local = ok.shape[0]
        pos = shard * n_local + jnp.arange(n_local, dtype=jnp.int32)
        big = jnp.iinfo(jnp.int32).max
        local_first_bad = jnp.min(jnp.where(ok, big, pos))
        first_bad = jax.lax.pmin(local_first_bad, BATCH_AXIS)
        words = pbatch._pack_bits_u32(ok & (pos < n_real))
        n_ok = jax.lax.psum(
            jnp.sum(jax.lax.population_count(words)).astype(jnp.int32),
            BATCH_AXIS,
        )
        return v, first_bad, n_ok

    spec = P(BATCH_AXIS)
    out = _shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(),) + tuple(spec for _ in cols),
        out_specs=(
            pbatch.Verdicts(*(spec,) * 7),
            P(),  # first_bad: replicated scalar
            P(),  # n_ok: psum over packed-mask popcounts, replicated
        ),
        **_CHECK_KW,
    )(n_real, *cols)
    return out


def sharded_stage_run(
    params, lview, eta0, hvs, pre, mesh: Mesh | None = None
):
    """The sharded entry of `protocol.batch.validate_batch`: stage the
    window — COLUMNAR when a ViewColumns window arrives (stage_columns:
    whole-matrix slices, one vectorized SHA pad per hash family, no
    per-header objects), per-view otherwise — then shard and verify over
    the mesh. Returns `sharded_run_batch`'s (Verdicts, first_bad, n_ok)."""
    batch = pbatch.stage_any(params, lview, eta0, hvs, pre)
    return sharded_run_batch(  # octflow: disable=FLOW304 — reached from
        # `validate_batch` through `batch.PraosRules.run_sharded`
        batch, mesh)


# process-wide sharded-dispatch sequence (the ShardSpan `index`); only
# advanced while a tracer is installed — same contract as the window
# sequence in protocol/batch
_SHARD_SEQ = 0


def _emit_shard_spans(n_dev: int, v: "pbatch.Verdicts", b: int,
                      wall_s: float) -> None:
    """Per-shard WindowSpan analogue through BATCH_TRACER: shard id,
    lanes carried, popcount-vocabulary ok counts, bucket-pad waste.
    Host-side numpy over the already-materialized padded verdict
    columns — emits nothing (and costs one None check) untraced, so
    the SPMD hot path stays telemetry-free by default."""
    global _SHARD_SEQ
    if pbatch.BATCH_TRACER is None:
        return
    from ..utils.trace import ShardSpan

    idx = _SHARD_SEQ
    _SHARD_SEQ += 1
    ok = (
        np.asarray(v.ok_ocert_sig) & np.asarray(v.ok_kes_sig)
        & np.asarray(v.ok_vrf)
        & (np.asarray(v.ok_leader) | np.asarray(v.leader_ambiguous))
    )
    lanes = ok.shape[0] // n_dev  # pad_batch guarantees divisibility
    for s in range(n_dev):
        start = s * lanes
        real = int(min(max(b - start, 0), lanes))
        n_ok = int(np.count_nonzero(ok[start:start + real]))
        pbatch.BATCH_TRACER(ShardSpan(
            index=idx, shard=s, lanes=lanes, lanes_real=real,
            n_ok=n_ok, pad_lanes=lanes - real, wall_s=wall_s,
        ))


def sharded_run_batch(batch: pbatch.PraosBatch, mesh: Mesh | None = None):
    """Device-parallel `protocol.batch.run_batch`: shard the staged batch
    over the mesh, verify, reduce verdicts with collectives.

    Returns (Verdicts as host numpy sliced to the true batch size,
    first_bad_index or None, n_ok) — drop-in for the sequential epilogue
    in `validate_batch`. With a batch tracer installed (OCT_TRACE /
    obs.install), each dispatch additionally emits one ShardSpan per
    mesh position — the per-shard telemetry MULTICHIP rounds bank
    through the same recorder/ledger machinery as bench."""
    import time

    from ..testing import chaos

    # chaos seam (device-error@shard:N): a shard-level device failure
    # at the N-th sharded dispatch — the supervisor's "sharded" ladder
    # (retry -> xla-twin -> host reference) absorbs it in tier-1
    chaos.fire("shard")

    if mesh is None:
        mesh = make_mesh()
    n_dev = mesh.devices.size
    padded, b = pad_batch(batch, n_dev)
    cols = [
        jax.device_put(
            np.asarray(c), NamedSharding(mesh, P(BATCH_AXIS))
        )
        for c in pbatch.flatten_batch(padded)
    ]
    t0 = time.monotonic()
    v, first_bad, n_ok = _sharded_verify(mesh, jnp.int32(b), *cols)
    vp = pbatch.Verdicts(*(np.asarray(x) for x in v))  # materialize (wait)
    wall = time.monotonic() - t0
    _emit_shard_spans(n_dev, vp, b, wall)
    v = pbatch.Verdicts(*(x[:b] for x in vp))
    fb = int(first_bad)
    return v, (fb if fb < b else None), int(n_ok)
