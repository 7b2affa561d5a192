"""Child process for test_pk_smoke: composed 4-stage pk verification at
a pinned tiny shape (B=8, KES depth 1, unrolled hash cores — the TPU
code path through ops/pk/verify), cross-checked lane-for-lane against
the native verifier. Run in a subprocess so OCT_PK_HASH_IMPL is set
before any ops module is imported.

The composed core runs EAGERLY (jax.disable_jit): XLA:CPU's compile of
the composed graph is pathological on a cold cache (>30 min on a 1-core
box), while eager dispatch is ~4 min deterministically with no cache
dependence. Exits 0 on agreement.
"""

import dataclasses
import os
import sys
from fractions import Fraction

os.environ["OCT_PK_HASH_IMPL"] = "unrolled"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from ouroboros_consensus_tpu import compile_cache

compile_cache.configure()

import numpy as np
from jax import numpy as jnp

from ouroboros_consensus_tpu.ops.pk import verify as pv
from ouroboros_consensus_tpu.protocol import batch as pbatch
from ouroboros_consensus_tpu.protocol import praos
from ouroboros_consensus_tpu.testing import fixtures

PARAMS = praos.PraosParams(
    slots_per_kes_period=100,
    max_kes_evolutions=2,
    security_param=4,
    active_slot_coeff=Fraction(1, 2),
    epoch_length=100_000,
    kes_depth=1,
)
ETA0 = b"\x07" * 32
B = 8


def main() -> int:
    pools = [fixtures.make_pool(i, kes_depth=1) for i in range(2)]
    lview = fixtures.make_ledger_view(pools)
    hvs, slot, prev = [], 1, None
    while len(hvs) < B:
        pool = fixtures.find_leader(PARAMS, pools, lview, slot, ETA0)
        if pool is not None:
            hvs.append(
                fixtures.forge_header_view(
                    PARAMS, pool, slot=slot, epoch_nonce=ETA0,
                    prev_hash=prev, body_bytes=b"b%d" % len(hvs),
                )
            )
            prev = (b"%032d" % len(hvs))[:32]
        slot += 1
    # one corruption per verifier leg
    hvs[2] = dataclasses.replace(
        hvs[2],
        ocert=dataclasses.replace(
            hvs[2].ocert,
            sigma=hvs[2].ocert.sigma[:-1] + bytes([hvs[2].ocert.sigma[-1] ^ 1]),
        ),
    )
    hvs[4] = dataclasses.replace(
        hvs[4], kes_sig=hvs[4].kes_sig[:-1] + bytes([hvs[4].kes_sig[-1] ^ 1])
    )
    hvs[6] = dataclasses.replace(
        hvs[6],
        vrf_proof=hvs[6].vrf_proof[:1]
        + bytes([hvs[6].vrf_proof[1] ^ 1])
        + hvs[6].vrf_proof[2:],
    )
    pre = pbatch.host_prechecks(PARAMS, lview, hvs)
    batch = pbatch.stage(PARAMS, lview, ETA0, hvs, pre.kes_evolution)
    arrays = [jnp.asarray(x) for x in pbatch.pk_arrays(batch)]

    def f(*a):
        (ed_pk, ed_r, ed_s, ed_hb, ed_hnb, kes_vk, kes_per, kes_r, kes_s,
         kes_leaf, kes_sib, kes_hb, kes_hnb, vrf_pk, vrf_g, vrf_c, vrf_s,
         vrf_al, beta, tlo, thi) = a
        return pv.verify_praos_core(
            ed_pk, ed_r, ed_s, ed_hb, ed_hnb[0],
            kes_vk, kes_per[0], kes_r, kes_s, kes_leaf, kes_sib,
            kes_hb, kes_hnb[0],
            vrf_pk, vrf_g, vrf_c, vrf_s, vrf_al,
            beta, tlo, thi, kes_depth=1,
        )

    # EAGER, not jitted: the composed graph's XLA:CPU compile is
    # pathological on a cold cache (>30 min measured on the 1-core CI
    # box — the algebraic-simplifier blowup, PERF.md r4/r5), while
    # eager op dispatch of the same graph is ~4 min deterministically,
    # every run, with no cache dependence. The smoke certifies the
    # composed SEMANTICS lane-for-lane; compiled-path coverage lives in
    # the OCT_SLOW tier and the on-hardware scripts.
    with jax.disable_jit():
        v = jax.tree.map(np.asarray, f(*arrays))
    fields = ("ok_ocert_sig", "ok_kes_sig", "ok_vrf", "ok_leader")
    mism = []
    for i in range(B):
        # native verifier one lane at a time (it short-circuits at the
        # first failing lane, so batch-level lane-for-lane is invalid)
        pre_i = pbatch.HostChecks(
            pre.kes_window_errors[i : i + 1],
            pre.vrf_lookup_errors[i : i + 1],
            pre.kes_evolution[i : i + 1],
        )
        vn = pbatch.run_batch_native(PARAMS, lview, ETA0, hvs[i : i + 1], pre_i)
        sigs_ok = all(
            bool(getattr(vn, f)[0])
            for f in ("ok_ocert_sig", "ok_kes_sig", "ok_vrf")
        )
        for fname in fields:
            if fname == "ok_leader" and not sigs_ok:
                # the native verifier short-circuits: leadership is not
                # evaluated after a failed signature leg (always False
                # there), while the batched core computes legs
                # independently — the composed verdict is identical
                # because _lane_error applies reference order
                continue
            got = bool(np.asarray(getattr(v, fname))[..., i].reshape(-1)[0])
            want = bool(getattr(vn, fname)[0])
            if got != want:
                mism.append((i, fname, got, want))
        if not mism:
            # eta (nonce contribution) must agree bit-for-bit on fully
            # valid lanes — it feeds the evolving-nonce fold. Gate on
            # sigs_ok, not ok_vrf alone: the native verifier
            # short-circuits inside a lane, so ok_vrf/eta are don't-care
            # once an earlier leg failed
            if sigs_ok and bool(vn.ok_vrf[0]):
                dev_eta = np.asarray(v.eta)[..., i].reshape(-1)
                nat_eta = np.asarray(vn.eta[0]).reshape(-1)
                if not np.array_equal(dev_eta, nat_eta):
                    mism.append((i, "eta", None, None))
    # the three corruptions must actually be caught by the composed core
    caught = (
        not bool(np.asarray(v.ok_ocert_sig).reshape(-1)[2])
        and not bool(np.asarray(v.ok_kes_sig).reshape(-1)[4])
        and not bool(np.asarray(v.ok_vrf).reshape(-1)[6])
    )
    if mism or not caught:
        print(f"MISMATCH lanes={mism} corruptions_caught={caught}")
        return 1
    print("composed pk smoke OK (8 lanes, depth-1, unrolled hashes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
