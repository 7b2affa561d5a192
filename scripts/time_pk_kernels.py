"""Per-kernel compile + hot timing of the pk pipeline at a fixed batch,
then the full differential check vs the native verifier. One process."""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from fractions import Fraction

import numpy as np
import jax
from jax import numpy as jnp

from ouroboros_consensus_tpu.ops.pk import kernels as K
from ouroboros_consensus_tpu.protocol import batch as pbatch
from ouroboros_consensus_tpu.protocol import praos
from ouroboros_consensus_tpu.testing import fixtures

B = int(sys.argv[1]) if len(sys.argv) > 1 else 4096
NSRC = 128
DEPTH = 3

PARAMS = praos.PraosParams(
    slots_per_kes_period=100, max_kes_evolutions=62, security_param=4,
    active_slot_coeff=Fraction(1, 2), epoch_length=100_000, kes_depth=DEPTH,
)
ETA0 = b"\x07" * 32

pools = [fixtures.make_pool(i, kes_depth=DEPTH) for i in range(3)]
lview = fixtures.make_ledger_view(pools)

t0 = time.time()
hvs, slot, prev = [], 1, None
while len(hvs) < NSRC:
    pool = fixtures.find_leader(PARAMS, pools, lview, slot, ETA0)
    if pool is not None:
        hvs.append(fixtures.forge_header_view(
            PARAMS, pool, slot=slot, epoch_nonce=ETA0, prev_hash=prev,
            body_bytes=b"body-%d" % len(hvs)))
        prev = (b"%032d" % len(hvs))[:32]
    slot += 1
print(f"forged {NSRC} in {time.time()-t0:.1f}s", flush=True)

import dataclasses
hvs[10] = dataclasses.replace(hvs[10], ocert=dataclasses.replace(
    hvs[10].ocert, sigma=hvs[10].ocert.sigma[:-1] + bytes([hvs[10].ocert.sigma[-1] ^ 1])))
hvs[20] = dataclasses.replace(hvs[20], kes_sig=hvs[20].kes_sig[:-1] + bytes([hvs[20].kes_sig[-1] ^ 1]))
hvs[30] = dataclasses.replace(hvs[30], vrf_proof=hvs[30].vrf_proof[:1] + bytes([hvs[30].vrf_proof[1] ^ 1]) + hvs[30].vrf_proof[2:])
hvs[40] = dataclasses.replace(hvs[40], vrf_output=hvs[40].vrf_output[:1] + bytes([hvs[40].vrf_output[1] ^ 1]) + hvs[40].vrf_output[2:])

pre = pbatch.host_prechecks(PARAMS, lview, hvs)
staged = pbatch.stage(PARAMS, lview, ETA0, hvs, pre.kes_evolution)
reps = (B + NSRC - 1) // NSRC
big = pbatch.PraosBatch(
    ed=type(staged.ed)(*(np.concatenate([np.asarray(c)] * reps)[:B] for c in staged.ed)),
    kes=type(staged.kes)(*(np.concatenate([np.asarray(c)] * reps)[:B] for c in staged.kes)),
    vrf=type(staged.vrf)(*(np.concatenate([np.asarray(c)] * reps)[:B] for c in staged.vrf)),
    beta=np.concatenate([staged.beta] * reps)[:B],
    thr_lo=np.concatenate([staged.thr_lo] * reps)[:B],
    thr_hi=np.concatenate([staged.thr_hi] * reps)[:B],
)
arrays = [jnp.asarray(x) for x in pbatch.pk_arrays(big)]
(ed_pk, ed_r, ed_s, ed_hb, ed_hnb, kes_vk, kes_per, kes_r, kes_s, kes_leaf,
 kes_sib, kes_hb, kes_hnb, vrf_pk, vrf_g, vrf_c, vrf_s, vrf_al,
 beta, tlo, thi) = arrays


def timed(name, fn, *a):
    t0 = time.time()
    out = fn(*a)
    jax.tree.map(np.asarray, out)
    compile_s = time.time() - t0
    t0 = time.time()
    n = 3
    for _ in range(n):
        out = fn(*a)
    jax.tree.map(np.asarray, out)
    hot = (time.time() - t0) / n
    print(f"{name:8s} compile+run {compile_s:7.1f}s   hot {hot*1e3:8.1f}ms "
          f"({B/hot:8.0f} lanes/s)", flush=True)
    return out


ed_j = jax.jit(K.ed_points)
kes_j = jax.jit(lambda *a: K.kes_points(*a, depth=DEPTH))
vrf_j = jax.jit(K.vrf_points)
fin_j = jax.jit(K.finish)

n_live = jax.device_put(K.all_tiles(B))  # every tile: a full window
ed_ok, ed_pt = timed("ed", ed_j, ed_pk, ed_s, ed_hb, ed_hnb, n_live)
kes_ok, kes_pt = timed("kes", kes_j, kes_vk, kes_per, kes_s, kes_leaf, kes_sib, kes_hb, kes_hnb, n_live)
vrf_ok, vrf_pts = timed("vrf", vrf_j, vrf_pk, vrf_g, vrf_c, vrf_s, vrf_al, n_live)
fin = timed("finish", fin_j, ed_ok, ed_pt, ed_r, kes_ok, kes_pt, kes_r,
            vrf_ok, vrf_pts, vrf_c, beta, tlo, thi, n_live)

# whole pipeline hot (one dispatch)
full_j = jax.jit(lambda *a: K.verify_praos_tiles(*a, kes_depth=DEPTH))
t0 = time.time()
out = full_j(*arrays)
jax.tree.map(np.asarray, out)
print(f"full pipeline first: {time.time()-t0:.1f}s", flush=True)
best = 1e9
for _ in range(3):
    t0 = time.time()
    out = full_j(*arrays)
    jax.tree.map(np.asarray, out)
    best = min(best, time.time() - t0)
print(f"full pipeline hot: {best*1e3:.1f}ms -> {B/best:.0f} headers/s", flush=True)

# differential vs native on the first NSRC lanes
v = pbatch._pk_materialize(out, B)
vn = pbatch.run_batch_native(PARAMS, lview, ETA0, hvs, pre)
mism = []
for i in range(11):  # up to + including first corrupt lane
    for f_ in ("ok_ocert_sig", "ok_kes_sig", "ok_vrf"):
        if bool(getattr(v, f_)[i]) != bool(getattr(vn, f_)[i]):
            mism.append((i, f_))
fails = {i for i in range(NSRC)
         if not (v.ok_ocert_sig[i] and v.ok_kes_sig[i] and v.ok_vrf[i])}
print("mismatch vs native:", mism or "none")
print("failing lanes (want {10,20,30,40}):", sorted(fails))
print("eta match:", bool((v.eta[:9] == vn.eta[:9]).all()),
      "lv match:", bool((v.leader_value[:9] == vn.leader_value[:9]).all()))
ok10 = not v.ok_ocert_sig[10] and not v.ok_kes_sig[20] and not v.ok_vrf[30] and not v.ok_vrf[40]
print("corruption kinds:", "OK" if ok10 else "WRONG")
