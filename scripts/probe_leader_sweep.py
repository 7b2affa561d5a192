#!/usr/bin/env python3
"""The forge's leader-value sweep (ops/pk/elect.py, forge.LeaderSweep) on
the chip: what its program takes to build, how many pairs a second it
elects, and whether it elects what the host prover elects.

    python3 scripts/probe_leader_sweep.py [--pools 512] [--slots 4096]

Prints one JSON line; exit 1 if a sampled pair differs from the host's.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time
from fractions import Fraction

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pools", type=int, default=512)
    ap.add_argument("--slots", type=int, default=4096)
    ap.add_argument("--seed", type=int, default=2147500001)
    a = ap.parse_args()

    import jax

    from ouroboros_consensus_tpu import compile_cache
    from ouroboros_consensus_tpu.ops.host import fast
    from ouroboros_consensus_tpu.protocol import forge, nonces, praos
    from ouroboros_consensus_tpu.protocol.leader import check_leader_value
    from ouroboros_consensus_tpu.testing import fixtures

    cache = compile_cache.configure()
    os.environ.setdefault("OCT_PK_AOT_WRITEBACK", "1")
    os.environ.setdefault("OCT_PK_AOT_DIR", os.path.join(cache, "oct_pk_aot"))
    dev = jax.devices()[0]
    params = praos.PraosParams(
        slots_per_kes_period=3600, max_kes_evolutions=62,
        security_param=2160, active_slot_coeff=Fraction(1, 2),
        epoch_length=43200, kes_depth=7)
    t0 = time.monotonic()
    pools = [fixtures.make_pool(a.seed + i, kes_depth=7)
             for i in range(a.pools)]
    stakes = fixtures.capped_zipf_stakes(a.pools)
    lview = fixtures.make_ledger_view(pools, stakes)
    t_pools = time.monotonic() - t0
    forge.LEADER_SWEEP = True
    sweep = forge.LeaderSweep(params, pools)
    thr = forge.pool_thresholds(params, lview, pools)
    out = {"device": dev.device_kind, "platform": dev.platform,
           "pools": a.pools, "pools_s": round(t_pools, 2),
           "slots_per_dispatch": sweep.n_slots}
    bad = []
    for label, eta0 in (("neutral", None), ("nonce", b"\x5a" * 32)):
        t0 = time.monotonic()
        rows, first_s = [], None
        for chunk, part in sweep.rows(thr, range(1000, 1000 + a.slots), eta0):
            if first_s is None:
                first_s = time.monotonic() - t0
                t1 = time.monotonic()
            rows.extend(part)
        rest = time.monotonic() - t1
        pairs_rest = (a.slots - sweep.n_slots) * a.pools
        out[label] = {
            "first_dispatch_s": round(first_s, 2),
            "rest_s": round(rest, 3), "winners": len(rows),
            "pairs_per_s": round(pairs_rest / rest) if rest > 0 else None}
        # the host's word on a sample: every claimed winner wins, no
        # earlier pool of its slot does, and a slot with no winner has none
        rng = random.Random(a.seed)
        won = dict(rows)
        f = params.active_slot_coeff

        def wins(s, i):
            beta = fast.ecvrf_proof_to_hash(fast.ecvrf_prove(
                pools[i].vrf_seed, nonces.mk_input_vrf(s, eta0)))
            return check_leader_value(nonces.vrf_leader_value(beta),
                                      stakes[i], f)

        for s, i in rows:
            if not wins(s, i):
                bad.append([label, s, i, "claimed winner loses"])
        for s in rng.sample(range(1000, 1000 + a.slots), 12):
            upto = won.get(s, a.pools)
            for i in range(upto):
                if wins(s, i):
                    bad.append([label, s, i, "earlier pool wins"])
        out[label]["winner_share"] = round(len(rows) / a.slots, 4)
    out["programs"] = forge.SWEEP_PROGRAMS
    out["bad"] = bad[:16]
    print(json.dumps(out), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
