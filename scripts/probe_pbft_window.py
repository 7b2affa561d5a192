#!/usr/bin/env python3
"""One Byron window on the chip: the `unpack` / `ed` (three SHA-512 blocks
a lane) / `finish_pbft` / `reduce` programs of protocol/pbft built and
run on a production-width window, its verdicts against the native
verifier's, and the window's device time.

    python3 scripts/probe_pbft_window.py [--lanes 8192] [--runs 5]

The window: an EBB, then main headers signed in turn by seven delegates
(hardfork/byron_mock), with a bad signature, a key outside the
delegation map and the EBB among them. Prints one JSON line; exit 1 if
a verdict or the fold's first error differs from the host's.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--lanes", type=int, default=8192)
    ap.add_argument("--runs", type=int, default=5)
    ap.add_argument("--seed", type=int, default=2147500011)
    a = ap.parse_args()

    import jax
    import numpy as np

    from ouroboros_consensus_tpu import compile_cache, native_loader
    from ouroboros_consensus_tpu.hardfork import byron_mock, cardano
    from ouroboros_consensus_tpu.ops.host import fast
    from ouroboros_consensus_tpu.protocol import batch as pbatch
    from ouroboros_consensus_tpu.protocol import pbft
    from ouroboros_consensus_tpu.protocol.abstract import ConsensusError
    from ouroboros_consensus_tpu.protocol.instances import PBftState

    compile_cache.configure()
    params, creds, views = cardano.deployment(a.seed)
    delegs = creds[1:]
    t0 = time.monotonic()
    blocks = [byron_mock.forge_ebb(slot=0, block_no=0, prev_hash=None)]
    for s in range(1, a.lanes):
        j = s % len(delegs)
        blocks.append(byron_mock.forge_block(
            delegs[j].cold_seed, slot=s, block_no=s - 1,
            prev_hash=blocks[-1].hash_, sign=fast.ed25519_sign))
    headers = [b.header for b in blocks]
    bad_sig, stranger = a.lanes * 3 // 4, a.lanes - 2
    h = headers[bad_sig]
    headers[bad_sig] = byron_mock.ByronMockHeader(
        h.block_no, h.slot, h.prev_hash, h.issuer_vk, h.body_hash,
        h.sig[:40] + bytes([h.sig[40] ^ 1]) + h.sig[41:])
    other = byron_mock.forge_block(b"\x99" * 32, slot=stranger,
                                   block_no=stranger - 1,
                                   prev_hash=headers[stranger - 1].hash_,
                                   sign=fast.ed25519_sign)
    headers[stranger] = other.header
    cols = pbft.ByronColumns.from_headers(headers)
    forge_s = time.monotonic() - t0

    lview = views.byron
    want_ok = np.array([
        h.is_ebb or native_loader.native_ed25519_verify(
            h.issuer_vk, h.sig, h.signed_bytes) for h in headers])
    # the sequential rule (PBftProtocol.apply_checked_sig, header by
    # header) over the native verifier's verdicts
    proto = pbft.PBFT_RULES.protocol_of(params.byron, lview)
    st, want = PBftState(), None
    for i, h in enumerate(headers):
        if h.is_ebb:
            continue
        try:
            st = proto.apply_checked_sig(st, h.slot, h.issuer_vk,
                                         bool(want_ok[i]), lview.delegates)
        except ConsensusError as e:  # the rule's error
            want = (st, i, e)
            break
    want = want or (st, len(headers), None)

    lanes = pbatch.window_lanes(a.lanes)
    runs = []
    got = None
    for r in range(a.runs):
        t = time.monotonic()
        sw = pbatch.prepare_window(params.byron, lview, None, cols, lanes)
        t_stage = time.monotonic() - t
        pre, out, b = pbatch.dispatch_prepared(sw)
        v = pbatch.materialize_verdicts(out, b)
        t_dev = time.monotonic() - t
        res = pbft.epilogue(params.byron, pbft.PBFT_RULES.tick(
            params.byron, lview, 0, PBftState()), cols, pre, v)
        runs.append({"stage_s": round(t_stage, 4),
                     "to_verdicts_s": round(t_dev, 4),
                     "all_s": round(time.monotonic() - t, 4)})
        got = (pbatch._mask_bits(v.masks[0], b), res)
    ok_dev, res = got
    main = cols.main
    verdicts_agree = bool((ok_dev[main] == want_ok[main]).all())
    fold_agree = (res.n_valid == want[1] and res.error == want[2]
                  and res.state == want[0])
    doc = {
        "device": str(jax.devices()[0]), "impl": pbatch._impl(),
        "lanes": lanes, "headers": len(headers), "forge_s": round(forge_s, 2),
        "signed_bytes": int(cols.signed.shape[1]),
        "verdicts_agree": verdicts_agree, "fold_agree": fold_agree,
        "n_valid": [res.n_valid, want[1]],
        "error": [repr(res.error), repr(want[2])],
        "bad_lanes_refused": [bool(not ok_dev[bad_sig])],
        "runs": runs,
    }
    print(json.dumps(doc), flush=True)
    return 0 if verdicts_agree and fold_agree else 1


if __name__ == "__main__":
    sys.exit(main())
