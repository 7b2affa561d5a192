"""db-synthesizer: forge a synthetic Praos (or TPraos) chain as fast as
possible.

Reference: `Cardano.Tools.DBSynthesizer` — the `runForge` loop
(Tools/DBSynthesizer/Forging.hs:54-57 "mirrors the forging loop from
NodeKernel") minus clock and network: per slot, check leadership for every
credential, forge and append the winner's block directly to the
ImmutableDB, threading the protocol state with `reupdate` (the trusted,
crypto-free path — we produced the signatures ourselves).

Limits mirror the reference's `ForgeLimit` (Types.hs): slot count, block
count, or epoch count.

A `TPraosParams` (with a `TPraosLedgerView` and the genesis delegates'
credentials among `pools`) forges the Shelley-era protocol: two VRF
certificates a block, an active overlay slot's block its delegate's, an
inactive one left empty, the lottery elsewhere.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from fractions import Fraction

from ..block.forge import evaluate_vrf, forge_block
from ..protocol import batch as pbatch
from ..protocol import nonces, tpraos
from ..protocol.leader import check_leader_value
from ..protocol.praos import PraosParams, PraosState
from ..protocol.views import LedgerView
from ..storage.immutable import ImmutableDB
from ..testing import fixtures


@dataclass(frozen=True)
class ForgeLimit:
    """Stop condition (exactly one should be set). Types.hs ForgeLimit."""

    slots: int | None = None
    blocks: int | None = None
    epochs: int | None = None


@dataclass
class ForgeResult:
    """Counters the reference prints at the end of a run."""

    n_slots: int = 0
    n_blocks: int = 0
    wall_s: float = 0.0
    final_state: PraosState | None = None


def default_params(kes_depth: int = 7) -> PraosParams:
    """Benchmark-chain parameters: mainnet-shaped ratios scaled down so
    a synthetic chain crosses epochs (stability windows stay non-trivial)."""
    return PraosParams(
        slots_per_kes_period=3600,
        max_kes_evolutions=62,
        security_param=108,
        active_slot_coeff=Fraction(1, 2),
        epoch_length=4320,
        kes_depth=kes_depth,
    )


def make_credentials(n_pools: int, kes_depth: int = 7):
    pools = [fixtures.make_pool(i, kes_depth=kes_depth) for i in range(n_pools)]
    return pools, fixtures.make_ledger_view(pools)


def make_tpraos(params: PraosParams, pools, lview, n_delegs: int,
                d: Fraction, first_seed: int = 1000):
    """A TPraos deployment over a Praos one: (TPraosParams, the pools'
    credentials followed by the genesis delegates', TPraosLedgerView).
    Delegate j is `fixtures.make_pool(first_seed + j)`."""
    from ..protocol.views import hash_vrf_vk

    delegs = [fixtures.make_pool(first_seed + j, kes_depth=params.kes_depth)
              for j in range(n_delegs)]
    view = tpraos.TPraosLedgerView(
        pool_distr=lview.pool_distr,
        gen_delegs=tuple(
            tpraos.GenDeleg(g.vk_cold, hash_vrf_vk(g.vrf_vk)) for g in delegs
        ),
    )
    return tpraos.TPraosParams(params, Fraction(d)), [*pools, *delegs], view


_VRF_BUCKET = 4096


def _prove_span(pools, slots, eta0):
    """Batched device VRF evaluation for every (slot, pool) pair of a
    span. Returns {(slot, pool_index): PraosIsLeader}. The VRF is the
    only per-header forging cost with no chain dependency (alpha =
    InputVRF(slot, eta0), Praos/VRF.hs:47), so it batches across the
    whole span on device; header assembly + KES signing stay sequential
    because each body embeds the previous header's hash (signature
    included).
    """
    from ..protocol.praos import PraosIsLeader

    from ..ops import ecvrf_batch

    pairs = [(s, i) for s in slots for i in range(len(pools))]
    out = {}
    for lo in range(0, len(pairs), _VRF_BUCKET):
        part = pairs[lo : lo + _VRF_BUCKET]
        seeds = [pools[i].vrf_seed for _s, i in part]
        alphas = [nonces.mk_input_vrf(s, eta0) for s, _i in part]
        # pad to the bucket so the jit caches exactly one shape
        pad = _VRF_BUCKET - len(part)
        if pad:
            seeds.extend([seeds[0]] * pad)
            alphas.extend([alphas[0]] * pad)
        proofs, betas = ecvrf_batch.prove_batch(seeds, alphas)
        for (s, i), proof, beta in zip(part, proofs, betas):
            out[(s, i)] = PraosIsLeader(beta.tobytes(), proof.tobytes())
    return out


def synthesize(
    db_path: str,
    params: PraosParams,
    pools: list[fixtures.PoolCredentials],
    lview: LedgerView,
    limit: ForgeLimit,
    txs_per_block: int = 0,
    chunk_size: int = 21600,
    vrf_backend: str = "auto",
    trace=lambda s: None,
    ledger_view_for_epoch=None,  # epoch -> LedgerView (epoch-varying
    # stake: forge against the distribution validators will derive);
    # None = the constant `lview`
    txs_for_block=None,  # (slot, block_no) -> tuple[bytes, ...]
    ledger=None,  # LEDGER IN THE LOOP: fold this ledger (view_for_epoch
    genesis_state=None,  # + tick_then_apply) over the forged blocks and
    # derive each epoch's election view from ITS stake snapshots — the
    # forging twin of db_analyser's ledger-derived revalidation (so
    # Shelley-backed chains synthesize at tool level)
    resume: bool = False,  # continue forging into a NON-empty DB: the
    # store is reopened dirty-aware (deep revalidation + repair when
    # the last writer crashed), the protocol state rebuilt by
    # replaying the surviving chain with the trusted reupdate path,
    # and forging continues from the tip — forging is deterministic,
    # so a killed-and-resumed synthesis converges on the byte-
    # identical chain an uninterrupted run produces
    network_magic: int | None = None,  # chain magic for the DB marker
    elector=None,  # (slots: range, eta0) -> [(slot, pool index), ...]:
    # the election made elsewhere (each slot's first winning pool in
    # list order, as protocol/forge.LeaderSweep.rows gives them), e.g.
    # by the process that holds the chip; the winners are proved and
    # the blocks assembled here
) -> ForgeResult:
    """The forging loop (Forging.hs:57): tick → leader check per
    credential → forge → append, until the limit trips.

    The writer speaks the store crash protocol (storage/guard.py): DB
    lock held for the whole forge, chain-magic marker written on
    first open, clean-shutdown marker absent while forging and written
    back after the final flush — a killed synthesis leaves a DIRTY
    store whose next open deep-revalidates and repairs.

    vrf_backend: "device" evaluates VRFs in epoch-span batches on the
    accelerator; "host" per-slot on the CPU; "auto" picks device when
    the run is big enough to amortize the kernel compile."""
    from ..storage import guard as _guard_mod
    from ..storage.open import open_repair_store

    if resume and ledger is not None:
        raise ValueError(
            "resume is not supported in ledger mode (the ledger fold "
            "has its own snapshot/replay machinery)"
        )
    if elector is not None and ledger is not None:
        raise ValueError(
            "an elector needs the view to elect against before the "
            "blocks exist: ledger mode derives it from them"
        )
    os.makedirs(db_path, exist_ok=True)
    # open as a READER first: the non-empty-DB refusal below must be
    # side-effect-free (an operator mistake may not dirty a healthy
    # store); promote_writer() adopts the writer protocol only once we
    # have committed to mutating
    guard = _guard_mod.StoreGuard(
        db_path, network_magic=network_magic, writer=False
    )
    guard.open()
    try:
        if resume:
            # a resume is committed to writing: adopt the writer
            # protocol up front so any tail repair the open computes
            # happens under the writer guard (never a reader's)
            guard.promote_writer()
            if guard.opened_dirty:
                # the previous writer crashed: reopen with the full
                # ValidateAllChunks + repair scan (torn tails truncated
                # + quarantined, lagging indices rebuilt) before
                # trusting the tip
                imm = open_repair_store(db_path, chunk_size=chunk_size)
            else:
                imm = ImmutableDB(
                    os.path.join(db_path, "immutable"),
                    chunk_size=chunk_size,
                )
        else:
            # repair=False: this probe happens under the READER guard —
            # the non-empty refusal below must be side-effect-free (an
            # operator mistake may not touch somebody else's dirty tail)
            imm = ImmutableDB(
                os.path.join(db_path, "immutable"), chunk_size=chunk_size,
                repair=False,
            )
            if not imm.is_empty:
                raise RuntimeError(
                    f"refusing to forge into non-empty DB at {db_path} "
                    "(pass resume=True to continue a crashed synthesis)"
                )
            if imm.repairs:
                # "empty" came out of a read-only scan that COMPUTED
                # repairs (e.g. a wholly-torn first chunk reparsed to
                # zero entries): forging here would append after
                # un-truncated garbage
                raise RuntimeError(
                    f"refusing to forge into corrupted store at "
                    f"{db_path} (pass resume=True to repair and "
                    "continue, or run db_truncater --to-last-valid)"
                )
            guard.promote_writer()
            imm.prepare_write()  # the probe was read-only by design
        out = _synthesize_locked(
            imm, db_path, params, pools, lview, limit, txs_per_block,
            vrf_backend, trace, ledger_view_for_epoch, txs_for_block,
            ledger, genesis_state, elector,
        )
    except BaseException:
        # a killed/raising forge leaves DIRTY; the pre-writer refusal
        # path releases the lock without having touched any marker
        guard.close(clean=False)
        raise
    guard.close(clean=True)
    return out


# trusted-fold memo: a resume whose deep-open confirms the tip this
# process itself forged skips the whole-chain reupdate replay. Keyed by
# the store path; the (tip slot, tip hash) check makes a stale entry —
# another writer, an external truncation — fall through to the replay.
# The stored tuple is EXACTLY what _replay_forged_state would return.
_REPLAY_MEMO: dict[str, tuple] = {}


def _replay_forged_state(params, lview, imm):
    """Rebuild the forging state from a surviving chain: the trusted
    reupdate fold (we forged these signatures ourselves — exactly the
    reference's crypto-free path; tick/reupdate never read the stake
    distribution, so the constant view serves every epoch). Yields the
    PraosState at the tip plus the per-pool ocert counters, tip hash,
    next block number and next slot — everything the forging loop
    threads."""
    from ..block.praos_block import Block

    rules = pbatch.rules_of(params)
    st = rules.initial_state()
    prev_hash = None
    block_no = 0
    slot = 0
    for _entry, raw in imm.stream_all():
        b = Block.from_bytes(raw)
        ticked = rules.tick(params, lview, b.slot, st)
        st = rules.reupdate(params, b.header.to_view(), b.slot, ticked)
        prev_hash = b.hash_
        block_no = b.block_no + 1
        slot = b.slot + 1
    # reupdate keyed these by hash_key(vk_cold) == pool.pool_id
    return st, dict(st.ocert_counters), prev_hash, block_no, slot


def _forge_pipeline(
    imm, params, pools, lview, limit, res, st, prev_hash, block_no,
    slot, counters, ledger_view_for_epoch, txs_per_block, txs_for_block,
    engine, trace, elector=None,
):
    """The batched forging fast path: elect whole slot windows in one
    sweep (device or batched-host, protocol/forge.py), then run the
    sequential assembly tail over just the elected slots. Byte- and
    state-identical to the per-slot loop below for the same inputs
    (tests/test_forge.py holds the equation); returns the threaded
    (st, prev_hash, block_no, slot)."""
    from ..protocol import forge as forge_mod
    from ..testing import chaos

    rules = pbatch.rules_of(params)
    asm = forge_mod.BlockAssembler(params, pools)
    stg = forge_mod.stage_engine(params, pools, engine)
    tracer = pbatch.BATCH_TRACER

    def done() -> bool:
        if limit.slots is not None and slot >= limit.slots:
            return True
        if limit.blocks is not None and block_no >= limit.blocks:
            return True
        if limit.epochs is not None and params.epoch_of(slot) >= limit.epochs:
            return True
        return False

    while not done():
        lv_now = (
            ledger_view_for_epoch(params.epoch_of(slot))
            if ledger_view_for_epoch is not None
            else lview
        )
        # eta0 is epoch-constant: one tick at the window start serves
        # the whole (epoch-clamped) window's elections; the per-block
        # reupdate below re-ticks at each forged slot exactly as the
        # reference loop does
        ticked0 = rules.tick(params, lv_now, slot, st)
        eta0 = ticked0.state.epoch_nonce
        epoch_end = (params.epoch_of(slot) + 1) * params.epoch_length
        wend = min(epoch_end, slot + forge_mod.window_slots(len(pools)))
        if limit.slots is not None:
            wend = min(wend, limit.slots)
        if limit.blocks is not None:
            # don't elect far past where the block limit will trip:
            # ~1/f slots per block, padded 2x + a margin
            need = limit.blocks - block_no
            est = int(2 * need / float(params.active_slot_coeff)) + 64
            wend = min(wend, slot + est)
        wend = max(wend, slot + 1)
        windex = forge_mod.next_window_index()
        t_el = time.monotonic()
        if rules.overlay:
            elected = forge_mod.elect_window_tpraos(
                params, lv_now, pools, range(slot, wend), eta0
            )
        elif elector is not None:
            elected = forge_mod.elected_from_rows(
                pools, elector(range(slot, wend), eta0), eta0
            )
        else:
            thr = forge_mod.pool_thresholds(params, lv_now, pools)
            elected = forge_mod.elect_window_recovering(
                params, pools, stg, thr, range(slot, wend), eta0, engine,
                lv_now, windex, tracer=tracer,
            )
        elect_s = time.monotonic() - t_el
        if engine == "device" and elected:
            # pre-sign the window's deduped OCert issues through the
            # forge_sign graph (byte-identical to the host signer)
            triples = {
                (el.pool, counters.get(pools[el.pool].pool_id, 0),
                 asm.ocert_window(el.slot))
                for el in elected
            }
            missing = {t for t in triples if t not in asm._ocerts}
            if missing:
                asm.prime_ocerts(
                    forge_mod.sign_ocerts_batch(pools, missing)
                )
        t_asm = time.monotonic()
        signed = 0
        last_forged = slot
        for el in elected:
            if limit.blocks is not None and block_no >= limit.blocks:
                break
            s = el.slot
            ticked = rules.tick(params, lv_now, s, st)
            n = counters.get(pools[el.pool].pool_id, 0)
            if txs_for_block is not None:
                txs = tuple(txs_for_block(s, block_no))
            else:
                txs = tuple(
                    b"tx-%d-%d" % (s, i) for i in range(txs_per_block)
                )
            block = asm.forge(
                el.pool, slot=s, block_no=block_no, prev_hash=prev_hash,
                txs=txs, ocert_counter=n, is_leader=el.is_leader,
            )
            imm.append_block(s, block_no, block.hash_, block.bytes_)
            st = rules.reupdate(params, block.header.to_view(), s, ticked)
            counters[pools[el.pool].pool_id] = n
            prev_hash = block.hash_
            block_no += 1
            last_forged = s
            signed += 1
            res.n_blocks += 1
            chaos.fire("forge")
            if res.n_blocks % 1000 == 0:
                trace(f"forged {res.n_blocks} blocks to slot {s}")
        if limit.blocks is not None and block_no >= limit.blocks:
            # the reference loop stops right after the tripping block's
            # slot — count only the slots up to and including it
            consumed = last_forged + 1 - slot
        else:
            consumed = wend - slot
        slot += consumed
        res.n_slots += consumed
        if tracer is not None:
            from ..utils.trace import ForgeSpan

            tracer(ForgeSpan(
                index=windex, engine=engine, slots=consumed,
                pairs=(wend - (slot - consumed)) * len(pools),
                elected=len(elected), signed=signed, elect_s=elect_s,
                assemble_s=time.monotonic() - t_asm,
            ))
    return st, prev_hash, block_no, slot


def _synthesize_locked(
    imm, db_path, params, pools, lview, limit, txs_per_block,
    vrf_backend, trace, ledger_view_for_epoch, txs_for_block,
    ledger, genesis_state, elector=None,
) -> ForgeResult:

    from ..protocol import forge as forge_mod

    # the rows of an election made elsewhere go through the pipeline's
    # assembly whatever the lever says: there is nothing to elect here
    engine = "rows" if elector is not None else (
        forge_mod.engine_from_env(vrf_backend))
    rules = pbatch.rules_of(params)
    st = rules.initial_state()
    if rules.overlay:
        if elector is not None or ledger is not None:
            raise ValueError("a TPraos chain is elected here, on the "
                             "host, against a constant ledger view")
        if "device" in (engine, vrf_backend):
            raise ValueError("no TPraos device forge yet (no leader-"
                             "value sweep for this protocol): pass "
                             "vrf_backend=\"host\" and leave "
                             "OCT_FORGE_DEVICE unset or 0")
    if ledger is not None:
        # the ledger fold derives each epoch's view from state the loop
        # itself threads — the whole-window election has no view to
        # elect against yet, so ledger mode stays on the per-slot loop
        engine = "loop"
    if vrf_backend == "auto":
        # host signing runs through the native C library (ops/host/fast)
        # at ~0.3 ms/proof — robust on every platform; the device span
        # prover stays opt-in (vrf_backend="device") for chips where the
        # sign-side kernels compile fast
        vrf_backend = "host"

    res = ForgeResult()
    t0 = time.monotonic()
    prev_hash: bytes | None = None
    block_no = 0
    slot = 0
    counters: dict[bytes, int] = {}
    if not imm.is_empty:
        # resume: rebuild the forging state from the surviving (just
        # deep-validated/repaired) chain and continue from the tip —
        # forging is deterministic, so the resumed chain converges on
        # the uninterrupted run's bytes
        tip = imm.tip()
        memo_key = os.path.realpath(db_path)
        memo = _REPLAY_MEMO.get(memo_key)
        if (
            memo is not None
            and memo[0] == tip.slot
            and memo[1] == tip.hash_
        ):
            st, counters, prev_hash, block_no, slot = (
                memo[2], dict(memo[3]), memo[4], memo[5], memo[6],
            )
            trace(f"resuming synthesis at slot {slot} "
                  f"({block_no} blocks survive, memoized fold)")
        else:
            st, counters, prev_hash, block_no, slot = _replay_forged_state(
                params, lview, imm
            )
            trace(f"resuming synthesis at slot {slot} "
                  f"({block_no} blocks survive)")

    if ledger is not None:
        if genesis_state is None:
            raise ValueError("ledger mode needs genesis_state")
        if ledger_view_for_epoch is not None:
            raise ValueError("pass ledger OR ledger_view_for_epoch")
        if txs_per_block and txs_for_block is None:
            raise ValueError(
                "ledger mode folds every tx through the ledger rules: "
                "placeholder txs_per_block txs would not decode — "
                "supply real txs via txs_for_block"
            )
        ledger_epoch_len = getattr(
            getattr(ledger, "genesis", None), "epoch_length", None
        )
        if ledger_epoch_len is not None and ledger_epoch_len != params.epoch_length:
            raise ValueError(
                f"ledger epoch_length {ledger_epoch_len} != protocol "
                f"epoch_length {params.epoch_length}: the two epoch "
                "clocks would silently desync"
            )
        lst = genesis_state
        _view_cache: dict[int, object] = {}

        def ledger_view_for_epoch(epoch):  # noqa: F811 — the seam above
            # epoch-constant: derive once per epoch, not per slot
            if epoch not in _view_cache:
                tls = ledger.tick(lst, max(slot, 1))
                _view_cache[epoch] = ledger.view_for_epoch(tls.state, epoch)
            return _view_cache[epoch]

    def done() -> bool:
        if limit.slots is not None and slot >= limit.slots:
            return True
        if limit.blocks is not None and block_no >= limit.blocks:
            return True
        if limit.epochs is not None and params.epoch_of(slot) >= limit.epochs:
            return True
        return False

    span_proofs: dict = {}
    span_end = 0

    if engine != "loop":
        # the batched pipeline (protocol/forge.py): whole-window
        # elections + amortized assembly. It advances the same state
        # the loop below threads, so after it returns done() is True
        # and the per-slot reference loop is a no-op — except when a
        # recovery ladder exhausted mid-run, which re-enters it as the
        # floor that cannot fail for device reasons.
        st, prev_hash, block_no, slot = _forge_pipeline(
            imm, params, pools, lview, limit, res, st, prev_hash,
            block_no, slot, counters, ledger_view_for_epoch,
            txs_per_block, txs_for_block, engine, trace, elector,
        )
    while not done():
        lv_now = (
            ledger_view_for_epoch(params.epoch_of(slot))
            if ledger_view_for_epoch is not None
            else lview
        )
        ticked = rules.tick(params, lv_now, slot, st)
        eta0 = ticked.state.epoch_nonce
        if vrf_backend == "device" and slot >= span_end:
            # next span: up to the epoch boundary (eta0 is epoch-constant)
            epoch_end = (params.epoch_of(slot) + 1) * params.epoch_length
            span_end = min(epoch_end, slot + 16 * _VRF_BUCKET)
            if limit.slots is not None:
                span_end = min(span_end, limit.slots)
            if limit.blocks is not None:
                # don't prove far past where the block limit will trip:
                # ~1/f slots per block, padded 2x + a margin
                need = limit.blocks - block_no
                est = int(2 * need / float(params.active_slot_coeff)) + 64
                span_end = min(span_end, slot + est)
            span_proofs = _prove_span(pools, range(slot, span_end), eta0)
        if rules.overlay:
            # the overlay schedule or the 512-bit lottery says who forges
            el = forge_mod.elect_slot_tpraos(params, lv_now, pools, slot, eta0)
            slot_pools = [] if el is None else [(el.pool, pools[el.pool])]
        else:
            el, slot_pools = None, enumerate(pools)
        for pi, pool in slot_pools:
            if el is not None:
                is_leader = el.is_leader
            elif vrf_backend == "device":
                is_leader = span_proofs[(slot, pi)]
            else:  # host: lazy per-slot evaluation (small runs)
                is_leader = evaluate_vrf(pool, slot, eta0)
            if el is None:
                lv_val = nonces.vrf_leader_value(is_leader.vrf_output)
                entry = lv_now.pool_distr.get(pool.pool_id)
                if entry is None:
                    continue  # pool has no stake this epoch
                if not check_leader_value(
                    lv_val, entry.stake, params.active_slot_coeff
                ):
                    continue
            n = counters.get(pool.pool_id, 0)
            if txs_for_block is not None:
                txs = tuple(txs_for_block(slot, block_no))
            else:
                txs = tuple(
                    b"tx-%d-%d" % (slot, i) for i in range(txs_per_block)
                )
            block = forge_block(
                params,
                pool,
                slot=slot,
                block_no=block_no,
                prev_hash=prev_hash,
                epoch_nonce=eta0,
                txs=txs,
                ocert_counter=n,
                is_leader=is_leader,
            )
            if ledger is not None:
                # the fold MUST accept what we forged BEFORE the block
                # is persisted — a rejected tx must not leave an
                # invalid block on disk
                lst = ledger.tick_then_apply(lst, block)
            imm.append_block(slot, block_no, block.hash_, block.bytes_)
            st = rules.reupdate(params, block.header.to_view(), slot, ticked)
            counters[pool.pool_id] = n
            prev_hash = block.hash_
            block_no += 1
            res.n_blocks += 1
            if res.n_blocks % 1000 == 0:
                trace(f"forged {res.n_blocks} blocks to slot {slot}")
            break  # first winning credential forges (one block per slot)
        # NB: on a leaderless slot `st` is left un-ticked — tick is a pure
        # function of (state, slot) re-derived at the next forged block;
        # latching `ticked.state` here would rotate the epoch nonce twice
        # (is_new_epoch keys off last_slot, which only blocks advance)
        slot += 1
        res.n_slots += 1

    imm.flush()
    # forge-time sidecars: seal every retired chunk's columnar sidecar
    # NOW so the first replay opens hot (write-once; skips fresh seals;
    # no-op under OCT_SIDECAR=0 or without the native extractor)
    from ..storage import sidecar as sidecar_mod

    # walked=True: the forge wrote these exact bytes this run — the
    # seal covers a chunk whose integrity holds by construction
    sidecar_mod.backfill_store(imm, walked=True)
    res.wall_s = time.monotonic() - t0
    res.final_state = st
    tip = imm.tip()
    if tip is not None:
        # seed the trusted-fold memo: a resume-then-extend onto this
        # exact tip skips the whole-chain reupdate replay
        _REPLAY_MEMO[os.path.realpath(db_path)] = (
            tip.slot, tip.hash_, st, dict(counters), prev_hash,
            block_no, tip.slot + 1,
        )
    return res


def main(argv=None) -> None:
    """CLI (app/db-synthesizer.hs + DBSynthesizer/Parsers.hs analog)."""
    import argparse

    p = argparse.ArgumentParser(prog="db_synthesizer", description=__doc__)
    p.add_argument("--out", required=True, help="chain DB directory to create")
    p.add_argument("--pools", type=int, default=2)
    p.add_argument("--kes-depth", type=int, default=7)
    lim = p.add_mutually_exclusive_group(required=True)
    lim.add_argument("--slots", type=int)
    lim.add_argument("--blocks", type=int)
    lim.add_argument("--epochs", type=int)
    p.add_argument("--txs-per-block", type=int, default=0)
    p.add_argument("--resume", action="store_true",
                   help="continue a crashed synthesis: deep-validate + "
                        "repair the surviving chain, rebuild the "
                        "forging state from it, forge on from the tip")
    p.add_argument("--config", default=None,
                   help="node config.json (with CredentialsFile) instead "
                        "of --pools/--kes-depth generated credentials")
    p.add_argument("--protocol", choices=("praos", "tpraos"),
                   default="praos",
                   help="tpraos: the Shelley-era protocol (two VRF "
                        "certificates a block, the BFT overlay)")
    p.add_argument("--delegates", type=int, default=7,
                   help="tpraos: genesis delegates (mainnet: 7)")
    p.add_argument("--decentralisation", default="1/2",
                   help="tpraos: d, the overlay's share of the slots")
    p.add_argument("--cardano", action="store_true",
                   help="forge the multi-era composite (era-tagged "
                        "blocks crossing the Byron/Shelley/Babbage "
                        "boundaries); pairs with db_analyser --cardano")
    p.add_argument("--with-ledgers", action="store_true",
                   help="with --cardano: real era ledgers in the loop")
    a = p.parse_args(argv)
    if a.with_ledgers and not a.cardano:
        p.error("--with-ledgers requires --cardano")
    from .. import compile_cache

    compile_cache.configure()  # before the first trace
    if a.cardano:
        from ..hardfork import composite as cardano

        if a.config is not None:
            p.error("--cardano uses the composite's built-in config")
        if not a.slots:
            p.error("--cardano forges by --slots")
        cfg = cardano.CardanoMockConfig(with_ledgers=a.with_ledgers)
        n = cardano.synthesize(a.out, cfg, a.slots)
        print(f"forged {n} blocks over {a.slots} slots at {a.out}")
        return
    if a.config:
        from .config import load_config

        params, lview, pools = load_config(a.config)
        if pools is None:
            p.error("--config needs a CredentialsFile to forge with")
    else:
        params = default_params(kes_depth=a.kes_depth)
        pools, lview = make_credentials(a.pools, kes_depth=a.kes_depth)
        if a.protocol == "tpraos":
            params, pools, lview = make_tpraos(
                params, pools, lview, a.delegates,
                Fraction(a.decentralisation), first_seed=a.pools)
    res = synthesize(
        a.out, params, pools, lview,
        ForgeLimit(slots=a.slots, blocks=a.blocks, epochs=a.epochs),
        txs_per_block=a.txs_per_block,
        trace=lambda s: print(s),
        resume=a.resume,
    )
    # the chain carries its own config (tools-test pipeline shape)
    from .config import write_genesis_files

    write_genesis_files(
        os.path.join(a.out, "config"), params, lview, pools
    )
    print(
        f"forged {res.n_blocks} blocks over {res.n_slots} slots "
        f"in {res.wall_s:.1f}s"
    )


if __name__ == "__main__":
    main()
