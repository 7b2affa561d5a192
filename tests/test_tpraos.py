"""TPraos: overlay schedule, host/device/native differential validation,
and the TPraos→Praos state translation (reference: Protocol/TPraos.hs,
Protocol/Praos/Translate.hs)."""

import math
from fractions import Fraction

import pytest

from ouroboros_consensus_tpu.protocol import praos, tpraos
from ouroboros_consensus_tpu.protocol.views import hash_key, hash_vrf_vk
from ouroboros_consensus_tpu.testing import fixtures

KES_DEPTH = 3


def mk_params(d, f=Fraction(1), epoch_length=500):
    inner = praos.PraosParams(
        slots_per_kes_period=100,
        max_kes_evolutions=62,
        security_param=5,
        active_slot_coeff=f,
        epoch_length=epoch_length,
        kes_depth=KES_DEPTH,
    )
    return tpraos.TPraosParams(praos=inner, decentralization=d)


def mk_setup(d, f=Fraction(1), n_delegs=2):
    params = mk_params(d, f)
    pool = fixtures.make_pool(0, kes_depth=KES_DEPTH)
    delegs = [
        fixtures.make_pool(10 + i, kes_depth=KES_DEPTH) for i in range(n_delegs)
    ]
    base = fixtures.make_ledger_view([pool])
    lview = tpraos.TPraosLedgerView(
        pool_distr=base.pool_distr,
        gen_delegs=[
            tpraos.GenDeleg(dp.vk_cold, hash_vrf_vk(dp.vrf_vk))
            for dp in delegs
        ],
    )
    return params, pool, delegs, lview


def test_overlay_schedule_density_and_assignment():
    params = mk_params(Fraction(1, 4), f=Fraction(1, 2))
    n = params.praos.epoch_length
    overlay = [s for s in range(n) if tpraos.overlay_position(params, s) is not None]
    # ceil-step schedule: exactly ceil(n*d) overlay slots in the epoch
    assert len(overlay) == math.ceil(n * Fraction(1, 4))
    # positions are consecutive integers
    pos = [tpraos.overlay_position(params, s) for s in overlay]
    assert pos == list(range(len(overlay)))
    # f=1/2 -> every second overlay position active, round-robin delegates
    seen = []
    for s in overlay:
        a = tpraos.overlay_slot_assignment(params, 2, s)
        assert a is not None
        active, j = a
        if active:
            seen.append(j)
    assert seen[:4] == [0, 1, 0, 1]
    # d=0: no overlay slots at all
    p0 = mk_params(Fraction(0))
    assert tpraos.overlay_position(p0, 17) is None


def forge_header(params, creds, slot, nonce, prev, block_no=0):
    """One real-codec two-certificate header (the packed device path
    reads every field out of the KES-signed body)."""
    from ouroboros_consensus_tpu.block.forge import forge_block

    return forge_block(
        params.praos, creds, slot=slot, block_no=block_no, prev_hash=prev,
        epoch_nonce=nonce,
        is_leader=tpraos.prove_certificates(creds.vrf_seed, slot, nonce),
    ).header.to_view()


def forge_chain(params, pool, delegs, lview, n_slots):
    """Forge the deterministic TPraos chain: scheduled delegate on active
    overlay slots, the pool elsewhere (f=1 so it always wins)."""
    nonce = b"\x09" * 32
    hvs = []
    prev = None
    for slot in range(1, n_slots):
        a = tpraos.overlay_slot_assignment(params, len(delegs), slot)
        if a is None:
            creds = pool
        else:
            active, j = a
            if not active:
                continue
            creds = delegs[j]
        hvs.append(forge_header(params, creds, slot, nonce, prev, len(hvs)))
        prev = b"%032d" % slot
    return nonce, hvs


@pytest.fixture(scope="module")
def chain():
    params, pool, delegs, lview = mk_setup(Fraction(1, 3), f=Fraction(1))
    nonce, hvs = forge_chain(params, pool, delegs, lview, 120)
    return params, pool, delegs, lview, nonce, hvs


def _host_fold(params, lview, nonce, hvs):
    import dataclasses

    st = dataclasses.replace(tpraos.TPraosState(), epoch_nonce=nonce)
    for hv in hvs:
        t = tpraos.tick(params, lview, hv.slot, st)
        t = tpraos.TickedTPraosState(
            dataclasses.replace(t.state, epoch_nonce=nonce), t.ledger_view
        )
        st = tpraos.update(params, hv, hv.slot, t)
    return st


def _batch_validate(params, lview, nonce, hvs, backend):
    import dataclasses

    proto = tpraos.TPraosProtocol(params, use_device_batch=True)
    st = dataclasses.replace(tpraos.TPraosState(), epoch_nonce=nonce)
    ticked = tpraos.tick(params, lview, hvs[0].slot, st)
    ticked = tpraos.TickedTPraosState(
        dataclasses.replace(ticked.state, epoch_nonce=nonce), ticked.ledger_view
    )
    return proto.validate_batch(ticked, hvs, backend=backend)


@pytest.mark.slow
def test_host_device_native_agree(chain):
    params, pool, delegs, lview, nonce, hvs = chain
    assert len(hvs) > 30
    host_st = _host_fold(params, lview, nonce, hvs)
    for backend in ("device", "native"):
        res = _batch_validate(params, lview, nonce, hvs, backend)
        assert res.error is None, f"{backend}: {res.error!r}"
        assert res.n_valid == len(hvs)
        assert res.state == host_st, backend


@pytest.mark.slow
def test_wrong_delegate_rejected(chain):
    params, pool, delegs, lview, nonce, hvs = chain
    # find an overlay header and re-forge it with the OTHER delegate
    for idx, hv in enumerate(hvs):
        a = tpraos.overlay_slot_assignment(params, len(delegs), hv.slot)
        if a is not None and a[0]:
            j = a[1]
            other = delegs[1 - j]
            bad = forge_header(params, other, hv.slot, nonce,
                               hv.prev_hash, idx)
            bad_hvs = list(hvs[: idx]) + [bad]
            break
    else:
        pytest.fail("no active overlay header in chain")
    for backend in ("device", "native", None):
        if backend is None:
            import dataclasses

            st = dataclasses.replace(tpraos.TPraosState(), epoch_nonce=nonce)
            err = None
            for hv in bad_hvs:
                t = tpraos.tick(params, lview, hv.slot, st)
                t = tpraos.TickedTPraosState(
                    dataclasses.replace(t.state, epoch_nonce=nonce),
                    t.ledger_view,
                )
                try:
                    st = tpraos.update(params, hv, hv.slot, t)
                except praos.PraosValidationError as e:
                    err = e
                    break
            assert isinstance(err, tpraos.WrongGenesisDelegate)
        else:
            res = _batch_validate(params, lview, nonce, bad_hvs, backend)
            assert res.n_valid == idx, backend
            assert isinstance(res.error, tpraos.WrongGenesisDelegate), backend


def test_inactive_overlay_slot_rejected():
    params, pool, delegs, lview = mk_setup(Fraction(1, 2), f=Fraction(1, 2))
    nonce = b"\x09" * 32
    # find an inactive overlay slot and forge a (pool) block there
    slot = next(
        s for s in range(1, 200)
        if tpraos.overlay_slot_assignment(params, 2, s) == (False, None)
    )
    hv = fixtures.forge_tpraos_header_view(
        params.praos, pool, slot=slot, epoch_nonce=nonce,
        prev_hash=None, body_bytes=b"x",
    )
    res = _batch_validate(params, lview, nonce, [hv], "native")
    assert isinstance(res.error, tpraos.NonActiveSlot)


def test_translate_state_carries_nonces(chain):
    params, pool, delegs, lview, nonce, hvs = chain
    st = _host_fold(params, lview, nonce, hvs)
    p = tpraos.translate_state(st)
    assert isinstance(p, praos.PraosState) and not isinstance(p, tpraos.TPraosState)
    assert p.evolving_nonce == st.evolving_nonce
    assert p.candidate_nonce == st.candidate_nonce
    assert p.ocert_counters == st.ocert_counters
    assert p.last_slot == st.last_slot


def test_check_is_leader_overlay():
    params, pool, delegs, lview = mk_setup(Fraction(1, 2), f=Fraction(1))
    import dataclasses

    st = dataclasses.replace(tpraos.TPraosState(), epoch_nonce=b"\x07" * 32)
    ticked = tpraos.TickedTPraosState(st, lview)
    slot = next(
        s for s in range(1, 100)
        if (a := tpraos.overlay_slot_assignment(params, 2, s)) and a[0]
    )
    _active, j = tpraos.overlay_slot_assignment(params, 2, slot)
    cbl = fixtures.can_be_leader(delegs[j])
    assert tpraos.check_is_leader(params, cbl, slot, ticked, deleg_index=j)
    assert tpraos.check_is_leader(params, cbl, slot, ticked, deleg_index=1 - j) is None
