"""The Byron era's window path (protocol/pbft): the columnar PBFT fold
against the sequential rule (`instances.PBftProtocol`) and against the
benchmark's plain reference (`benchmark/reference/pbft.py`), case by case;
Byron windows through `validate_stream` with the signing window carried
across windows and across pieces; the Byron header's columns (native
headerscan) against the Python decoder."""

from fractions import Fraction

import numpy as np
import pytest

from benchmark.reference import pbft as ref
from ouroboros_consensus_tpu import native_loader
from ouroboros_consensus_tpu.hardfork import byron_mock
from ouroboros_consensus_tpu.ops.host import fast
from ouroboros_consensus_tpu.protocol import batch as pbatch
from ouroboros_consensus_tpu.protocol import pbft
from ouroboros_consensus_tpu.protocol.abstract import ConsensusError
from ouroboros_consensus_tpu.protocol.instances import (
    PBftLedgerView, PBftState, TickedPBftState)

K, KEYS = 2160, 7
PARAMS = pbft.PBftEraParams(num_genesis_keys=KEYS,
                            threshold=Fraction(22, 100), security_param=K)
SEEDS = [bytes([j + 1]) * 32 for j in range(KEYS)]
VKS = [fast.ed25519_public(s) for s in SEEDS]
VIEW = PBftLedgerView({vk: j for j, vk in enumerate(VKS)})
STRANGER = b"\x77" * 32


def _headers(slots, signers, bad_sig=(), ebb_at=()):
    """Byron headers at `slots`, lane i signed by delegate signers[i]
    (-1: a key outside the map), `bad_sig` lanes with a flipped byte,
    `ebb_at` lanes EBBs."""
    out, prev = [], b"\x05" * 32
    for i, (slot, j) in enumerate(zip(slots, signers)):
        if i in ebb_at:
            blk = byron_mock.forge_ebb(slot=slot, block_no=i, prev_hash=prev)
        else:
            seed = STRANGER if j < 0 else SEEDS[j]
            blk = byron_mock.forge_block(seed, slot=slot, block_no=i,
                                         prev_hash=prev,
                                         sign=fast.ed25519_sign)
        h = blk.header
        if i in bad_sig:
            h = byron_mock.ByronMockHeader(
                h.block_no, h.slot, h.prev_hash, h.issuer_vk, h.body_hash,
                h.sig[:40] + bytes([h.sig[40] ^ 1]) + h.sig[41:])
        out.append(h)
        prev = h.hash_
    return out


def _sequential(st, headers, ok):
    """`PBftProtocol.apply_checked_sig` header by header over the given
    signature verdicts -> (state, n_valid, error)."""
    proto = pbft.PBFT_RULES.protocol_of(PARAMS, VIEW)
    for i, h in enumerate(headers):
        if h.is_ebb:
            continue
        try:
            st = proto.apply_checked_sig(st, h.slot, h.issuer_vk, ok[i],
                                         VIEW.delegates)
        except ConsensusError as e:  # the rule's error
            return st, i, e
    return st, len(headers), None


def _reference(st, headers, ok):
    """benchmark/reference/pbft.py over the same headers and verdicts."""
    rp = ref.Params(K, Fraction(22, 100), dict(VIEW.delegates))
    rst = ref.State(st.signers)
    for i, h in enumerate(headers):
        rh = ref.Header(h.is_ebb, h.slot, h.block_no, h.prev_hash,
                        b"" if h.is_ebb else h.issuer_vk, h.sig,
                        b"" if h.is_ebb else h.signed_bytes)
        if not h.is_ebb and not ok[i]:
            return rst, i, ("PBftInvalidSignature", {"slot": h.slot})
        err = ref.check(rp, rh, rst, crypto=False)
        if err is not None:
            return rst, i, err
        rst = ref.update(rp, rh, rst)
    return rst, len(headers), None


def _columnar(st, headers, ok):
    cols = pbft.ByronColumns.from_headers(headers)
    pre = pbft.prechecks(PARAMS, VIEW, cols)
    one = np.ones(len(headers), bool)
    v = pbatch.Verdicts(np.asarray(ok, bool), one, one, one, ~one, None,
                        None)
    return pbft._fold(PARAMS, st, cols, pre, v)


def _carried(pattern):
    """A full carried window: `pattern` [(genesis key, count), ...] in
    order, at slots 0, 1, ...."""
    seq = [g for g, n in pattern for _ in range(n)]
    return PBftState(tuple((s, g) for s, g in enumerate(seq)))


# (name, carried state, signers of the window's lanes, bad-signature
# lanes, EBB lanes, slot step, the index the first error is at)
CASES = [
    ("all-valid", PBftState(), [i % KEYS for i in range(40)], (), (0,), 1,
     None),
    ("bad-signature", PBftState(), [i % KEYS for i in range(30)], (17,),
     (), 1, 17),
    ("not-a-delegate", PBftState(), [i % KEYS for i in range(20)] + [-1],
     (), (), 1, 20),
    ("ebb-changes-nothing", PBftState(), [0, 1, 2, 3, 4], (), (2,), 1,
     None),
    ("slot-before-the-last", PBftState(), [0, 1, 2, 3], (), (), -1, 1),
    # the genesis key 0 holds 474 of the trailing 2,160 at the END of
    # the carried window: its next block makes 475 (allowed), the one
    # after 476 (refused)
    ("threshold-475-476", _carried([(1, K - 474), (0, 474)]),
     [0, 0, 1], (), (), 1, 1),
    # 475 carried, but the oldest of them leaves the window with each new
    # block: the key may sign on
    ("threshold-window-slides", _carried([(0, 475), (2, K - 475)]),
     [0, 0, 0], (), (), 1, None),
]


@pytest.mark.parametrize(
    "name,st,signers,bad,ebbs,step,want_at", CASES,
    ids=[c[0] for c in CASES])
def test_columnar_fold_is_the_rule(name, st, signers, bad, ebbs, step,
                                   want_at):
    last = st.signers[-1][0] if st.signers else 0
    slots = ([last + 1 + i for i in range(len(signers))] if step > 0
             else [last + 100 - i for i in range(len(signers))])
    headers = _headers(slots, signers, bad_sig=bad, ebb_at=ebbs)
    ok = [h.is_ebb or native_loader.native_ed25519_verify(
        h.issuer_vk, h.sig, h.signed_bytes) for h in headers]
    want = _sequential(st, headers, ok)
    got = _columnar(st, headers, ok)
    rst, rn, rerr = _reference(st, headers, ok)
    assert (got.n_valid, got.error) == (want[1], want[2])
    assert got.state == want[0]
    assert rn == want[1] and rst.signers == want[0].signers
    if want_at is None:
        assert got.error is None and got.n_valid == len(headers)
    else:
        assert got.n_valid == want_at
        name_, fields = rerr
        assert type(got.error).__name__ == name_
        from dataclasses import asdict

        assert asdict(got.error) == fields
    if name == "threshold-475-476":
        assert (got.error.signed, got.error.allowed) == (476, 475)


def test_window_carried_across_windows_and_pieces(monkeypatch):
    """A Byron run through `validate_stream` (the device loop; the `ed`
    verdicts the native verifier's through the stub, the fold the
    columnar one) in windows of 16 lanes and two pieces, with one key
    made to sign past the threshold late in the second piece: the same
    first error, index and window as the sequential rule."""
    from ouroboros_consensus_tpu.testing import stubs

    stubs.install_stub_crypto(monkeypatch)
    params = pbft.PBftEraParams(num_genesis_keys=3, threshold=Fraction(1, 2),
                                security_param=10)
    view = PBftLedgerView({VKS[j]: j for j in range(3)})
    # round robin over three keys, then key 0 alone from lane 40 on
    signers = [i % 3 for i in range(40)] + [0] * 10
    headers = _headers(list(range(1, 51)), signers, ebb_at=(0,))
    cols = pbft.ByronColumns.from_headers(headers)
    pieces = iter([cols[:23], cols[23:]])
    res = pbatch.validate_stream(params, lambda _e: view, PBftState(),
                                 pieces, max_batch=16)
    proto = pbft.PBFT_RULES.protocol_of(params, view)
    st, want = PBftState(), None
    for i, h in enumerate(headers):
        if h.is_ebb:
            continue
        try:
            st = proto.update(h.to_view(), h.slot,
                              TickedPBftState(st, view.delegates))
        except ConsensusError as e:  # the rule's error
            want = (i, e)
            break
    assert want is not None and want[0] > 40
    assert (res.n_valid, res.error) == want
    assert res.state == st


def test_byron_columns_native_against_the_decoder():
    """headerscan's Byron columns (native_loader.extract_byron through
    ByronColumns.pieces_from_native) against the Python decoder, EBB
    and main headers, one chunk of era-tagged blocks."""
    from ouroboros_consensus_tpu.hardfork.combinator import HardForkBlock

    headers = _headers(list(range(0, 12)), [i % KEYS for i in range(12)],
                       ebb_at=(0,))
    blocks = [byron_mock.forge_ebb(slot=0, block_no=0, prev_hash=None)]
    for h in headers[1:]:
        blocks.append(byron_mock.ByronMockBlock(h, ()))
    raw = [HardForkBlock(0, b).bytes_ for b in blocks]
    buf = b"".join(raw)
    offs = np.cumsum([0] + [len(r) for r in raw[:-1]])
    era, io, _il = native_loader.scan_eras(buf, offs)
    assert era.tolist() == [0] * len(raw)
    cols = native_loader.extract_byron(buf, io)
    (piece,) = pbft.ByronColumns.pieces_from_native(cols, buf)
    want = pbft.ByronColumns.from_headers([b.header for b in blocks])
    for f in pbft.ByronColumns._FIELDS:
        assert np.array_equal(getattr(piece, f), getattr(want, f)), f
    for i, b in enumerate(blocks):
        assert byron_mock.ByronMockBlock.from_bytes(
            buf[io[i]:io[i] + _il[i]]) == b
        assert cols["tx_root"][i].tobytes() == b.header.body_hash
    assert piece.signed.shape[1] == byron_mock.BYRON_SIGNED_LEN
