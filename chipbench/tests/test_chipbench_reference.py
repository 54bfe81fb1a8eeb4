"""The plain reference and the check that decides ``correct``: the
benchmark's own record protocol agrees with the program's, the check passes
the reference's own output and fails a flipped byte, a misrouted request, a
bad record tag and the int8 control; and the fused round's logical bytes
do not depend on the layout."""
import numpy as np
import pytest

from chipbench import refcipher, reference, spec
from chipbench.kernel_bytes import fused_round_bytes
from chipbench.traffic import Traffic

DATA = spec.HERE / "tests" / "data"
SECRET = b"chipbench-test"


def _case(cfg, mix, n_rounds=3, seed=5):
    config = spec.load_json(DATA / f"{cfg}.json")
    traffic = Traffic(spec.load_json(DATA / f"{mix}.json"), config, seed,
                      seconds=0.01)
    sent = [list(range(n_rounds)) for _ in range(traffic.connections)]
    return config, traffic, sent


def _keys(i, u):
    return refcipher.session_key(SECRET, b"tls-tx", 1000 + 10 * i + u)


def _wires(config, traffic, sent, keys=None):
    """The reference proxy's output, stored exactly (int64)."""
    want = reference.expected(traffic, config, sent)
    out = {}
    for i in range(len(sent)):
        for u in range(config["upstreams_per_connection"]):
            ks = want[i].get(u, [])
            frames = [traffic.frame(k, i) for k in ks]
            if keys is not None and ks:
                frames = refcipher.seal(
                    [keys(i, u)] * len(ks), [k + 1 for k in ks],
                    [(traffic.header(k, i), traffic.body(k, i))
                     for k in ks])
            out[i, u] = (np.concatenate(frames) if frames
                         else np.zeros((0,), np.int64))
    return out


def test_record_protocol_matches_the_program():
    from repro.core import LengthPrefixedParser, seal_record
    from repro.core.vpi import VpiRegistry

    reg = VpiRegistry(secret=SECRET)
    assert reg.derive_key(b"tls-rx", 42) == \
        refcipher.session_key(SECRET, b"tls-rx", 42)
    key = refcipher.session_key(SECRET, b"tls-tx", 7)
    frame = np.concatenate([[17, 4, 9], np.arange(13) * 19 % 256])
    theirs = seal_record(key, frame, LengthPrefixedParser(), seq=3)
    ours = refcipher.seal([key], [3], [(frame[:7], frame[7:])])[0]
    np.testing.assert_array_equal(theirs, ours)
    seq, plain, ok, end = refcipher.open_record(key, ours, 0)
    assert (seq, ok, end) == (3, True, len(ours))
    np.testing.assert_array_equal(plain, frame)


def test_traffic_is_seeded_and_balanced():
    config, a, _ = _case("tiny-l7route", "tiny-bulk")
    _, b, _ = _case("tiny-l7route", "tiny-bulk")
    np.testing.assert_array_equal(a.frame(2, 3), b.frame(2, 3))
    # every round gives each body size the same share of connections
    for k in range(a.rounds):
        sizes, counts = np.unique(a.body_len[k], return_counts=True)
        assert len(set(counts)) == 1 and len(sizes) == 2
    ups = [reference.upstream(config, a.header(0, i))
           for i in range(a.connections)]
    assert ups.count(0) == ups.count(1)


@pytest.mark.parametrize("cfg,mix,tls", [("tiny-l7route", "tiny-bulk", False),
                                         ("tiny-hwktls", "tiny-rec", True)])
def test_check_passes_the_reference_output(cfg, mix, tls):
    config, traffic, sent = _case(cfg, mix)
    keys = _keys if tls else None
    wires = _wires(config, traffic, sent, keys)
    numbers, failed = reference.check(traffic, config, sent,
                                      lambda i, u: wires[i, u], keys)
    assert failed == 0 and all(v == 0 for v in numbers.values()), numbers


def test_one_flipped_body_byte_fails():
    config, traffic, sent = _case("tiny-l7route", "tiny-bulk")
    wires = _wires(config, traffic, sent)
    key = next(k for k, w in wires.items() if len(w))
    wires[key] = wires[key].copy()
    wires[key][-1] ^= 1
    numbers, failed = reference.check(traffic, config, sent,
                                      lambda i, u: wires[i, u])
    assert numbers["corrupt"] == 1 and failed == 1


def test_one_misrouted_request_fails():
    config, traffic, sent = _case("tiny-l7route", "tiny-bulk")
    wires = _wires(config, traffic, sent)
    i = 0
    u = reference.upstream(config, traffic.header(0, i))
    frame = traffic.frame(sent[i][-1], i)
    wires[i, u] = wires[i, u][:-len(frame)]
    wires[i, 1 - u] = np.concatenate([wires[i, 1 - u], frame])
    numbers, failed = reference.check(traffic, config, sent,
                                      lambda i_, u_: wires[i_, u_])
    assert numbers["misrouted"] == 1 and numbers["missing"] == 1
    assert failed == 1


def test_a_bad_record_tag_fails():
    config, traffic, sent = _case("tiny-hwktls", "tiny-rec")
    wires = _wires(config, traffic, sent, _keys)
    wires[0, 0] = wires[0, 0].copy()
    wires[0, 0][refcipher.REC_HEADER - 1] ^= 1      # the first record's tag
    numbers, _ = reference.check(traffic, config, sent,
                                 lambda i, u: wires[i, u], _keys)
    assert numbers["bad_tags"] == 1


def test_leaked_pages_fail():
    config, traffic, sent = _case("tiny-l7route", "tiny-bulk")
    wires = _wires(config, traffic, sent)
    numbers, _ = reference.check(traffic, config, sent,
                                 lambda i, u: wires[i, u], leaked_pages=3)
    assert numbers["leaked_pages"] == 3


@pytest.mark.parametrize("cfg,mix,tls", [("tiny-l7route", "tiny-bulk", False),
                                         ("tiny-hwktls", "tiny-rec", True)])
def test_int8_control_fails(cfg, mix, tls):
    config, traffic, sent = _case(cfg, mix)
    keys = _keys if tls else None
    numbers, failed = reference.check(
        traffic, config, sent,
        reference.control_wires(traffic, config, sent, keys), keys)
    n = sum(len(s) for s in sent)
    assert failed == n
    assert numbers["corrupt"] + numbers["bad_tags"] + numbers["missing"] >= n


def test_fused_round_bytes_ignore_the_layout():
    """The same requests count the same bytes whatever page size and
    metadata padding the round's operands use."""
    requests = [(283, 16384), (300, 65536), (291, 16101)]
    page_layouts = [(4096, 384), (128, 384), (256, 512)]
    counts = set()
    for page, meta_max in page_layouts:
        pps = max(-(-b // page) for _, b in requests)
        operand_bytes = len(requests) * (meta_max + pps * page) * 4
        n = fused_round_bytes(requests, tls=False)
        assert n < operand_bytes
        counts.add(n)
    assert counts == {2 * (283 + 300 + 291) + 3 * (16384 + 65536 + 16101)}
    assert fused_round_bytes(requests, tls=True) \
        - fused_round_bytes(requests, tls=False) == 2 * (16384 + 65536 + 16101)
