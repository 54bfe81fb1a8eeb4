"""kTLS-analogue encrypted datapath: record layer, token cipher, sw/hw
modes through the socket facade, batched crypto rounds, and the fused
kernel's keystream operand."""
import contextlib
import hashlib
import struct
import tracemalloc

import numpy as np
import pytest

from repro.core import (
    CopyCounters,
    CryptoRecordParser,
    LibraStack,
    ProxyRuntime,
    build_chunked_message,
    build_delimited_message,
    build_message,
    crypto,
    open_record,
    open_stream,
    seal_record,
    trace,
)
from repro.core.crypto import (
    KS_BLOCK,
    KS_MASK,
    REC_HEADER,
    REC_MAGIC,
    TAG_SLOT,
    RecordAuthError,
    keystream,
    keystream_batch,
    xor_tokens,
)
from repro.core.parser import ChunkedParser, DelimiterParser, LengthPrefixedParser

RNG = np.random.default_rng(77)

BUILDERS = {
    "length-prefixed": build_message,
    "delimiter": build_delimited_message,
    "chunked": lambda m, p: build_chunked_message(
        [p[i : i + 24] for i in range(0, len(p), 24)]),
}


def _stack(**kw):
    kw.setdefault("n_shards", 4)
    kw.setdefault("pages_per_shard", 128)
    kw.setdefault("page_size", 16)
    kw.setdefault("secret", b"tls")
    return LibraStack(**kw)


# ---------------------------------------------------------------------------
# cipher primitives
# ---------------------------------------------------------------------------

def test_keystream_deterministic_and_span_resumable():
    key = b"k" * 16
    full = keystream(key, seq=9, n=100)
    assert full.dtype == np.int64
    assert full.min() >= 0 and full.max() <= KS_MASK   # int32-safe by design
    # any span regenerates independently (partial sends, §A.1 drains)
    parts = [keystream(key, 9, 13, 0), keystream(key, 9, 50, 13),
             keystream(key, 9, 37, 63)]
    assert np.array_equal(np.concatenate(parts), full)
    # different seq / key => different stream
    assert not np.array_equal(keystream(key, 10, 100), full)
    assert not np.array_equal(keystream(b"j" * 16, 9, 100), full)


def test_keystream_batch_matches_per_record_calls():
    keys = [b"a" * 16, b"b" * 16, b"a" * 16]
    seqs, lens, offs = [3, 4, 5], [17, 0, 40], [0, 2, 9]
    batched = keystream_batch(keys, seqs, lens, offsets=offs)
    for got, k, s, n, o in zip(batched, keys, seqs, lens, offs):
        assert np.array_equal(got, keystream(k, s, n, o))


_M64 = (1 << 64) - 1


def _oracle_seed(key, seq):
    """The record seed, straight from its definition (blake2b of seq)."""
    return struct.unpack("<Q", hashlib.blake2b(
        struct.pack("<q", seq), key=key, digest_size=8).digest())[0]


def _oracle_keystream(seed, n, offset):
    """Scalar splitmix64 in Python ints mod 2**64: token i is the top 31
    bits of splitmix64(seed + offset + i)."""
    toks = []
    for i in range(n):
        z = (seed + offset + i + 0x9E3779B97F4A7C15) & _M64
        z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & _M64
        z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _M64
        z ^= z >> 31
        toks.append((z >> 33) & KS_MASK)
    return np.array(toks, np.int64)


#: (len, offset) per record of one sweep
KAT_CASES = {
    "longer-than-a-block": [(65536, 0)],
    "block-boundaries": [(KS_BLOCK - 1, 0), (2, 5), (KS_BLOCK + 1, 0),
                         (KS_BLOCK, 3), (KS_BLOCK - 1, 1)],
    "zero-lengths": [(0, 0), (7, 0), (0, 4), (0, 0), (12, 1), (0, 9)],
    "offsets": [(100, 1), (50, KS_BLOCK - 1), (33, 1 << 40)],
}


@pytest.mark.parametrize("wrap", [False, True], ids=["seeds", "wrap-2**64"])
@pytest.mark.parametrize("case", sorted(KAT_CASES))
def test_keystream_known_answers_against_scalar_splitmix(
        monkeypatch, case, wrap):
    """Both entry points against an independent scalar oracle; ``wrap``
    puts every record seed within a few tokens of 2**64, so the indices
    wrap mod 2**64 inside the records."""
    records = KAT_CASES[case]
    keys = [bytes([65 + i % 3]) * 16 for i in range(len(records))]
    seqs = [11 + i for i in range(len(records))]
    seed_of = _oracle_seed
    if wrap:
        def seed_of(key, seq):
            return (_M64 - 2 - seq % 3) & _M64
        monkeypatch.setattr(crypto, "_record_seed", seed_of)
    lens = [n for n, _ in records]
    offs = [o for _, o in records]
    batched = keystream_batch(keys, seqs, lens, offsets=offs)
    assert len(batched) == len(records)
    for got, k, s, (n, o) in zip(batched, keys, seqs, records):
        want = _oracle_keystream(seed_of(k, s), n, o)
        assert got.dtype == np.int64
        assert np.array_equal(got, want)
        assert np.array_equal(keystream(k, s, n, o), want)


def test_keystream_literal_vector():
    """Tokens pinned from the whole-array implementation this one replaced."""
    want = [329788551, 204235295, 653698113, 1584559891, 1441911209,
            216831013, 2116752157, 1672633375, 1047021839, 658867411,
            664264769, 1244399173]
    assert keystream(b"k" * 16, 7, 12, 5).tolist() == want
    got = keystream_batch([b"j" * 16, b"k" * 16], [7, 7], [3, 12],
                          offsets=[0, 5])
    assert got[1].tolist() == want


def test_keystream_batch_peak_memory_is_its_output():
    """A sweep holds the output and block-sized scratch, never a
    temporary the size of the sweep (the whole-array sweep peaked at
    several times its output)."""
    lens = [16384] * 128
    keystream_batch([b"w" * 16], [0], [KS_BLOCK + 1])   # warm imports
    tracemalloc.start()
    try:
        kss = keystream_batch([b"m" * 16] * len(lens), range(len(lens)), lens)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert [len(k) for k in kss] == lens
    assert peak <= 8 * sum(lens) + 4 * 2 ** 20, peak


def test_keystream_sweep_opens_one_span_however_many_blocks(monkeypatch):
    names = []

    @contextlib.contextmanager
    def span(name):
        names.append(name)
        yield

    monkeypatch.setattr(trace, "span", span)
    keystream_batch([b"t" * 16] * 3, [1, 2, 3], [KS_BLOCK] * 3)
    assert names == ["tls.keystream"]
    keystream(b"t" * 16, 1, 3 * KS_BLOCK)
    keystream_batch([b"t" * 16], [1], [0])
    assert names == ["tls.keystream"]


def test_xor_cipher_is_involution_and_int32_safe():
    toks = RNG.integers(0, 2 ** 31 - 1, 64)
    ks = keystream(b"x" * 16, 1, 64)
    enc = xor_tokens(toks, ks)
    assert enc.max() < 2 ** 31          # ciphertext rides the int32 stream
    assert np.array_equal(xor_tokens(enc, ks), toks)


# ---------------------------------------------------------------------------
# record framing
# ---------------------------------------------------------------------------

def test_seal_open_roundtrip_all_inner_protocols():
    key = b"s" * 16
    cases = [
        (LengthPrefixedParser(), build_message(np.arange(5), RNG.integers(0, 9, 30))),
        (DelimiterParser(), build_delimited_message(np.arange(4), RNG.integers(0, 9, 20))),
        (ChunkedParser(), np.concatenate([[19, 6], RNG.integers(0, 9, 6)])),
    ]
    for parser, frame in cases:
        rec = seal_record(key, frame, parser, seq=7)
        assert int(rec[0]) == REC_MAGIC
        # ciphertext differs from plaintext (overwhelmingly likely)
        assert not np.array_equal(rec[REC_HEADER:], frame)
        got, used = open_record(key, rec)
        assert used == len(rec)
        assert np.array_equal(got, frame), parser.name


def test_crypto_record_parser_semantics():
    # header format: [REC_MAGIC, seq, inner_meta_len, payload_len, tag]
    p = CryptoRecordParser()
    assert p.parse(np.array([REC_MAGIC, 1])).need_more          # short header
    assert not p.parse(np.array([99, 0, 0, 0])).ok              # bad magic
    assert not p.parse(np.array([99, 0, 0, 0])).need_more
    assert not p.parse(np.array([REC_MAGIC, 1, -2, 5, 0])).ok   # bad lens
    r = p.parse(np.array([REC_MAGIC, 4, 2, 50, 0, 11, 12]))
    assert r.ok and r.meta_len == REC_HEADER + 2 and r.payload_len == 50
    # header present but inner metadata still arriving
    assert p.parse(np.array([REC_MAGIC, 4, 5, 50, 0, 11])).need_more


# ---------------------------------------------------------------------------
# scalar facade: sw / hw modes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["sw", "hw"])
def test_scalar_recv_forward_plaintext_identity(mode):
    stack = _stack()
    src = stack.socket("length-prefixed", tls=mode)
    dst = stack.socket("length-prefixed", tls=mode)
    frame = build_message(RNG.integers(100, 200, 5), RNG.integers(1000, 2000, 40))
    src.deliver(src.tls.seal(frame, src.parser.inner))
    buf, n = src.recv(1 << 20)
    # proxy sees the record header + DECRYPTED inner metadata + VPI
    assert int(buf[0]) == REC_MAGIC
    assert np.array_equal(buf[REC_HEADER : REC_HEADER + 3], frame[:3])
    assert n == REC_HEADER + 8 + 40     # record meta + payload, logical
    src.forward(dst, buf)
    got = open_stream(dst.tls.tx_key, dst.tx_wire())
    assert np.array_equal(got, frame)
    # the anchored payload crossed zero-copy in both modes; only sw paid
    # the separate §B.1 decrypt+encrypt passes
    c = stack.counters
    assert c.anchored == c.zero_copied == 40
    if mode == "sw":
        assert c.crypto_copied == 80    # one decrypt + one encrypt pass
        assert src.tls.stats["sw_decrypt_passes"] == 1
        assert dst.tls.stats["sw_encrypt_passes"] == 1
    else:
        assert c.crypto_copied == 0     # fused: zero extra passes


@pytest.mark.parametrize("mode", ["sw", "hw"])
def test_pool_holds_plaintext(mode):
    """Anchored ciphertext is decrypted exactly once, into the pool — the
    pool content is mode-independent plaintext (what a plaintext socket
    would have anchored)."""
    stack = _stack()
    src = stack.socket("length-prefixed", tls=mode)
    payload = RNG.integers(1000, 2000, 40)
    frame = build_message(np.arange(4), payload)
    src.deliver(src.tls.seal(frame, src.parser.inner))
    src.recv(1 << 20)
    (pages, ln), = src.connection.anchored.values()
    assert np.array_equal(stack.pool.read_payload(pages, ln), payload)


@pytest.mark.parametrize("mode", ["sw", "hw"])
def test_partial_encrypted_send_resumes_under_budget(mode):
    stack = _stack()
    src = stack.socket("length-prefixed", tls=mode)
    dst = stack.socket("length-prefixed", tls=mode)
    frame = build_message(RNG.integers(100, 200, 4), RNG.integers(1000, 2000, 40))
    src.deliver(src.tls.seal(frame, src.parser.inner))
    buf, _ = src.recv(1 << 20)
    sends = [src.forward(dst, buf, budget=13)]
    while dst.pending_send is not None:
        sends.append(dst.send(budget=13))
    assert all(s > 0 for s in sends) and len(sends) > 2
    got = open_stream(dst.tls.tx_key, dst.tx_wire())
    assert np.array_equal(got, frame)


@pytest.mark.parametrize("mode", ["sw", "hw"])
def test_short_record_full_copy_tx_resumes_under_budget(mode):
    """A record whose payload is under the admission threshold takes the
    native full-copy path end to end; the TX keystream must resume across
    budget-truncated chunks (TlsSession.tx_resume)."""
    stack = _stack()
    src = stack.socket("length-prefixed", tls=mode)
    dst = stack.socket("length-prefixed", tls=mode)
    frame = build_message(np.arange(4), np.array([7, 8, 9]))   # payload 3 < 8
    src.deliver(src.tls.seal(frame, src.parser.inner))
    buf, _ = src.recv(1 << 20)
    src.forward(dst, buf, budget=5)
    while dst.pending_send is not None:
        dst.send(budget=5)
    assert np.array_equal(open_stream(dst.tls.tx_key, dst.tx_wire()), frame)
    assert stack.counters.anchored == 0    # never touched the pool


@pytest.mark.parametrize("mode", ["sw", "hw"])
def test_exhaustion_drain_decrypts(mode):
    """§A.1 overflow on an encrypted record: the anchored prefix is
    impossible (pool too small), so the payload drains through the native
    copy path — decrypted span by span across several recv calls."""
    stack = _stack(n_shards=1, pages_per_shard=2)
    src = stack.socket("length-prefixed", tls=mode)
    frame = build_message(RNG.integers(100, 200, 4),
                          RNG.integers(1000, 2000, 80))   # 5 pages > 2-page pool
    src.deliver(src.tls.seal(frame, src.parser.inner))
    parts = [src.recv(1 << 6)[0]]                         # small buffer: drains
    while src.connection.rx_drain_remaining > 0:
        parts.append(src.recv(1 << 6)[0])
    got = np.concatenate(parts)
    assert np.array_equal(got[REC_HEADER:], frame)
    assert stack.counters.full_copied == 80


def test_record_spanning_ring_wrap():
    """A record delivered in dribbles after enough prior traffic that the
    RxRing slides/wraps mid-record: the zero-copy windows, residency gate
    and keystream offsets must all survive the buffer moving under them."""
    stack = _stack()
    src = stack.socket("length-prefixed", tls="hw")
    dst = stack.socket("length-prefixed", tls="hw")
    rng = np.random.default_rng(5)
    frames = []
    for _ in range(6):   # advance the ring head well past the origin
        f = build_message(rng.integers(100, 200, 4), rng.integers(1000, 2000, 24))
        frames.append(f)
        src.deliver(src.tls.seal(f, src.parser.inner))
        buf, _ = src.recv(1 << 20)
        src.forward(dst, buf)
    big = build_message(rng.integers(100, 200, 6), rng.integers(1000, 2000, 64))
    frames.append(big)
    rec = src.tls.seal(big, src.parser.inner)
    for i in range(0, len(rec), 7):
        src.deliver(rec[i : i + 7])
        # L7 gating, as the runtime does: only recv parseable+resident frames
        if not src.needs_more_data():
            buf, n = src.recv(1 << 20)
            if n:
                src.forward(dst, buf)
    got = open_stream(dst.tls.tx_key, dst.tx_wire())
    assert np.array_equal(got, np.concatenate(frames))


# ---------------------------------------------------------------------------
# sw/hw parity through the runtime (chunked + delimiter inner protocols)
# ---------------------------------------------------------------------------

def _run_proxy(tls, *, protos, batched, budget=None, recv_buf=1 << 20,
               n_chans=4, n_msgs=3, payload=72, seed=11):
    stack = _stack()
    rt = ProxyRuntime(stack, tick_every=8, batched=batched)
    rng = np.random.default_rng(seed)
    dsts, wants = [], []
    for i in range(n_chans):
        proto = protos[i % len(protos)]
        src = stack.socket(proto, tls=tls)
        dst = stack.socket(proto, tls=tls)
        rt.channel(src, dst, name=f"{proto}-{i}", budget=budget,
                   recv_buf=recv_buf)
        dsts.append(dst)
        frames = []
        for _ in range(n_msgs):
            msg = BUILDERS[proto](rng.integers(100, 200, 6),
                                  rng.integers(1000, 2000, payload))
            if proto == "chunked":
                # each chunk frame is its own record
                parser = ChunkedParser()
                pos, sub = 0, []
                while pos < len(msg):
                    r = parser.parse(msg[pos:])
                    end = pos + r.meta_len + r.payload_len
                    sub.append(msg[pos:end])
                    pos = end
                frames.extend(sub)
            else:
                frames.append(msg)
        wants.append(np.concatenate(frames))
        if tls:
            src.deliver(src.tls.seal_frames(frames, src.parser.inner))
        else:
            src.deliver(np.concatenate(frames))
    rt.run()
    plains = [open_stream(d.tls.tx_key, d.tx_wire()) if tls else d.tx_wire()
              for d in dsts]
    msgs = rt.messages_forwarded()
    snap = stack.counters.snapshot()
    crypto_copied = stack.counters.crypto_copied
    rt.shutdown()
    assert stack.alloc.free_pages == stack.alloc.total_pages
    return plains, wants, msgs, snap, crypto_copied


@pytest.mark.parametrize("batched", [False, True])
def test_sw_hw_parity_chunked_delimiter(batched):
    protos = ("chunked", "delimiter")
    plain, want_p, msgs_p, _, cc_p = _run_proxy(None, protos=protos,
                                                batched=batched)
    sw, want_s, msgs_s, _, cc_s = _run_proxy("sw", protos=protos,
                                             batched=batched)
    hw, want_h, msgs_h, _, cc_h = _run_proxy("hw", protos=protos,
                                             batched=batched)
    assert msgs_p == msgs_s == msgs_h
    for pw, sw_, hw_, want in zip(plain, sw, hw, want_p):
        # every regime forwards byte-identical plaintext
        assert np.array_equal(pw, want)
        assert np.array_equal(sw_, want)
        assert np.array_equal(hw_, want)
    assert cc_p == cc_h == 0 and cc_s > 0


def test_sw_hw_parity_under_budget_and_tiny_recv_buf():
    """Fragmented metadata (tiny recv_buf) and budget-truncated sends, both
    encrypted modes: the reassembly + keystream continuations compose."""
    protos = ("length-prefixed",)
    plain, want, msgs_p, _, _ = _run_proxy(None, protos=protos, batched=False,
                                           budget=20, recv_buf=9)
    for tls in ("sw", "hw"):
        got, _, msgs, _, _ = _run_proxy(tls, protos=protos, batched=False,
                                        budget=20, recv_buf=9)
        assert msgs == msgs_p
        for g, w in zip(got, want):
            assert np.array_equal(g, w), tls


def test_batched_matches_scalar_counters_per_mode():
    """Within each tls mode, the batched scheduler must copy exactly the
    tokens the scalar scheduler copies (sw batches nothing — it falls back
    per message — but the outcome and counters still match)."""
    for tls in (None, "sw", "hw"):
        _, _, msgs_s, snap_s, _ = _run_proxy(
            tls, protos=("length-prefixed", "delimiter"), batched=False)
        _, _, msgs_b, snap_b, _ = _run_proxy(
            tls, protos=("length-prefixed", "delimiter"), batched=True)
        assert msgs_s == msgs_b, tls
        assert snap_s == snap_b, tls


# ---------------------------------------------------------------------------
# batched data plane specifics
# ---------------------------------------------------------------------------

def test_recv_batch_excludes_sw_includes_hw():
    stack = _stack()
    sw = stack.socket("length-prefixed", tls="sw")
    hw = stack.socket("length-prefixed", tls="hw")
    plain = stack.socket("length-prefixed")
    frame = build_message(np.arange(4), RNG.integers(1000, 2000, 32))
    sw.deliver(sw.tls.seal(frame, sw.parser.inner))
    hw.deliver(hw.tls.seal(frame, hw.parser.inner))
    plain.deliver(frame)
    res = stack.recv_batch([sw, hw, plain])
    # sw must take the scalar decrypt-and-copy path (§B.1: software crypto
    # forfeits the fused batch); hw and plaintext ride the batch
    assert set(res) == {hw.fileno(), plain.fileno()}
    buf, n = sw.recv(1 << 20)
    assert n > 0 and stack.counters.crypto_copied == 32


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_recv_batch_kernel_impl_decrypts_like_host(impl):
    def load(stack):
        socks = []
        rng = np.random.default_rng(21)
        for _ in range(3):
            s = stack.socket("length-prefixed", tls="hw")
            f = build_message(rng.integers(100, 200, 5),
                              rng.integers(1000, 2000, 40))
            s.deliver(s.tls.seal(f, s.parser.inner))
            socks.append(s)
        return socks

    sh, sk = _stack(), _stack()
    rh = sh.recv_batch(load(sh), impl="host")
    rk = sk.recv_batch(load(sk), impl=impl)
    assert len(rh) == len(rk) == 3
    assert np.array_equal(sh.pool.data, sk.pool.data)   # plaintext, decrypted
    assert sh.counters.snapshot() == sk.counters.snapshot()
    for (bh, nh), (bk, nk) in zip(rh.values(), rk.values()):
        assert nh == nk
        assert np.array_equal(bh[:-1], bk[:-1])          # VPIs differ only


def test_kernel_keystream_operand_bit_exact_vs_crypto_oracle():
    from repro.kernels import ops, ref
    from repro.kernels.testing import selcopy_crypto_case

    rng = np.random.default_rng(31)
    for b, page, pps, meta_max in [(1, 8, 2, 8), (3, 16, 4, 16)]:
        stream, ml, tl, pool, tables, ks = selcopy_crypto_case(
            rng, b=b, page=page, pps=pps, meta_max=meta_max)
        want = ref.selective_copy_crypto_ref(stream, ml, tl, pool, tables,
                                             ks, meta_max=meta_max)
        for impl in ("ref", "interpret"):
            got = ops.selective_copy(stream, ml, tl, pool, tables,
                                     meta_max=meta_max, impl=impl,
                                     reserved_scratch=True, keystream=ks)
            assert np.array_equal(np.array(got[0]), np.array(want[0])), impl
            assert np.array_equal(np.array(got[1]), np.array(want[1])), impl


def test_mixed_plain_and_hw_sockets_share_one_batch():
    """One fused round over a mix of plaintext and encrypted sockets: the
    keystream sweep only covers the encrypted rows; everyone's plaintext
    lands in the pool."""
    stack = _stack()
    rng = np.random.default_rng(41)
    socks, payloads = [], []
    for i in range(4):
        tls = "hw" if i % 2 else None
        s = stack.socket("length-prefixed", tls=tls)
        p = rng.integers(1000, 2000, 32)
        f = build_message(rng.integers(100, 200, 4), p)
        s.deliver(s.tls.seal(f, s.parser.inner) if tls else f)
        socks.append(s)
        payloads.append(p)
    res = stack.recv_batch(socks)
    assert len(res) == 4
    for s, p in zip(socks, payloads):
        (pages, ln), = s.connection.anchored.values()
        assert np.array_equal(stack.pool.read_payload(pages, ln), p)


# ---------------------------------------------------------------------------
# per-record auth tag (truncated blake2b)
# ---------------------------------------------------------------------------

def _tampered_record(sock, frame, flip_at):
    """Seal a record toward ``sock`` and flip one ciphertext token."""
    rec = sock.tls.seal(frame, sock.parser.inner)
    rec = rec.copy()
    rec[flip_at] ^= 0b101
    return rec


def test_record_tag_is_31_bit_and_survives_proxy_reseal():
    """The tag authenticates the plaintext, so a proxy re-sealing the
    record under its TX key preserves it — the wire-side open (which
    verifies) accepts end-to-end proxied traffic."""
    stack = _stack()
    src = stack.socket("length-prefixed", tls="hw")
    dst = stack.socket("length-prefixed", tls="hw")
    frame = build_message(RNG.integers(100, 200, 5),
                          RNG.integers(1000, 2000, 40))
    rec = src.tls.seal(frame, src.parser.inner)
    assert 0 <= int(rec[TAG_SLOT]) <= KS_MASK
    src.deliver(rec)
    buf, _ = src.recv(1 << 20)
    src.forward(dst, buf)
    # open_stream verifies every record tag; a mismatch would raise
    got = open_stream(dst.tls.tx_key, dst.tx_wire())
    assert np.array_equal(got, frame)


@pytest.mark.parametrize("mode", ["sw", "hw"])
def test_scalar_recv_rejects_tampered_record_and_frees_pages(mode):
    """Tampered payload ciphertext: the RX verify (sw: on the decrypt
    pass; hw: the record-layer check before the fused scatter) rejects
    the record — nothing anchored, nothing delivered, stream advanced
    past it, and the socket keeps working for the next good record."""
    stack = _stack()
    src = stack.socket("length-prefixed", tls=mode)
    frame = build_message(RNG.integers(100, 200, 5),
                          RNG.integers(1000, 2000, 40))
    src.deliver(_tampered_record(src, frame, flip_at=REC_HEADER + 10))
    free0 = stack.alloc.free_pages
    with pytest.raises(RecordAuthError):
        src.recv(1 << 20)
    assert stack.alloc.free_pages == free0           # nothing anchored
    assert src.rx_available() == 0                   # record consumed
    assert src.tls.stats["auth_failures"] == 1
    assert stack.counters.snapshot() == CopyCounters().snapshot()
    # the connection recovers: the next good record flows normally
    good = build_message(RNG.integers(100, 200, 5),
                         RNG.integers(1000, 2000, 40))
    src.deliver(src.tls.seal(good, src.parser.inner))
    buf, n = src.recv(1 << 20)
    assert n == REC_HEADER + 8 + 40


def test_short_record_full_copy_path_rejects_tampering():
    """Records below the admission threshold ride the native full-copy
    path — the sw verify-on-decrypt still rejects tampering there."""
    stack = _stack()
    src = stack.socket("length-prefixed", tls="sw", min_payload=64)
    frame = build_message(RNG.integers(100, 200, 4),
                          RNG.integers(1000, 2000, 16))
    src.deliver(_tampered_record(src, frame, flip_at=REC_HEADER + 8))
    with pytest.raises(RecordAuthError):
        src.recv(1 << 20)
    assert src.rx_available() == 0
    src.deliver(src.tls.seal(frame, src.parser.inner))
    buf, n = src.recv(1 << 20)
    assert np.array_equal(buf[REC_HEADER:], frame)   # decrypted whole record


def test_batched_sweep_rejects_tampered_record_keeps_round_alive():
    """hw-kTLS batched round with one tampered record among good ones:
    the tag check folded into the keystream sweep drops ONLY the bad
    slot — its pages return to the freelist, its bytes are consumed —
    while the rest of the round anchors and delivers normally."""
    stack = _stack()
    socks, frames = [], []
    for i in range(4):
        s = stack.socket("length-prefixed", tls="hw")
        f = build_message(RNG.integers(100, 200, 5),
                          RNG.integers(1000, 2000, 40))
        socks.append(s)
        frames.append(f)
        if i == 2:
            s.deliver(_tampered_record(s, f, flip_at=REC_HEADER + 20))
        else:
            s.deliver(s.tls.seal(f, s.parser.inner))
    free0 = stack.alloc.free_pages
    results = stack.recv_batch(socks)
    good_fds = {s.fileno() for i, s in enumerate(socks) if i != 2}
    assert set(results) == good_fds
    assert socks[2].tls.stats["auth_failures"] == 1
    assert socks[2].rx_available() == 0              # bad record consumed
    # only the good records' pages stay anchored
    assert stack.alloc.free_pages == free0 - 3 * 3   # 40 tokens = 3 pages
    # good flows decrypted correctly (inner metadata surfaced plaintext)
    for i, s in enumerate(socks):
        if i == 2:
            continue
        buf, n = results[s.fileno()]
        assert np.array_equal(buf[REC_HEADER:-1], frames[i][:8])
        assert n == REC_HEADER + 8 + 40


def test_tampered_metadata_ciphertext_also_rejected():
    stack = _stack()
    src = stack.socket("length-prefixed", tls="hw")
    frame = build_message(RNG.integers(100, 200, 5),
                          RNG.integers(1000, 2000, 40))
    src.deliver(_tampered_record(src, frame, flip_at=REC_HEADER + 1))
    with pytest.raises(RecordAuthError):
        src.recv(1 << 20)
    assert src.tls.stats["auth_failures"] == 1


def test_partial_serve_of_resident_tampered_record_rejected():
    """A tiny user buffer serving only a prefix of a full-copy record must
    not leak tampered plaintext: the whole resident record is verified
    BEFORE any byte reaches the caller."""
    stack = _stack()
    src = stack.socket("length-prefixed", tls="sw", min_payload=64)
    frame = build_message(RNG.integers(100, 200, 4),
                          RNG.integers(1000, 2000, 16))
    src.deliver(_tampered_record(src, frame, flip_at=REC_HEADER + 9))
    with pytest.raises(RecordAuthError):
        src.recv(7)                       # buffer far smaller than record
    assert src.rx_available() == 0        # whole record consumed
    assert src.tls.stats["auth_failures"] == 1
    # and a good record still serves fine through a tiny buffer
    src.deliver(src.tls.seal(frame, src.parser.inner))
    buf, n = src.recv(7)
    assert n == 7 and np.array_equal(buf[REC_HEADER:7], frame[:2])
