"""One-kernel scheduling rounds: ``batch_impl='fused-round'`` must serve
the batched proxy datapath byte-, counter-, and verdict-identically to
the classic three-launch path (anchor, policy match, egress gather) —
across plaintext and hw-kTLS records, single stacks and 4-worker
clusters, budget-truncated sends, punted slow-path verdicts, and seeded
chaos — while collapsing the per-round launch count to one and landing
speculative TX gathers (``tx_spec_hits``). The kernel itself is pinned
bit-exact against :func:`repro.kernels.ref.fused_round_ref` across the
optional-operand matrix and the DMA-staged buffer depths."""
import numpy as np
import pytest

from repro.core import (
    ClusterRuntime,
    FaultPlan,
    LibraCluster,
    LibraStack,
    PolicyTable,
    ProxyRuntime,
    PythonPolicyRouter,
    between,
    build_message,
    drop,
    eq,
    forward,
    punt,
    rule,
)
from repro.core.crypto import REC_HEADER
from repro.core.policy import payload_at
from repro.kernels import ops, ref
from repro.kernels.testing import fused_round_case

STACK_KW = dict(n_shards=4, pages_per_shard=128, page_size=16)

#: app metadata starts after the [MAGIC, len_meta, len_payload] header
TAG = 3


def _stack(**kw):
    for k, v in STACK_KW.items():
        kw.setdefault(k, v)
    kw.setdefault("secret", b"fr")
    return LibraStack(**kw)


def _frames(n, seed=0, tags=(100, 200), payload=24):
    rng = np.random.default_rng(seed)
    return [build_message(np.concatenate([[rng.integers(*tags)],
                                          rng.integers(100, 200, 3)]),
                          rng.integers(1000, 2000, payload))
            for _ in range(n)]


def _table(tls=None):
    """Metadata route + payload-prefix route + drop: every round needs
    the full anchor + match + gather launch triple on the multi-pass
    path. Offsets shift past the record header under hw-kTLS."""
    off = (REC_HEADER if tls else 0) + TAG
    return PolicyTable([
        rule(drop(), between(off, 196, 199)),
        rule(forward(1), payload_at(0, 1950, 2000)),
        rule(forward(0), between(off, 100, 199)),
    ])


def _run(impl, *, tls=None, policy=False, n_chans=6, n_msgs=5, seed=2,
         batched=True, **rt_kw):
    """One proxy run; returns (decrypted wires, Fig. 9 snapshot, msgs,
    stack, rt). Wires are compared decrypted because TLS keys derive
    from per-process connection ids (ciphertext differs across runs)."""
    stack = _stack()
    rt = ProxyRuntime(stack, tick_every=32, batched=batched,
                      batch_impl=impl,
                      policy=_table(tls) if policy else None, **rt_kw)
    for i in range(n_chans):
        src = stack.socket("length-prefixed", tls=tls)
        dsts = [stack.socket("length-prefixed", tls=tls) for _ in range(2)]
        rt.channel(src, dsts, name=f"ch{i}")
        frames = _frames(n_msgs, seed=seed + i)
        wire = (src.tls.seal_frames(frames, src.parser.inner) if tls
                else np.concatenate(frames))
        src.deliver(wire)
    rt.run()
    wires = tuple(
        (d.tls.open_wire(d.tx_wire()) if tls else d.tx_wire()).tobytes()
        for ch in rt.channels for d in ch.dsts)
    snap = stack.counters.snapshot()
    msgs = rt.messages_forwarded()
    rt.shutdown()
    assert stack.alloc.free_pages == stack.alloc.total_pages
    return wires, snap, msgs, stack, rt


# ---------------------------------------------------------------------------
# kernel: interpret-mode bit-exactness vs the oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("crypto,policy,n_buffers", [
    (False, False, 0),
    (True, False, 0),
    (False, True, 0),
    (True, True, 2),
    (True, True, 4),
])
def test_fused_round_interpret_matches_oracle(crypto, policy, n_buffers):
    """``ops.fused_round(impl='interpret')`` is bit-exact against
    ``fused_round_ref`` — meta, pool, verdict, and gathered payload —
    including the DMA-staged buffer depths (the parity gate sweeps the
    full matrix; this pins the ops-layer entry point)."""
    rng = np.random.default_rng(23)
    case = fused_round_case(rng, b=2, page=8, pps=2, meta_max=8)
    base = (case["stream"], case["meta_len"], case["total_len"],
            case["pool"], case["tables"])
    kw = dict(meta_max=8)
    if crypto:
        kw.update(keystream=case["keystream"],
                  tx_keystream=case["tx_keystream"])
    if policy:
        kw.update(cond_off=case["cond_off"], cond_lo=case["cond_lo"],
                  cond_hi=case["cond_hi"], live=case["live"])
        if crypto:
            kw.update(meta_ks=case["meta_ks"])
    want = ref.fused_round_ref(*base, **kw)
    got = ops.fused_round(*base, impl="interpret", n_buffers=n_buffers,
                          **kw)
    for gi, wi, tag in zip(got, want, ("meta", "pool", "verdict", "out")):
        if wi is None:
            assert gi is None, tag
            continue
        assert np.array_equal(np.array(gi), np.array(wi)), tag


@pytest.mark.parametrize("impl", ["ref", "interpret"])
@pytest.mark.parametrize("entry", ["fused_round", "selective_copy"])
def test_row_view_streams_match_flat_streams(impl, entry):
    """The host builds the stream, RX and TX keystreams as their [B, 1, W]
    row view (no relayout on a TPU); every impl must give the same result
    as for the flat [B, W] operands, with the flat oracle as the truth."""
    rng = np.random.default_rng(29)
    case = fused_round_case(rng, b=3, page=8, pps=2, meta_max=8)
    base = (case["meta_len"], case["total_len"], case["pool"],
            case["tables"])
    flat = dict(keystream=case["keystream"])
    if entry == "fused_round":
        flat.update(tx_keystream=case["tx_keystream"],
                    cond_off=case["cond_off"], cond_lo=case["cond_lo"],
                    cond_hi=case["cond_hi"], live=case["live"],
                    meta_ks=case["meta_ks"])
        want = ref.fused_round_ref(case["stream"], *base, meta_max=8, **flat)
    else:
        want = ref.selective_copy_crypto_ref(
            case["stream"], *base, flat["keystream"], meta_max=8)
    rows = {k: (v[:, None, :] if k in ("keystream", "tx_keystream") else v)
            for k, v in flat.items()}
    extra = {} if entry == "fused_round" else dict(reserved_scratch=True)
    got = getattr(ops, entry)(case["stream"][:, None, :], *base, meta_max=8,
                              impl=impl, **rows, **extra)
    for gi, wi in zip(got, want):
        if wi is None:
            assert gi is None
            continue
        assert np.array_equal(np.array(gi), np.array(wi))


# ---------------------------------------------------------------------------
# datapath identity: single stack, plaintext / hw-kTLS × policy, + scalar
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tls", [None, "hw"])
@pytest.mark.parametrize("policy", [False, True])
def test_fused_round_identity_single_stack(tls, policy):
    """The one-kernel round forwards the exact bytes, Fig. 9 counters,
    and message count of the three-launch batched path AND the scalar
    schedule — plaintext and hw-kTLS, with and without the L7 table."""
    fused = _run("fused-round:ref", tls=tls, policy=policy)
    multi = _run("ref", tls=tls, policy=policy)
    scalar = _run("host", tls=tls, policy=policy, batched=False)
    assert fused[0] == multi[0] == scalar[0]
    assert fused[1] == multi[1] == scalar[1]
    assert fused[2] == multi[2] == scalar[2]
    # the fused rounds actually ran on the device plane (policy rounds
    # with no table still fuse anchor + gather)
    assert fused[3].pool.xfer["fused_rounds"] > 0


def test_fused_round_payload_prefix_matches_python_router():
    """The payload-prefix condition inside the fused kernel routes
    identically to the naive Python interpreter peeking the anchored
    first-page window — the full offload round-trip for satellite #3."""
    def run(offloaded):
        stack = _stack()
        src = stack.socket("length-prefixed")
        dsts = [stack.socket("length-prefixed") for _ in range(2)]
        t = _table()
        if offloaded:
            rt = ProxyRuntime(stack, policy=t, batched=True,
                              batch_impl="fused-round:ref")
            rt.channel(src, dsts)
        else:
            rt = ProxyRuntime(stack, batched=True)
            pr = PythonPolicyRouter(t, dsts, parser=src.parser,
                                    stack=stack, src=src)
            rt.channel(src, dsts, rewrite=pr.rewrite, router=pr.router)
        for f in _frames(16, seed=7, payload=12):
            src.deliver(f)
        rt.run()
        s = t.summary()
        s.pop("rounds")
        s.pop("buckets")
        return ([d.tx_wire().tolist() for d in dsts],
                stack.counters.snapshot(), s)

    off, py = run(True), run(False)
    assert off == py
    # backend 1 actually received payload-routed traffic
    assert len(off[0][1]) > 0


# ---------------------------------------------------------------------------
# launch accounting + TX speculation
# ---------------------------------------------------------------------------

def test_fused_round_is_one_launch_and_speculates_tx():
    """3 → 1 launches per round by construction: the fused path's device
    launches are exactly its fused rounds (no separate anchor / match /
    gather passes), strictly fewer than the multi-pass path's, and the
    speculative TX-encrypted gather lands (``tx_spec_hits``) so egress
    costs no extra launch either."""
    fused = _run("fused-round:ref", tls="hw", policy=True)
    multi = _run("ref", tls="hw", policy=True)
    fx, mx = fused[3].pool.xfer, multi[3].pool.xfer
    assert fx["fused_rounds"] > 0
    assert fx["device_rounds"] == fx["fused_rounds"]     # one launch/round
    assert fx["policy_match_rounds"] == 0                # folded in
    assert fx["tx_spec_hits"] > 0                        # egress rode along
    launches_fused = fx["device_rounds"] + fx["policy_match_rounds"]
    launches_multi = mx["device_rounds"] + mx["policy_match_rounds"]
    assert launches_multi > launches_fused
    # no bounce was needed to serve this workload
    assert fused[3].counters.device_fallbacks == 0


# ---------------------------------------------------------------------------
# budget truncation + punt slow path
# ---------------------------------------------------------------------------

def test_fused_round_budget_truncation_identity():
    """A channel send budget truncates messages mid-flight (continued on
    later rounds): the fused path must replay the exact same partial-send
    schedule and bytes as the multi-pass path."""
    def run(impl):
        stack = _stack()
        rt = ProxyRuntime(stack, tick_every=32, batched=True,
                          batch_impl=impl)
        src, dst = stack.socket_pair()
        ch = rt.channel(src, dst, budget=20)
        for f in _frames(8, seed=4, payload=40):
            src.deliver(f)
        rt.run()
        out = (dst.tx_wire().tobytes(), stack.counters.snapshot(),
               ch.stats.messages, ch.stats.partial_sends)
        rt.shutdown()
        return out

    fused, multi = run("fused-round:ref"), run("ref")
    assert fused == multi
    assert fused[3] > 0                 # the budget actually truncated


def test_fused_round_punt_slow_path_identity():
    """PUNT verdicts leave the fused round for the per-message Python
    slow path; byte/counter/stats identity must survive the detour."""
    off = TAG
    table = PolicyTable([
        rule(punt(), between(off, 150, 199)),
        rule(forward(0), between(off, 0, 10 ** 6)),
    ])

    def run(impl):
        stack = _stack()
        rt = ProxyRuntime(stack, tick_every=32, batched=True,
                          batch_impl=impl, policy=table.clone())
        src = stack.socket("length-prefixed")
        dsts = [stack.socket("length-prefixed") for _ in range(2)]
        rt.channel(src, dsts)
        for f in _frames(12, seed=6):
            src.deliver(f)
        rt.run()
        punts = stack.counters.policy_punts
        out = (tuple(d.tx_wire().tobytes() for d in dsts),
               stack.counters.snapshot(), punts)
        rt.shutdown()
        return out

    fused, multi = run("fused-round:ref"), run("ref")
    assert fused == multi
    assert fused[2] > 0                 # the punt path was exercised


# ---------------------------------------------------------------------------
# chaos: seeded FaultPlan replays identically across impls
# ---------------------------------------------------------------------------

def test_fused_round_chaos_identity_under_fault_plan():
    """The same seeded FaultPlan (EAGAIN storm + a reset + early
    corruption) fires the same events against the fused and multi-pass
    rounds — final wires, channel stats, and fired-event logs agree, and
    no pool page leaks through the retry/drop machinery."""
    def run(impl):
        stack = _stack()
        plan = (FaultPlan(seed=11)
                .eagain(0, start=1, until=9, p=0.6)
                .reset(1, at=4)
                .corrupt(p=0.3, start=0, until=2))
        rt = ProxyRuntime(stack, tick_every=8, batched=True,
                          batch_impl=impl, fault_plan=plan)
        src = stack.socket("length-prefixed")
        d0, d1 = (stack.socket("length-prefixed"),
                  stack.socket("length-prefixed"))
        ch = rt.channel(src, [d0, d1], max_retries=4, retry_timeout=64)
        for f in _frames(8, seed=3):
            src.deliver(f)
        rt.run()
        out = (list(plan.log), plan.summary(),
               (ch.stats.messages, ch.stats.retries, ch.stats.timeouts),
               d0.tx_wire().tobytes(), d1.tx_wire().tobytes())
        rt.shutdown()
        assert stack.alloc.free_pages == stack.alloc.total_pages
        return out

    assert run("fused-round:ref") == run("ref")


# ---------------------------------------------------------------------------
# 4-worker cluster identity
# ---------------------------------------------------------------------------

def test_fused_round_identity_four_worker_cluster():
    """Per-worker fused rounds on a 4-worker cluster: backend bytes,
    aggregated counters, and policy telemetry equal the multi-pass
    cluster run, with every page drained."""
    def run(impl):
        cl = LibraCluster(4, secret=b"frc", **STACK_KW)
        crt = ClusterRuntime(cl, policy=_table(), batched=True,
                             batch_impl=impl, tick_every=32)
        outs = []
        rng = np.random.default_rng(9)
        for i in range(8):
            w = cl.workers[i % 4]
            src = w.socket("length-prefixed")
            dsts = [w.socket("length-prefixed") for _ in range(2)]
            crt.runtimes[i % 4].channel(src, dsts, name=f"ch{i}")
            outs.append(dsts)
            for f in _frames(4, seed=int(rng.integers(1 << 30))):
                src.deliver(f)
        crt.run()
        wires = tuple(d.tx_wire().tobytes() for dsts in outs for d in dsts)
        agg = cl.counters_aggregate()
        summ = crt.policy_summary()["aggregate"]
        fused_rounds = sum(w.pool.xfer["fused_rounds"] for w in cl.workers)
        assert cl.pages_in_use == 0
        return wires, agg.snapshot(), summ, fused_rounds

    fw, fs, fp, fr = run("fused-round:ref")
    mw, ms, mp, mr = run("ref")
    assert fw == mw and fs == ms and fp == mp
    assert fr > 0 and mr == 0           # only the fused impl fuses
