"""One-chip smoke run of the Libra proxy datapath on a TPU.

Drives the system's main path through the entry points a user calls —
``LibraStack`` -> ``ProxyRuntime(batched=True,
batch_impl="fused-round:pallas")`` -> ``DevicePool.fused_round_device`` ->
the fused Pallas round kernel — at the size of one proxy worker in the
paper's Nginx/HAProxy setup: 256 concurrent client->backend connections,
4 length-prefixed messages each, a ~300-token header (the metadata, copied
to the host) and a 16 Ki- or 64 Ki-token body (anchored, never copied),
4096-token pages, and a device pool of 4096 pages that holds one full
round in flight (~64 MiB resident). Tokens stand for bytes, one int32
each. Two phases:

  (a) plaintext, with an L7 ``PolicyTable`` routing on a header field to
      two backends per connection;
  (b) hw-kTLS (``tls="hw"``) on both sides of every connection.

Each phase runs the same seeded traffic twice: through the fused device
round, and through the scalar full-copy reference (``batched=False``,
``batch_impl="host"``, sockets whose ``min_payload`` forces the standard
copy path). Every backend's received bytes must be identical (hw-kTLS
wires are compared after opening their records: session keys differ per
run), at least one fused round must have run, no round may have fallen
back off the device, and the pool must drain.

    python chip_smoke.py [--seed N]

Fails at once unless JAX's first device is a TPU. The last line of
standard output is ``{"ok": true, "device": {...}}``; any failed check
raises and exits non-zero. Only one chip is used: every worker's pool
lives on the default device, so no path spans chips yet.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

#: one proxy worker's deployment shape
N_CONNS = 256
MSGS_PER_CONN = 4
HEADER_TOKENS = (280, 300)        # metadata prefix, [MAGIC, lens] included
BODY_TOKENS = (16 * 1024, 64 * 1024)
PAGE = 4096
POOL_PAGES = N_CONNS * max(BODY_TOKENS) // PAGE   # one full round in flight

#: parse window: a ~300-token header does not fit the 256-token default
LOOKAHEAD = 512
#: first application-metadata slot after [MAGIC, len_meta, len_payload]
TAG = 3
#: the scalar reference's sockets never select: the standard copy path
FULL_COPY = 1 << 40


def make_traffic(rng: np.random.Generator, *, n_conns: int, n_msgs: int,
                 header=HEADER_TOKENS, bodies=BODY_TOKENS):
    """Per connection, ``n_msgs`` (metadata, payload) pairs of byte-valued
    tokens; the first metadata token is the routing tag."""
    out = []
    for _ in range(n_conns):
        msgs = []
        for _ in range(n_msgs):
            meta = rng.integers(0, 256, int(rng.integers(*header)) - TAG)
            body = rng.integers(0, 256, int(rng.choice(bodies)))
            msgs.append((meta, body))
        out.append(msgs)
    return out


def routing_table():
    """Tag-based L7 routing of every message to one of two backends."""
    from repro.core import PolicyTable, between, forward, rule

    return PolicyTable([rule(forward(0), between(TAG, 0, 127)),
                        rule(forward(1), between(TAG, 128, 255))])


def make_parser(tls):
    """Length-prefixed framing (in hw-kTLS records when ``tls``) with a
    parse window that covers the header."""
    from repro.core import CryptoRecordParser, LengthPrefixedParser

    inner = LengthPrefixedParser(lookahead=LOOKAHEAD)
    return CryptoRecordParser(inner=inner, lookahead=LOOKAHEAD) if tls \
        else inner


def run_proxy(traffic, *, impl: str, batched: bool, tls, policy: bool,
              page: int, pool_pages: int, full_copy: bool = False) -> dict:
    """Serve ``traffic`` through one fresh stack and return each backend's
    received plaintext plus the run's counters. Raises if the pool does
    not drain after shutdown."""
    from repro.core import LibraStack, ProxyRuntime, build_message

    stack = LibraStack(n_shards=1, pages_per_shard=pool_pages,
                       page_size=page, secret=b"chip-smoke")
    # batch_tile=0: the whole ready set is one device round (the adaptive
    # default sizes tiles for a host cache and would launch per message)
    rt = ProxyRuntime(stack, batched=batched, batch_impl=impl, batch_tile=0,
                      policy=routing_table() if policy else None)
    kw = dict(tls=tls, min_payload=FULL_COPY) if full_copy else dict(tls=tls)
    backends = []
    for i, msgs in enumerate(traffic):
        src = stack.socket(make_parser(tls), **kw)
        dsts = [stack.socket(make_parser(tls), **kw)
                for _ in range(2 if policy else 1)]
        rt.channel(src, dsts, name=f"conn{i}")
        backends.extend(dsts)
        frames = [build_message(m, p) for m, p in msgs]
        src.deliver(src.tls.seal_frames(frames, src.parser.inner) if tls
                    else np.concatenate(frames))
    t0 = time.perf_counter()
    rt.run()
    stack.pool.block_until_ready()
    wall_s = time.perf_counter() - t0
    wires = [d.tls.open_wire(d.tx_wire()) if tls else d.tx_wire()
             for d in backends]
    res = dict(wires=wires, messages=rt.messages_forwarded(),
               rounds=rt.rounds, wall_s=wall_s,
               fused_rounds=stack.pool.xfer["fused_rounds"],
               tx_spec_hits=stack.pool.xfer["tx_spec_hits"],
               device_fallbacks=stack.counters.device_fallbacks)
    rt.shutdown()
    if stack.alloc.free_pages != stack.alloc.total_pages:
        raise AssertionError(
            f"pool did not drain: {stack.alloc.free_pages} of "
            f"{stack.alloc.total_pages} pages free after shutdown")
    return res


def check_phase(name: str, traffic, *, impl: str, tls, policy: bool,
                page: int, pool_pages: int, compiles=None) -> dict:
    """One phase: the device run against the scalar full-copy reference.
    Raises on the first failed check; returns the device run's line."""
    kw = dict(tls=tls, policy=policy, page=page, pool_pages=pool_pages)
    want = run_proxy(traffic, impl="host", batched=False, full_copy=True,
                     **kw)
    before = dict(compiles) if compiles is not None else {}
    got = run_proxy(traffic, impl=impl, batched=True, **kw)
    n_msgs = sum(len(m) for m in traffic)
    if want["messages"] != n_msgs or got["messages"] != n_msgs:
        raise AssertionError(f"{name}: forwarded {got['messages']} (device) "
                             f"/ {want['messages']} (reference) of {n_msgs}")
    for k, (a, b) in enumerate(zip(got["wires"], want["wires"])):
        if not np.array_equal(a, b):
            raise AssertionError(f"{name}: backend {k} received different "
                                 f"bytes than the full-copy reference")
    if got["fused_rounds"] <= 0:
        raise AssertionError(f"{name}: no fused device round ran")
    if got["device_fallbacks"] != 0:
        raise AssertionError(f"{name}: {got['device_fallbacks']} rounds "
                             f"fell back off the device")
    line = {k: got[k] for k in ("messages", "rounds", "fused_rounds",
                                "tx_spec_hits", "device_fallbacks",
                                "wall_s")}
    if compiles is not None:
        line.update({k: compiles[k] - before.get(k, 0) for k in compiles})
    return line


def _count_compiles() -> dict:
    """Live counters of XLA compile requests (a request the persistent
    cache serves counts too) and of persistent-cache hits."""
    from jax import monitoring

    counts = {"compiles": 0, "cache_hits": 0}

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            counts["compiles"] += 1

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            counts["cache_hits"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    return counts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX's first device is "
              f"{dev.platform} ({dev.device_kind})", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.common.compile_cache import use_compile_cache

    cache = use_compile_cache(ROOT)
    compiles = _count_compiles()
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())} "
          f"compile cache: {cache}", flush=True)
    rng = np.random.default_rng(args.seed)
    phases = (("plaintext+policy", None, True), ("hw-ktls", "hw", False))
    for name, tls, policy in phases:
        traffic = make_traffic(rng, n_conns=N_CONNS, n_msgs=MSGS_PER_CONN)
        line = check_phase(name, traffic, impl="fused-round:pallas", tls=tls,
                           policy=policy, page=PAGE, pool_pages=POOL_PAGES,
                           compiles=compiles)
        print(f"phase {name}: " + " ".join(f"{k}={v}"
                                           for k, v in line.items())
              + f" device_kind={dev.device_kind}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
