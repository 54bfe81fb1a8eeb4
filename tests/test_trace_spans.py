"""Host spans of the socket datapath (``repro.core.trace``) and the TX
speculation counters, on the fused round's jnp oracle at a tiny size.

The spans are recorded by patching ``trace.span``, the one function every
call site goes through. A scheduling round opens one ``runtime.step``, one
``stack.recv_batch`` and one ``stack.forward_batch``, with the phase spans
nested inside them, and the same number of spans whatever the number of
connections: spans are per round, never per message. Rounds use at least
12 connections so a speculation-miss gather is above the host shortcut's
row count and takes the device path (``pool.*`` spans)."""
import contextlib
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import (
    LibraStack,
    PolicyTable,
    ProxyRuntime,
    between,
    build_message,
    forward,
    rule,
    trace,
)
from repro.core.crypto import REC_HEADER

SRC = Path(__file__).resolve().parents[1] / "src"
#: header slot (after the 3-token frame prefix) that routes a request
TAG = 3
ROUNDS = 3
#: (tls, upstreams per connection)
SHAPES = [(None, 1), (None, 2), ("hw", 1), ("hw", 2)]
RX_PHASES = {"rx.admit", "rx.stage", "rx.verdicts", "rx.scatter",
             "tls.rx_open"}
TX_PHASES = {"tx.prepare", "tx.stage", "tx.transmit"}


class _Node:
    def __init__(self, name, parent):
        self.name = name
        self.parent = parent
        self.children = []

    def walk(self):
        yield self
        for c in self.children:
            yield from c.walk()

    def ancestors(self):
        p = self.parent
        while p is not None:
            yield p.name
            p = p.parent


class _Recorder:
    """Records every span as a tree node, top-level spans in ``roots``."""

    def __init__(self):
        self.roots = []
        self._open = []

    @contextlib.contextmanager
    def span(self, name):
        node = _Node(name, self._open[-1] if self._open else None)
        (node.parent.children if node.parent else self.roots).append(node)
        self._open.append(node)
        try:
            yield
        finally:
            self._open.pop()


def _proxy(n_conns, tls, upstreams, seed=0):
    """A proxy of ``n_conns`` connections, ``ROUNDS`` requests delivered on
    each; with two upstreams a table routes on the tag, half of the
    connections each way, while the runtime hints every flow's first
    upstream."""
    stack = LibraStack(n_shards=1, pages_per_shard=12 * n_conns,
                       page_size=16, secret=b"spans")
    off = (REC_HEADER if tls else 0) + TAG
    policy = PolicyTable([rule(forward(0), between(off, 0, 127)),
                          rule(forward(1), between(off, 128, 255))]) \
        if upstreams == 2 else None
    rt = ProxyRuntime(stack, batched=True, batch_impl="fused-round:ref",
                      batch_tile=0, policy=policy)
    rng = np.random.default_rng(seed)
    for i in range(n_conns):
        src = stack.socket("length-prefixed", tls=tls)
        dsts = [stack.socket("length-prefixed", tls=tls)
                for _ in range(upstreams)]
        rt.channel(src, dsts)
        tag = 64 if i % 2 == 0 else 192
        frames = [build_message(np.concatenate([[tag],
                                                rng.integers(0, 256, 5)]),
                                rng.integers(0, 256, 40))
                  for _ in range(ROUNDS)]
        src.deliver(src.tls.seal_frames(frames, src.parser.inner) if tls
                    else np.concatenate(frames))
    return stack, rt


def _traced_rounds(monkeypatch, n_conns, tls, upstreams):
    """Run ``ROUNDS`` scheduling rounds under the recorder; returns the
    recorded ``runtime.step`` trees and the stack."""
    rec = _Recorder()
    monkeypatch.setattr(trace, "span", rec.span)
    stack, rt = _proxy(n_conns, tls, upstreams)
    for _ in range(ROUNDS):
        assert rt.step() == n_conns
    assert rt.messages_forwarded() == n_conns * ROUNDS
    assert stack.pool.xfer["fused_rounds"] == ROUNDS
    assert stack.counters.device_fallbacks == 0
    rt.shutdown()
    assert stack.alloc.free_pages == stack.alloc.total_pages
    return rec.roots, stack


@pytest.mark.parametrize("tls,upstreams", SHAPES)
def test_each_round_opens_one_step_and_two_calls_with_phases_inside(
        monkeypatch, tls, upstreams):
    roots, _ = _traced_rounds(monkeypatch, 12, tls, upstreams)
    assert [r.name for r in roots] == ["runtime.step"] * ROUNDS
    for step in roots:
        names = [n.name for n in step.walk()]
        assert names.count("runtime.step") == 1
        assert names.count("stack.recv_batch") == 1
        assert names.count("stack.forward_batch") == 1
        for node in step.walk():
            if node.name in RX_PHASES:
                assert "stack.recv_batch" in node.ancestors(), node.name
            if node.name in TX_PHASES:
                assert "stack.forward_batch" in node.ancestors(), node.name
            if node.name.startswith(("pool.", "tls.")):
                assert {"stack.recv_batch", "stack.forward_batch"} \
                    & set(node.ancestors()), node.name
        # the fused round's device call; with two upstreams half of the
        # round misses its speculation and the forward gathers on device
        recv, fwd = (next(n for n in step.walk() if n.name == c)
                     for c in ("stack.recv_batch", "stack.forward_batch"))
        assert "pool.wait" in {n.name for n in recv.walk()}
        assert ("pool.wait" in {n.name for n in fwd.walk()}) \
            == (upstreams == 2)


@pytest.mark.parametrize("tls,upstreams", SHAPES)
def test_span_count_per_round_does_not_grow_with_connections(
        monkeypatch, tls, upstreams):
    counts = {}
    for n_conns in (12, 24):
        roots, _ = _traced_rounds(monkeypatch, n_conns, tls, upstreams)
        counts[n_conns] = [sum(1 for _ in r.walk()) for r in roots]
    assert counts[12] == counts[24]
    assert max(counts[12]) <= 24


@pytest.mark.parametrize("tls,upstreams", SHAPES)
def test_record_layer_spans_only_with_hw_ktls(monkeypatch, tls, upstreams):
    roots, _ = _traced_rounds(monkeypatch, 12, tls, upstreams)
    names = {n.name for r in roots for n in r.walk()}
    assert ({"tls.rx_open", "tls.keystream"} <= names) == (tls == "hw")
    assert any(n.startswith("tls.") for n in names) == (tls == "hw")


@pytest.mark.parametrize("tls", [None, "hw"])
def test_tx_speculation_misses_are_counted(monkeypatch, tls):
    """Hinted to each flow's first upstream, half of a two-upstream round
    misses; with one upstream every speculation is used."""
    _, two = _traced_rounds(monkeypatch, 12, tls, 2)
    x = two.pool.xfer
    assert x["tx_spec_hits"] == x["tx_spec_misses"] == 6 * ROUNDS
    _, one = _traced_rounds(monkeypatch, 12, tls, 1)
    x = one.pool.xfer
    assert x["tx_spec_misses"] == 0
    assert x["tx_spec_hits"] == 12 * ROUNDS


def test_importing_the_core_does_not_import_jax():
    """The span factory imports JAX on the first span, not on import."""
    code = "import sys, repro.core; print('jax' in sys.modules)"
    env = dict(os.environ, PYTHONPATH=str(SRC))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"
