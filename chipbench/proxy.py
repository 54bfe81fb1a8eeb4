"""The system under test, built from a configuration: one proxy worker
(``LibraStack`` + ``ProxyRuntime``) with a client socket and its upstream
sockets per connection. The only module of the benchmark that imports the
program.

Configuration keys read here: ``page_bytes``, ``pool_pages``,
``batch_impl``, ``batch_tile``, ``parser_lookahead``, ``tls`` (null or
``"hw"``), ``upstreams_per_connection`` and ``routing`` (``slot`` and
``rules`` of ``upstream``, ``lo``, ``hi``: first match wins).
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass
class Proxy:
    stack: object
    runtime: object
    clients: List[object]            # the proxy's socket per client
    upstreams: List[List[object]]    # the proxy's upstream sockets per client

    def messages(self) -> np.ndarray:
        """Requests each connection has forwarded so far."""
        return np.fromiter((ch.stats.messages for ch in self.runtime.channels),
                           np.int64, count=len(self.runtime.channels))

    def received(self, i: int, u: int) -> np.ndarray:
        """Everything upstream ``u`` of connection ``i`` was sent."""
        return self.upstreams[i][u].tx_wire()

    def pages_in_use(self) -> int:
        return self.stack.alloc.total_pages - self.stack.alloc.free_pages


def _parser(config: dict):
    from repro.core import CryptoRecordParser, LengthPrefixedParser

    look = int(config["parser_lookahead"])
    inner = LengthPrefixedParser(lookahead=look)
    if config.get("tls"):
        return CryptoRecordParser(inner=inner, lookahead=look)
    return inner


def _policy(config: dict):
    from repro.core import PolicyTable, between, forward, rule

    routing = config.get("routing")
    if not routing:
        return None
    slot = int(routing["slot"])
    return PolicyTable([rule(forward(int(r["upstream"])),
                             between(slot, int(r["lo"]), int(r["hi"])))
                        for r in routing["rules"]])


def build(config: dict, connections: int, secret: bytes) -> Proxy:
    from repro.core import LibraStack, ProxyRuntime

    stack = LibraStack(n_shards=1, pages_per_shard=int(config["pool_pages"]),
                       page_size=int(config["page_bytes"]), secret=secret)
    rt = ProxyRuntime(stack, batched=True, batch_impl=config["batch_impl"],
                      batch_tile=int(config["batch_tile"]),
                      policy=_policy(config))
    tls = config.get("tls")
    n_up = int(config.get("upstreams_per_connection", 1))
    clients, ups = [], []
    for i in range(connections):
        src = stack.socket(_parser(config), tls=tls)
        dsts = [stack.socket(_parser(config), tls=tls) for _ in range(n_up)]
        rt.channel(src, dsts, name=f"conn{i}")
        clients.append(src)
        ups.append(dsts)
    return Proxy(stack, rt, clients, ups)
