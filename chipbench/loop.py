"""The closed loop, as ``wrk -c N`` drives a proxy: each of N clients has
one request outstanding and sends its next one as soon as the proxy has
forwarded the previous one.

The window drives ``ProxyRuntime.step()``; after each step the clients
whose request was forwarded (their channel's ``ChannelStats.messages``
grew) send the next one with ``LibraSocket.deliver``. A request's latency
runs from its delivery to the end of the step that forwarded it: channels
forward in order, so a per-connection FIFO of delivery times is exact.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Callable, ContextManager, List, Tuple

import numpy as np

Annotate = Callable[[str], ContextManager]

#: steps a warm-up round may take before set-up gives up
WARMUP_MAX_STEPS = 64


@dataclasses.dataclass
class Window:
    t0: float = 0.0
    t1: float = 0.0
    cpu_s: float = 0.0
    attempted: int = 0
    completed: int = 0
    latencies_s: List[float] = dataclasses.field(default_factory=list)
    # (start, end) of every step, and the (connection, request) pairs each
    # step forwarded
    steps: List[Tuple[float, float]] = dataclasses.field(default_factory=list)
    forwarded: List[List[Tuple[int, int]]] = dataclasses.field(
        default_factory=list)
    client_s: float = 0.0     # clients' own work inside the window
    #: the requests set-up made ran out and closed the window before its
    #: seconds were up; the rate over the shorter window stays exact
    ran_out: bool = False

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class ClosedLoop:
    def __init__(self, proxy, traffic):
        self.proxy = proxy
        self.traffic = traffic
        n = traffic.connections
        self.fifo = [collections.deque() for _ in range(n)]
        self.next_k = [0] * n
        self.sent: List[List[int]] = [[] for _ in range(n)]
        self.done = proxy.messages()
        # spans around steps and the clients' work (a traced window sets it)
        self.annotate: Annotate = lambda _name: contextlib.nullcontext()

    def outstanding(self) -> int:
        return sum(len(f) for f in self.fifo)

    def deliver(self, i: int) -> None:
        k = self.next_k[i]
        parts = self.traffic.wire(k, i)
        sock = self.proxy.clients[i]
        t = time.perf_counter()
        for p in parts:
            sock.deliver(p)
        self.fifo[i].append((k, t))
        self.next_k[i] = k + 1
        self.sent[i].append(k)

    def collect(self, t_end: float) -> List[Tuple[int, int, float]]:
        """Requests the last step forwarded: ``(connection, request,
        latency_s)``. A count beyond what was outstanding is ignored here;
        the byte check catches whatever caused it."""
        now = self.proxy.messages()
        grew = np.flatnonzero(now != self.done)
        out = []
        for i in grew:
            for _ in range(max(int(now[i] - self.done[i]), 0)):
                if not self.fifo[i]:
                    break
                k, t = self.fifo[i].popleft()
                out.append((int(i), k, t_end - t))
        self.done = now
        return out

    def _step(self) -> Tuple[float, float, int]:
        t_s = time.perf_counter()
        with self.annotate("step"):
            progressed = self.proxy.runtime.step()
        return t_s, time.perf_counter(), progressed

    def warmup(self, rounds: int) -> None:
        """Serve ``rounds`` whole rounds before the window (the shapes the
        window uses compile here)."""
        for _ in range(rounds):
            for i in range(len(self.fifo)):
                self.deliver(i)
            for _ in range(WARMUP_MAX_STEPS):
                if not self.outstanding():
                    break
                _, t_e, _ = self._step()
                self.collect(t_e)
            if self.outstanding():
                raise RuntimeError(f"warm-up round left {self.outstanding()} "
                                   f"requests unforwarded")

    def window(self, seconds: float) -> Window:
        """Run the loop for ``seconds``; the window closes at the end of
        the first step that ends after that, or earlier at the end of the
        step that forwarded a client's last request made in set-up."""
        w = Window()
        cpu0 = time.process_time()
        w.t0 = time.perf_counter()
        with self.annotate("client"):
            for i in range(len(self.fifo)):
                self.deliver(i)
        w.attempted = len(self.fifo)
        while True:
            t_s, t_e, _ = self._step()
            w.steps.append((t_s, t_e))
            c0 = time.perf_counter()
            with self.annotate("client"):
                got = self.collect(t_e)
                w.forwarded.append([(i, k) for i, k, _ in got])
                w.latencies_s.extend(lat for _, _, lat in got)
                w.completed += len(got)
                w.ran_out = t_e - w.t0 < seconds and any(
                    self.next_k[i] >= self.traffic.rounds for i, _, _ in got)
                if t_e - w.t0 < seconds and not w.ran_out:
                    for i, _, _ in got:
                        self.deliver(i)
                    w.attempted += len(got)
            w.client_s += time.perf_counter() - c0
            if t_e - w.t0 >= seconds or w.ran_out:
                w.t1 = t_e
                break
        w.cpu_s = time.process_time() - cpu0
        return w

    def drain(self, w: Window, limit_s: float) -> None:
        """After the window: keep stepping, for at most ``limit_s``, until
        every request sent in it has been forwarded or the proxy stops
        making progress. Late requests count their wait in
        ``w.latencies_s``; they are not window throughput."""
        t0 = time.perf_counter()
        while self.outstanding() and time.perf_counter() - t0 < limit_s:
            _, t_e, progressed = self._step()
            got = self.collect(t_e)
            w.latencies_s.extend(lat for _, _, lat in got)
            if not progressed and not got:
                break
