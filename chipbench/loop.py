"""The two loops that drive a proxy: the closed loop, as ``wrk -c N``
drives one, and the open loop, as ``wrk2 -R`` drives one.

Closed (:class:`ClosedLoop`): each of N clients has one request
outstanding and sends its next one as soon as the proxy has forwarded the
previous one. A request's latency runs from its delivery to the end of the
step that forwarded it.

Open (:class:`OpenLoop`, a mix with ``arrival``): requests arrive on the
seeded schedule of :func:`chipbench.traffic.arrivals`, whatever the proxy
does. A connection carries one request at a time, in HTTP/1.1 order; an
arrival whose connection is busy waits in that client's queue. A
request's latency runs from its scheduled time to the end of the step
that forwarded it (wrk2's correction for coordinated omission), so the
wait in the client's queue counts.

Both drive ``ProxyRuntime.step()``; between steps the clients whose
request was forwarded (their channel's ``ChannelStats.messages`` grew)
deliver with ``LibraSocket.deliver``. Channels forward in order, so a
per-connection FIFO of start times is exact.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import math
import time
from typing import Callable, ContextManager, Iterable, List, Optional, Tuple

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
    #: latency of every request of the window (closed: sent in it; open:
    #: due in it), late ones included
    latencies_s: List[float] = dataclasses.field(default_factory=list)
    # (start, end) of every step, and the (connection, request) pairs each
    # step forwarded
    steps: List[Tuple[float, float]] = dataclasses.field(default_factory=list)
    forwarded: List[List[Tuple[int, int]]] = dataclasses.field(
        default_factory=list)
    client_s: float = 0.0     # clients' own work inside the window
    #: the requests (closed) or the schedule (open) set-up made ran out
    #: and closed the window before its seconds were up; the rate over the
    #: shorter window stays exact
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
        self.next_k[i] = k + 1
        self.sent[i].append(k)
        self._wire(i, k)

    def _wire(self, i: int, k: int, t: Optional[float] = None) -> None:
        """Put request ``k`` of connection ``i`` on the wire; its latency
        runs from ``t``, or from now."""
        parts = self.traffic.wire(k, i)
        sock = self.proxy.clients[i]
        t = time.perf_counter() if t is None else t
        for p in parts:
            sock.deliver(p)
        self.fifo[i].append((k, t))

    def collect(self) -> List[Tuple[int, int, float]]:
        """Requests the last step forwarded: ``(connection, request,
        start)``, with the time its latency runs from. A count beyond what
        was outstanding is ignored here; the byte check catches whatever
        caused it."""
        now = self.proxy.messages()
        grew = np.flatnonzero(now != self.done)
        out = []
        for i in grew:
            for _ in range(max(int(now[i] - self.done[i]), 0)):
                if not self.fifo[i]:
                    break
                k, t = self.fifo[i].popleft()
                out.append((int(i), k, t))
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
                self._step()
                self.collect()
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
                got = self.collect()
                w.forwarded.append([(i, k) for i, k, _ in got])
                w.latencies_s.extend(t_e - t for _, _, t in got)
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
            got = self.collect()
            w.latencies_s.extend(t_e - t for _, _, t in got)
            if not progressed and not got:
                break


class OpenLoop(ClosedLoop):
    """Requests arrive on ``traffic.arrivals``' schedule (see the module's
    docstring). A step runs only while a request is outstanding: an idle
    proxy is never stepped, the loop sleeps until the next arrival."""

    def __init__(self, proxy, traffic):
        super().__init__(proxy, traffic)
        self.sched = traffic.arrivals
        #: arrivals waiting for their busy connection: ``(request, due)``
        self.queue = [collections.deque() for _ in range(len(self.fifo))]
        self.origin = 0.0          # perf_counter the schedule starts at
        self.made = 0              # arrivals of the schedule made due
        self.until = math.inf      # no arrival due from here on is made
        self.freed: Iterable[int] = ()   # connections the last step freed
        self.paused = 0.0          # perf_counter the warm-up ended at

    def end(self) -> float:
        """The clock time the schedule ends at."""
        return self.origin + self.sched.span_s

    def _arrive(self, now: float) -> List[int]:
        """Make the arrivals due before ``now`` (and before
        :attr:`until`); returns their connections."""
        hi = int(np.searchsorted(self.sched.at,
                                 min(now, self.until) - self.origin))
        lo, self.made = self.made, max(hi, self.made)
        conns = self.sched.conn[lo:hi].tolist()
        for i, k, at in zip(conns, self.sched.k[lo:hi].tolist(),
                            self.sched.at[lo:hi].tolist()):
            self.queue[i].append((k, self.origin + at))
            self.sent[i].append(k)
        return conns

    def _clients(self) -> None:
        """The client phase: make due arrivals, then put the next request
        of every idle connection that has one waiting on the wire."""
        for i in set(self._arrive(time.perf_counter())) | set(self.freed):
            q = self.queue[i]
            if q and not self.fifo[i]:
                k, due = q.popleft()
                self._wire(i, k, due)
        self.freed = ()

    def _run(self, until: float, w: Optional[Window]) -> float:
        """Client phases and steps until the end of the first step ending
        at or after ``until``, or ``until`` itself where the proxy is idle
        then; returns that time. Records the steps in ``w``."""
        while True:
            c0 = time.perf_counter()
            with self.annotate("client"):
                self._clients()
            now = time.perf_counter()
            if w is not None:
                w.client_s += now - c0
            if not self.outstanding():
                if now >= until:
                    return now
                nxt = (self.origin + float(self.sched.at[self.made])
                       if self.made < len(self.sched.at) else math.inf)
                time.sleep(max(min(nxt, until) - now, 0.0))
                continue
            t_s, t_e, _ = self._step()
            got = self.collect()
            self.freed = [i for i, _, _ in got]
            if w is not None:
                w.steps.append((t_s, t_e))
                w.forwarded.append([(i, k) for i, k, _ in got])
                w.completed += len(got)
                w.latencies_s.extend(t_e - t for _, _, t in got
                                     if t >= w.t0)
            if t_e >= until:
                return t_e

    def warmup(self, rounds: int) -> None:
        """The closed loop's whole rounds (the full round's shape compiles
        there), then the schedule's first ``warmup_s``, untimed."""
        super().warmup(rounds)
        self.origin = time.perf_counter()
        self.paused = self._run(self.origin + self.sched.warmup_s, None)

    def window(self, seconds: float) -> Window:
        """Run the open loop for ``seconds``, or to the schedule's end
        where that comes first (``ran_out``). ``attempted`` counts the
        arrivals due in the window."""
        w = Window()
        cpu0 = time.process_time()
        w.t0 = time.perf_counter()
        # the schedule's clock stood still since the warm-up ended (the
        # set-up's log, a trace starting): the window starts from the
        # queue the warm-up left, however long that took
        self.origin += w.t0 - self.paused
        until = w.t0 + seconds
        w.ran_out = until > self.end()
        w.t1 = self._run(min(until, self.end()), w)
        w.cpu_s = time.process_time() - cpu0
        self.until = w.t1
        due = np.searchsorted(self.sched.at,
                              [w.t0 - self.origin, w.t1 - self.origin])
        w.attempted = int(due[1] - due[0])
        return w

    def drain(self, w: Window, limit_s: float) -> None:
        """After the window: deliver and forward, for at most ``limit_s``,
        every arrival due in it that is still waiting or outstanding. A
        late request counts its wait in ``w.latencies_s``; one never
        forwarded is missing from the reference's check."""
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < limit_s:
            self._clients()
            if not self.outstanding():
                break
            _, t_e, progressed = self._step()
            got = self.collect()
            self.freed = [i for i, _, _ in got]
            w.latencies_s.extend(t_e - t for _, _, t in got if t >= w.t0)
            if not progressed and not got:
                break
