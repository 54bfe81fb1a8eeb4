"""The one traffic generator: reads a mix (``mixes/<name>.json``) and a
configuration and makes, from the seed, every request of every connection,
and for an open loop the schedule on which the requests arrive.

The configuration gives ``connections`` (clients, each with one request
outstanding) and ``routing``: where it routes, each connection keeps one
tag (its virtual host) in the header byte ``routing["slot"]``, chosen so
the connections spread evenly over the upstreams; where it is null, no
byte is set.

Mix keys:

* ``header_bytes``: ``[lo, hi]``, the whole header (the 3-byte frame
  prefix included), drawn uniformly per request;
* ``body_bytes``: body sizes; every round gives each size an equal share of
  the connections, in a seeded order, so every seed does the same work;
  or ``frame_bytes``: header plus body is this many bytes (a TLS record's
  plaintext);
* ``warmup_rounds``: rounds served before the window, untimed;
* ``max_rounds_per_s``: set-up makes (and, under hw-kTLS, seals) the
  requests of this many closed-loop rounds per second of window, a margin
  over the measured rate. Nothing is made inside the window: a run that
  uses them up closes its window early (:class:`chipbench.loop.Window`
  ``ran_out``). A mix with ``arrival`` does without it;
* ``arrival`` (optional; without it the loop is closed): ``{"kind":
  "open", "rate_msgs_per_s": r, "warmup_s": w, "on_s": a, "off_s": b}``
  (checked by :func:`chipbench.spec.check_arrival`), an open loop
  (:class:`chipbench.loop.OpenLoop`) scheduled as ``wrk2 -R r`` schedules
  one: each of the N connections sends at the constant rate ``r / N``, its
  request ``k`` due at ``(k + phase) * N / r``, with the phase drawn from
  the seed, uniform over one interval; ``w`` seconds of it run untimed
  before the window. With ``on_s`` and ``off_s`` (both or neither null)
  the schedule runs only in the ``a``-second on periods, at ``r * (a + b)
  / a`` per second, so the mean stays ``r``: the bursty arrivals of
  BurstGPT (arXiv:2401.17644). :func:`arrivals` makes the schedule in
  set-up from its own generator, apart from the requests' draws, for ``w``
  seconds and the window plus :data:`ARRIVAL_MARGIN_S`.

Bytes are int64 tokens of value 0..255, as the proxy carries them. Bodies
are views into one seeded byte pool; headers are built when delivered.
Under hw-kTLS (``config["tls"] == "hw"``) every request is sealed into one
record (:meth:`Traffic.seal`).
"""
from __future__ import annotations

import dataclasses
import math
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional, Tuple

import numpy as np

from chipbench import refcipher

_BODY_POOL_TOKENS = 1 << 22
#: threads that seal records, a round each (hashing and numpy release the
#: interpreter lock): sealing is most of the hw-kTLS cell's set-up
SEAL_THREADS = 4
#: arrivals made beyond the warm-up's and the window's seconds: the window
#: closes at the end of the first step ending after its seconds, and starts
#: at the end of the first warm-up step ending after the warm-up's
ARRIVAL_MARGIN_S = 5.0


def routing_rules(config: dict) -> List[dict]:
    return (config.get("routing") or {}).get("rules", [])


@dataclasses.dataclass
class Arrivals:
    """An open loop's schedule: arrival ``j`` is due ``at[j]`` seconds
    after the open loop starts, on connection ``conn[j]``, as that
    connection's request ``k[j]``. ``at`` is sorted and ends before
    ``span_s``."""
    at: np.ndarray
    conn: np.ndarray
    k: np.ndarray
    warmup_s: float
    span_s: float


def arrivals(arrival: dict, connections: int, first_k: int, seed: int,
             seconds: float) -> Arrivals:
    """The seeded schedule of a mix's ``arrival`` for a window of
    ``seconds``. Connections number their requests from ``first_k`` on."""
    rng = np.random.default_rng([seed, 1])
    rate = float(arrival["rate_msgs_per_s"])
    warm = float(arrival["warmup_s"])
    span = warm + seconds + ARRIVAL_MARGIN_S
    on, off = arrival["on_s"], arrival["off_s"]
    if on is None:
        on, off = span, 0.0
    on, off = float(on), float(off)
    cycles = math.floor(span / (on + off))
    on_total = cycles * on + min(span - cycles * (on + off), on)
    # each connection's constant interval on the on periods' own clock,
    # then the off periods put between them
    gap = connections * on / (rate * (on + off))
    phase = rng.uniform(0.0, 1.0, connections)
    t = (np.arange(math.ceil(on_total / gap) + 1) + phase[:, None]) * gap
    conn, k = np.nonzero(t < on_total)
    t = t[conn, k]
    order = np.argsort(t, kind="stable")
    t, conn, k = t[order], conn[order], k[order]
    at = np.floor(t / on) * (on + off) + np.mod(t, on)
    return Arrivals(at, conn, first_k + k, warm, span)


class Traffic:
    """Every request of one run, made in set-up from the seed."""

    def __init__(self, mix: dict, config: dict, seed: int, seconds: float):
        rng = np.random.default_rng(seed)
        n = self.connections = int(config["connections"])
        lo, hi = mix["header_bytes"]
        max_body = int(mix["frame_bytes"]) - lo if "frame_bytes" in mix \
            else max(mix["body_bytes"])
        self.body_pool = rng.integers(
            0, 256, _BODY_POOL_TOKENS + max_body, dtype=np.int64)
        routing = config.get("routing")
        #: ``(slot, tag of each connection)`` where the configuration routes
        self.tags = None if not routing else (
            int(routing["slot"]), self._tags(rng, routing["rules"], n))
        warm = int(mix["warmup_rounds"])
        #: an open loop's schedule; None for a closed loop
        self.arrivals = None if "arrival" not in mix else arrivals(
            mix["arrival"], n, warm, seed, seconds)
        #: the requests set-up makes per connection; the window never
        #: makes more
        self.rounds = warm + 1 + (
            math.ceil(seconds * float(mix["max_rounds_per_s"]))
            if self.arrivals is None
            else int(np.bincount(self.arrivals.conn, minlength=n).max()))
        shape = (self.rounds, n)
        self.head_len = rng.integers(lo, hi + 1, size=shape)
        if "frame_bytes" in mix:
            self.body_len = int(mix["frame_bytes"]) - self.head_len
        else:
            share = np.resize(np.asarray(mix["body_bytes"], np.int64), n)
            self.body_len = np.stack([rng.permutation(share)
                                      for _ in range(self.rounds)])
        self.body_off = rng.integers(0, _BODY_POOL_TOKENS, size=shape)
        self.meta_off = rng.integers(0, _BODY_POOL_TOKENS, size=shape)
        self.records: Optional[List[List[np.ndarray]]] = None

    @staticmethod
    def _tags(rng, rules: List[dict], n: int) -> np.ndarray:
        # connection i belongs to upstream i mod U: an even spread, so the
        # same number of requests takes each route every round
        by_up = {}
        for rl in rules:
            by_up.setdefault(int(rl["upstream"]), rl)
        ups = sorted(by_up)
        return np.array([rng.integers(by_up[ups[i % len(ups)]]["lo"],
                                      by_up[ups[i % len(ups)]]["hi"] + 1)
                         for i in range(n)])

    # -- one request ---------------------------------------------------------
    def meta_len(self, k: int, i: int) -> int:
        """Header tokens after the 3-token frame prefix."""
        return int(self.head_len[k, i]) - refcipher.FRAME_HEADER

    def header(self, k: int, i: int) -> np.ndarray:
        m = self.meta_len(k, i)
        h = np.empty(refcipher.FRAME_HEADER + m, np.int64)
        h[:3] = (refcipher.FRAME_MAGIC, m, int(self.body_len[k, i]))
        off = int(self.meta_off[k, i])
        h[3:] = self.body_pool[off:off + m]
        if self.tags is not None:
            slot, tags = self.tags
            h[slot] = tags[i]
        return h

    def body(self, k: int, i: int) -> np.ndarray:
        off = int(self.body_off[k, i])
        return self.body_pool[off:off + int(self.body_len[k, i])]

    def frame(self, k: int, i: int) -> np.ndarray:
        return np.concatenate([self.header(k, i), self.body(k, i)])

    def wire(self, k: int, i: int) -> Tuple[np.ndarray, ...]:
        """What client ``i`` sends as its ``k``-th request (``k`` below
        :attr:`rounds`)."""
        if self.records is not None:
            return (self.records[k][i],)
        return self.header(k, i), self.body(k, i)

    def seal(self, rx_keys: List[bytes]) -> None:
        """Seal every request into one hw-kTLS record toward the proxy's
        client socket ``i`` (key ``rx_keys[i]``, record seq ``k + 1``)."""
        n = self.connections

        def seal_round(k):
            return refcipher.seal(
                rx_keys, [k + 1] * n,
                [(self.header(k, i), self.body(k, i)) for i in range(n)])

        with ThreadPoolExecutor(SEAL_THREADS) as pool:
            self.records = list(pool.map(seal_round, range(self.rounds)))
