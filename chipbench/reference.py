"""The plain reference and the comparison that decides ``correct``.

The reference is what a plain proxy does with the seeded requests: each
request of connection ``i`` goes, whole and unaltered, to the upstream the
configuration's first matching routing rule picks from its header bytes
(upstream 0 when no rule matches or the configuration routes nothing), and
each upstream of a connection receives its requests in the order the client
sent them. Under hw-kTLS each arrives as one record under the upstream
socket's session key, with the client's record seq and a valid tag.

:func:`check` compares what every backend received with that, request by
request. Its numbers, each with limit 0:

* ``missing``: requests sent that no backend received;
* ``corrupt``: requests received with any byte different from the one sent;
* ``misrouted``: requests received by an upstream the rule did not pick;
* ``bad_tags``: hw-kTLS records whose tag does not verify;
* ``leaked_pages``: pool pages still in use after shutdown.

The control (:func:`control_wires`) is the reference put in the proxy's
place with its tokens stored as signed int8: the narrow store a change that
packs bytes would reach for, which breaks every byte of 128 or more.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from chipbench import refcipher
from chipbench.traffic import Traffic, routing_rules

LIMITS = {"missing": 0, "corrupt": 0, "misrouted": 0, "bad_tags": 0,
          "leaked_pages": 0}


def upstream(config: dict, header: np.ndarray) -> int:
    """The upstream the configuration's routing picks for a request."""
    routing = config.get("routing") or {}
    slot = int(routing.get("slot", 0))
    for rl in routing_rules(config):
        if len(header) > slot and rl["lo"] <= int(header[slot]) <= rl["hi"]:
            return int(rl["upstream"])
    return 0


def expected(traffic: Traffic, config: dict,
             sent: Sequence[Sequence[int]]) -> List[Dict[int, List[int]]]:
    """For each connection, upstream -> the request indices it must
    receive, in order. ``sent[i]`` lists connection ``i``'s requests."""
    out = []
    for i, ks in enumerate(sent):
        per: Dict[int, List[int]] = {}
        for k in ks:
            per.setdefault(upstream(config, traffic.header(k, i)),
                           []).append(k)
        out.append(per)
    return out


def _frames(wire: np.ndarray, key: Optional[bytes]):
    """Split a received byte stream into ``(seq, frame, tag_ok)``; ``seq``
    is None for plaintext. Stops at the first byte that starts no frame and
    yields the rest as one unparseable frame."""
    at = 0
    while at < len(wire):
        try:
            if key is not None:
                seq, frame, ok, at = refcipher.open_record(key, wire, at)
                yield seq, frame, ok
                continue
            if int(wire[at]) != refcipher.FRAME_MAGIC \
                    or len(wire) - at < refcipher.FRAME_HEADER:
                raise ValueError("no frame")
            end = at + refcipher.FRAME_HEADER + int(wire[at + 1]) \
                + int(wire[at + 2])
            if end > len(wire) or end <= at:
                raise ValueError("frame overruns the wire")
            yield None, wire[at:end], True
            at = end
        except ValueError:
            yield None, wire[at:], False
            return


def check(traffic: Traffic, config: dict, sent: Sequence[Sequence[int]],
          received: Callable[[int, int], np.ndarray],
          keys: Optional[Callable[[int, int], bytes]] = None,
          leaked_pages: int = 0) -> Tuple[Dict[str, int], int]:
    """Compare every backend's received bytes with the reference.

    ``received(i, u)`` is the byte stream upstream ``u`` of connection ``i``
    received; ``keys(i, u)`` its session's TX key under hw-kTLS. Returns
    the numbers named in the module docstring and the count of failed
    requests (missing, corrupt or misrouted)."""
    n = {k: 0 for k in LIMITS}
    n["leaked_pages"] = int(leaked_pages)
    n_up = max(int(config.get("upstreams_per_connection", 1)), 1)
    want = expected(traffic, config, sent)
    failed = 0
    for i in range(len(sent)):
        arrived = 0
        for u in range(n_up):
            key = keys(i, u) if keys is not None else None
            ks = want[i].get(u, [])
            frames = list(_frames(received(i, u), key))
            for j, (seq, frame, ok) in enumerate(frames):
                if not ok and key is None:
                    n["corrupt"] += 1          # unparseable plaintext bytes
                    continue
                if not ok:
                    n["bad_tags"] += 1
                if j >= len(ks):
                    # more arrived here than the rule sends: the extra ones
                    # belong on another upstream (or are duplicates)
                    n["misrouted"] += 1
                    continue
                arrived += 1
                k = ks[j]
                if upstream(config, frame[:refcipher.FRAME_HEADER
                                          + traffic.meta_len(k, i)]) != u:
                    n["misrouted"] += 1
                    failed += 1
                    continue
                good = (seq is None or seq == k + 1) and np.array_equal(
                    frame, traffic.frame(k, i))
                if not good:
                    n["corrupt"] += 1
                    failed += 1
        short = len(sent[i]) - arrived
        if short > 0:
            n["missing"] += short
            failed += short
    return n, failed


def control_wires(traffic: Traffic, config: dict,
                  sent: Sequence[Sequence[int]],
                  keys: Optional[Callable[[int, int], bytes]] = None
                  ) -> Callable[[int, int], np.ndarray]:
    """The reference proxy's output with every token passed through signed
    int8 storage: ``received(i, u)`` for :func:`check`."""
    want = expected(traffic, config, sent)

    def received(i: int, u: int) -> np.ndarray:
        ks = want[i].get(u, [])
        if not ks:
            return np.zeros((0,), np.int64)
        if keys is not None:
            frames = refcipher.seal(
                [keys(i, u)] * len(ks), [k + 1 for k in ks],
                [(traffic.header(k, i), traffic.body(k, i)) for k in ks])
        else:
            frames = [traffic.frame(k, i) for k in ks]
        wire = np.concatenate(frames)
        return wire.astype(np.int8).astype(np.int64)

    return received
