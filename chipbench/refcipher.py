"""The record protocol as the clients and backends speak it, written from
its specification and independent of the proxy's code.

Wire format of one request (the proxy's length-prefixed framing): the
frame ``[17, meta_len, payload_len, meta..., payload...]``, one int64 token
per byte. Under hw-kTLS each frame travels as one record
``[23, seq, inner_meta_len, payload_len, tag, ciphertext...]``:

* the session key of a socket is ``blake2b(key=secret, digest 16)`` over
  the label (``b"tls-rx"`` for records sent *to* the socket, ``b"tls-tx"``
  for records it sends) and the connection id, packed ``<q``: the key
  schedule both peers of a handshake share;
* the keystream of record ``seq`` is splitmix64 over ``seed + i``, where
  ``seed`` is ``blake2b(key=session key, digest 8)`` of ``seq`` packed
  ``<q``, shifted right by 33 and masked to 31 bits;
* the tag is ``blake2b(key=b"libra-record-mac", digest 8)`` over ``seq``
  packed ``<q`` followed by the plaintext frame's int64 bytes, masked to
  31 bits.

The benchmark seals the clients' records and opens the backends' records
with this module; the proxy under test has its own implementation.
"""
from __future__ import annotations

import hashlib
import struct
from typing import Sequence, Tuple

import numpy as np

FRAME_MAGIC = 17
FRAME_HEADER = 3
REC_MAGIC = 23
REC_HEADER = 5
MAC_KEY = b"libra-record-mac"
KS_MASK = 0x7FFFFFFF


def session_key(secret: bytes, label: bytes, conn_id: int) -> bytes:
    h = hashlib.blake2b(key=secret, digest_size=16)
    h.update(label)
    h.update(struct.pack("<q", int(conn_id)))
    return h.digest()


def _record_seed(key: bytes, seq: int) -> int:
    return struct.unpack("<Q", hashlib.blake2b(
        struct.pack("<q", int(seq)), key=key, digest_size=8).digest())[0]


def keystreams(keys: Sequence[bytes], seqs: Sequence[int],
               lens: Sequence[int]) -> np.ndarray:
    """The keystreams of several records, concatenated, in one sweep."""
    lens_arr = np.asarray(lens, np.int64)
    seeds = np.array([_record_seed(k, s) for k, s in zip(keys, seqs)],
                     np.uint64)
    starts = np.zeros_like(lens_arr)
    np.cumsum(lens_arr[:-1], out=starts[1:])
    # splitmix64 of seed + position, in place on one buffer
    z = np.repeat(seeds - starts.astype(np.uint64), lens_arr)
    z += np.arange(int(lens_arr.sum()), dtype=np.uint64)
    t = np.empty_like(z)
    z += np.uint64(0x9E3779B97F4A7C15)
    for shift, mult in ((30, 0xBF58476D1CE4E5B9), (27, 0x94D049BB133111EB)):
        np.right_shift(z, np.uint64(shift), out=t)
        z ^= t
        z *= np.uint64(mult)
    np.right_shift(z, np.uint64(31), out=t)
    z ^= t
    z >>= np.uint64(33)
    z &= np.uint64(KS_MASK)
    return z.view(np.int64)


def tag(seq: int, parts: Sequence[np.ndarray]) -> int:
    """The tag of the plaintext frame made of ``parts`` in order."""
    h = hashlib.blake2b(key=MAC_KEY, digest_size=8)
    h.update(struct.pack("<q", int(seq)))
    for p in parts:
        h.update(np.ascontiguousarray(p, np.int64).tobytes())
    return struct.unpack("<Q", h.digest())[0] & KS_MASK


def seal(keys: Sequence[bytes], seqs: Sequence[int],
         frames: Sequence[Sequence[np.ndarray]]) -> list:
    """Seal each plaintext frame, given as ``(header, body)`` parts, into
    one record under its key and seq."""
    lens = [sum(len(p) for p in f) for f in frames]
    ks = keystreams(keys, seqs, lens)
    out, pos = [], 0
    for seq, parts, n in zip(seqs, frames, lens):
        rec = np.empty(REC_HEADER + n, np.int64)
        rec[:REC_HEADER] = (REC_MAGIC, seq, len(parts[0]),
                            n - len(parts[0]), tag(seq, parts))
        at = REC_HEADER
        for p in parts:
            np.bitwise_xor(p, ks[pos:pos + len(p)], out=rec[at:at + len(p)])
            at += len(p)
            pos += len(p)
        out.append(rec)
    return out


def open_record(key: bytes, wire: np.ndarray, at: int
                ) -> Tuple[int, np.ndarray, bool, int]:
    """Open the record at ``wire[at:]``: ``(seq, plaintext frame, tag ok,
    end)``. Raises ``ValueError`` where no record header stands there."""
    if len(wire) - at < REC_HEADER or int(wire[at]) != REC_MAGIC:
        raise ValueError(f"no record header at token {at}")
    seq, imeta, plen, want = (int(v) for v in wire[at + 1:at + REC_HEADER])
    end = at + REC_HEADER + imeta + plen
    if imeta < 0 or plen < 0 or end > len(wire):
        raise ValueError(f"record at token {at} overruns the wire")
    body = wire[at + REC_HEADER:end]
    plain = np.bitwise_xor(body, keystreams([key], [seq], [len(body)]))
    return seq, plain, tag(seq, (plain,)) == want, end
