"""The bytes a kernel's work requires, from the requests it served and not
from the operands' padded int32 shapes, so the count is the same whatever
layout implements the kernel. One token is one byte of the request.

The fused round (anchor + hw-kTLS decrypt + policy match + egress gather
in one launch) must, for each request, read its header once from the
stream and write it once (the metadata handed to the host), and read its
body once from the stream, write it once into the pool and once into the
egress block. Under hw-kTLS it also reads the body's RX keystream once and
its TX keystream once (the header is decrypted on the host).
"""
from __future__ import annotations

from typing import Iterable, Tuple


def fused_round_bytes(requests: Iterable[Tuple[int, int]], tls: bool) -> int:
    """``requests``: ``(header_bytes, body_bytes)`` of each request."""
    per_body = 5 if tls else 3
    return sum(2 * int(h) + per_body * int(b) for h, b in requests)
