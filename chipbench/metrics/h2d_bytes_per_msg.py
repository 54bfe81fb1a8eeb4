"""Bytes the resident pool sent to the device over the window
(``pool.xfer['h2d_tokens']``, int32 tokens) per request forwarded."""
from chipbench.harness import TOKEN_BYTES


def read(run):
    n = run.window.completed
    return run.xfer.get("h2d_tokens", 0) * TOKEN_BYTES / n if n else None
