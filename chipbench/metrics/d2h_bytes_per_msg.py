"""Bytes the resident pool brought back from the device over the window
(``pool.xfer['d2h_tokens']``, int32 tokens) per request forwarded."""
from chipbench.harness import TOKEN_BYTES


def read(run):
    n = run.window.completed
    return run.xfer.get("d2h_tokens", 0) * TOKEN_BYTES / n if n else None
