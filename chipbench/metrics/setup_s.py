"""Set-up time: process start to the first timed step. Includes importing
JAX and the program, making the traffic (and sealing it, under hw-kTLS),
building the proxy, and the warm-up rounds with their compiles (or
persistent-cache loads)."""


def read(run):
    return run.setup_s
