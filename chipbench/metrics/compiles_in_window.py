"""Backend compiles (``jax.monitoring``) inside the window, those the
persistent cache serves included. Every shape compiles in the warm-up, so
this is 0 unless a round's shape changed."""


def read(run):
    return run.compiles_in_window
