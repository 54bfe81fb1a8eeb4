"""Requests forwarded in the window over the window's seconds. The window
closes at the end of the first step that ends after ``--seconds``."""


def read(run):
    w = run.window
    return w.completed / w.seconds if w.completed and w.seconds > 0 else None
