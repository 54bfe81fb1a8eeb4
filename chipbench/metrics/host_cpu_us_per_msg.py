"""The process's CPU time (``time.process_time``, every thread) over the
window, per request forwarded in it: the host cost selective copy exists
to cut. Includes the clients' own delivery work, which the harness
reports apart on standard error."""


def read(run):
    w = run.window
    return w.cpu_s / w.completed * 1e6 if w.completed else None
