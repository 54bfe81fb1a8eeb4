"""The open loop and the traffic it leaves alone: the committed mixes make
the same requests as before the open loop existed; a mix's ``arrival``
makes wrk2's seeded schedule, one constant interval per connection (bursty
where it asks), and the open loop run through the harness on the CPU keeps
HTTP/1.1 order, times a request from its scheduled arrival, never steps an
idle proxy and starts its window, traced or not, from the queue the warm-up
left."""
import collections
import hashlib
import time

import numpy as np
import pytest

from chipbench import harness, spec
from chipbench import trace as tracing
from chipbench.loop import ClosedLoop, OpenLoop
from chipbench.spec import ARRIVAL_KEYS, check_arrival
from chipbench.traffic import Traffic, arrivals

DATA = spec.HERE / "tests" / "data"
BENCH = spec.load_json(spec.ROOT / "BENCHMARK.json")
CELLS = {"bulk": ("tiny-l7route", "tiny-bulk"),
         "rec": ("tiny-hwktls", "tiny-rec")}

#: sha256 of each committed mix's requests (seed 2**31 + 977, 20 s), as the
#: generator made them before mixes could carry ``arrival``
DIGESTS = {
    "closed-bulk":
        "d0ec034be9c73d1e4bfc149f998d9b24a0e0ae68868687726109318dfda1532e",
    "closed-rec16k":
        "41ef323239b4e1821fe3d152488902b6a0344f5984609efa3cc95d4427522c6a",
    "closed-small1k":
        "4cb12dc6c006624801ed89a348b962a0404bef03b8755ffd7da47674bf2b7c3f",
}


def _digest(t: Traffic) -> str:
    h = hashlib.sha256()
    h.update(np.int64([t.rounds, t.connections]).tobytes())
    for a in (t.body_pool, t.head_len, t.body_len, t.body_off, t.meta_off):
        h.update(np.ascontiguousarray(np.asarray(a, np.int64)).tobytes())
    if t.tags is not None:
        h.update(np.int64([t.tags[0]]).tobytes())
        h.update(np.asarray(t.tags[1], np.int64).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_committed_mixes_make_the_same_requests(w):
    cell = spec.cell(w["name"], BENCH)
    t = Traffic(cell.mix, cell.config, 2 ** 31 + 977, 20.0)
    assert t.arrivals is None
    assert _digest(t) == DIGESTS[w["traffic"]]


def _arrival(rate=5000.0, warmup_s=4.0, on_s=None, off_s=None):
    return {"kind": "open", "rate_msgs_per_s": rate, "warmup_s": warmup_s,
            "on_s": on_s, "off_s": off_s}


def test_schedule_is_the_seeds():
    a = arrivals(_arrival(), 256, 2, 2 ** 33 + 7, 20.0)
    b = arrivals(_arrival(), 256, 2, 2 ** 33 + 7, 20.0)
    c = arrivals(_arrival(), 256, 2, 2 ** 33 + 8, 20.0)
    for x, y in ((a.at, b.at), (a.conn, b.conn), (a.k, b.k)):
        np.testing.assert_array_equal(x, y)
    assert len(a.at) != len(c.at) or not np.array_equal(a.at, c.at)


@pytest.mark.parametrize("on_off", [(None, None), (1.0, 1.0), (0.25, 0.75)])
def test_schedule_keeps_the_mean_rate(on_off):
    on, off = on_off
    rate = 5000.0
    a = arrivals(_arrival(rate, 4.0, on, off), 256, 2, 2 ** 32 + 99, 20.0)
    assert a.span_s == 29.0 and len(a.at) > 1e5
    assert np.all(np.diff(a.at) >= 0) and 0 <= a.at[0] and a.at[-1] < a.span_s
    # the mean over whole on/off cycles
    whole = a.span_s if on is None else a.span_s // (on + off) * (on + off)
    assert abs(np.sum(a.at < whole) / whole / rate - 1) < 0.03
    if on is not None:
        assert not np.any(np.mod(a.at, on + off) >= on)
        # the on periods carry the whole rate: (on + off) / on times it
        per_on = np.bincount((a.at // (on + off)).astype(int))[:-1] / on
        assert abs(np.mean(per_on) / (rate * (on + off) / on) - 1) < 0.03


@pytest.mark.parametrize("on_off", [(None, None), (1.0, 1.0)])
def test_schedule_gives_each_connection_wrk2s_constant_interval(on_off):
    """wrk2 -R r over N connections: connection i sends its request k at
    (k + phase_i) * N / r on the on periods' clock, the phases from the
    seed and spread over one interval."""
    on, off = on_off
    n, rate = 256, 5000.0
    a = arrivals(_arrival(rate, 4.0, on, off), n, 2, 2 ** 31 + 5, 20.0)
    on_rate = rate if on is None else rate * (on + off) / on
    gap = n / on_rate
    # back onto the on periods' clock
    clock = a.at if on is None else \
        a.at // (on + off) * on + np.mod(a.at, on + off)
    phases = []
    for i in range(n):
        t = clock[a.conn == i]
        np.testing.assert_allclose(np.diff(t), gap, rtol=1e-9)
        assert 0 <= t[0] < gap
        phases.append(t[0] / gap)
    counts = np.bincount(a.conn, minlength=n)
    assert counts.max() - counts.min() <= 1
    assert np.std(phases) > 0.2


def test_schedule_numbers_each_connections_requests_in_order():
    a = arrivals(_arrival(800.0, 0.5), 12, 3, 11, 2.0)
    for i in range(12):
        np.testing.assert_array_equal(
            a.k[a.conn == i], 3 + np.arange(np.sum(a.conn == i)))
    uniform = np.bincount(a.conn, minlength=12) / len(a.conn)
    assert np.all(np.abs(uniform - 1 / 12) < 0.03)
    cfg, mix = CELLS["bulk"]
    m = dict(spec.load_json(DATA / f"{mix}.json"), arrival=_arrival(800, .5))
    t = Traffic(m, spec.load_json(DATA / f"{cfg}.json"), 11, 2.0)
    most = np.bincount(t.arrivals.conn).max()
    assert t.rounds == m["warmup_rounds"] + most + 1
    assert t.head_len.shape == (t.rounds, 12)


@pytest.mark.parametrize("bad", [
    {"kind": "poisson"}, {"rate_msgs_per_s": None}, {"rate_msgs_per_s": 0},
    {"warmup_s": -1}, {"on_s": 1.0}, {"on_s": 0, "off_s": 1.0}])
def test_arrival_without_a_known_kind_and_rate_is_refused(bad, monkeypatch):
    mix = {"arrival": dict(_arrival(), **bad)}
    with pytest.raises(ValueError):
        check_arrival(mix)
    for key in ARRIVAL_KEYS:
        with pytest.raises(ValueError):
            check_arrival({"arrival": {k: v for k, v in _arrival().items()
                                       if k != key}})
    real = spec.load_json
    monkeypatch.setattr(spec, "load_json", lambda p: mix if "mixes" in str(
        p) else real(p))
    with pytest.raises(ValueError):
        spec.cell(BENCH["workloads"][0]["name"], BENCH)


def _open_cell(kind, rate, on_s=None, off_s=None):
    cfg, mix = CELLS[kind]
    m = dict(spec.load_json(DATA / f"{mix}.json"),
             arrival=_arrival(rate, 0.3, on_s, off_s))
    return spec.Cell(f"tiny-open-{kind}", spec.load_json(DATA / f"{cfg}.json"),
                     m, 1, BENCH["end_to_end"], BENCH["per_layer"])


def _run_open(cell, tmp_path, monkeypatch, seed=2 ** 31 + 41, hold=None):
    """One open-loop run through ``harness.run``, with every wire delivery
    and every step checked as it happens; returns the line, the loop and
    its window. ``hold`` holds the proxy back for that many seconds in the
    window's third step."""
    seen = {}
    real_wire, real_step = OpenLoop._wire, OpenLoop._step
    real_window = OpenLoop.window

    def wire(self, i, k, t=None):
        assert not self.fifo[i], "a second request on a busy connection"
        assert t is not None or not self.origin
        return real_wire(self, i, k, t)

    def step(self):
        assert self.outstanding() > 0, "a step on an idle proxy"
        seen["steps"] = seen.get("steps", 0) + 1
        if not (hold and seen.get("window") and seen["steps"] == 3):
            return real_step(self)
        rt = self.proxy.runtime
        real = rt.step

        def held(*a, **kw):
            time.sleep(hold)
            return real(*a, **kw)

        rt.step = held
        try:
            return real_step(self)
        finally:
            del rt.step

    def window(self, seconds):
        seen.update(loop=self, window=True, steps=0)
        seen["w"] = real_window(self, seconds)
        return seen["w"]

    monkeypatch.setattr(OpenLoop, "_wire", wire)
    monkeypatch.setattr(OpenLoop, "_step", step)
    monkeypatch.setattr(OpenLoop, "window", window)
    line = harness.run(cell, seed, 1.0, False, t_start=time.perf_counter(),
                       out_dir=tmp_path)
    return line, seen["loop"], seen["w"]


def _latencies(loop, w):
    """Each window-due request a window step forwarded: its scheduled
    time and its latency, rebuilt from the schedule and the steps."""
    s = loop.sched
    due = {(int(i), int(k)): loop.origin + float(at)
           for i, k, at in zip(s.conn, s.k, s.at)}
    return [(due[i, k], e - due[i, k])
            for (_, e), fwd in zip(w.steps, w.forwarded) for i, k in fwd
            if due[i, k] >= w.t0]


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_open_loop_through_the_harness_is_correct(kind, tmp_path,
                                                  monkeypatch):
    cell = _open_cell(kind, 300.0)
    line, loop, w = _run_open(cell, tmp_path, monkeypatch)
    assert line["correct"] is True and line["failed"] == 0
    assert line["path"]["fused_rounds"] == line["path"]["steps"] > 0
    at = loop.sched.at + loop.origin
    assert line["attempted"] == np.sum((at >= w.t0) & (at < w.t1)) > 0
    # the warm-up rounds, then every arrival due before the window closed,
    # and none due after it, was sent
    n, warm = len(loop.sent), cell.mix["warmup_rounds"]
    assert sum(map(len, loop.sent)) == n * warm + np.sum(at < w.t1)
    assert line["checks"]["missing"]["value"] == 0
    got = _latencies(loop, w)
    assert len(got) > 0
    have = collections.Counter(w.latencies_s)
    assert not collections.Counter(lat for _, lat in got) - have
    assert len(w.latencies_s) == line["attempted"]


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_open_loop_latency_counts_the_clients_wait(kind, tmp_path,
                                                   monkeypatch):
    """Requests that fall due while the proxy is held back one step wait
    in their clients' queues or sockets; their latency runs from when they
    were due, not from when they reached the proxy."""
    hold = 0.25
    line, loop, w = _run_open(_open_cell(kind, 400.0), tmp_path, monkeypatch,
                              hold=hold)
    assert line["correct"] is True
    t_s, t_e = w.steps[2]
    assert t_e - t_s >= hold
    waited = [(due, lat) for due, lat in _latencies(loop, w)
              if t_s < due < t_e]
    assert len(waited) >= 10
    for due, lat in waited:
        assert lat >= t_e - due
    # the window recorded these latencies, from the scheduled times
    assert not collections.Counter(lat for _, lat in waited) \
        - collections.Counter(w.latencies_s)
    assert max(lat for _, lat in waited) >= 0.8 * hold


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_open_loop_idles_between_sparse_arrivals(kind, tmp_path,
                                                 monkeypatch):
    """At a rate far under what the proxy serves, most of the window has
    nothing outstanding: the loop sleeps there instead of stepping."""
    line, loop, w = _run_open(_open_cell(kind, 20.0, 0.2, 0.2), tmp_path,
                              monkeypatch)
    assert line["correct"] is True
    assert 0 < len(w.steps) <= line["attempted"] + 5
    assert line["path"]["fused_rounds"] == line["path"]["steps"]


def test_closed_mixes_take_the_closed_loop(tmp_path, monkeypatch):
    used = []
    real = ClosedLoop.__init__

    def init(self, *a):
        used.append(type(self))
        real(self, *a)

    monkeypatch.setattr(ClosedLoop, "__init__", init)
    cfg, mix = CELLS["bulk"]
    cell = spec.Cell("tiny-bulk", spec.load_json(DATA / f"{cfg}.json"),
                     spec.load_json(DATA / f"{mix}.json"), 1,
                     BENCH["end_to_end"], BENCH["per_layer"])
    harness.run(cell, 5, 0.2, False, t_start=time.perf_counter(),
                out_dir=tmp_path)
    assert used == [ClosedLoop]


@pytest.mark.parametrize("traced", [False, True])
def test_window_starts_from_the_queue_the_warm_up_left(traced, tmp_path,
                                                       monkeypatch):
    """Between the warm-up and the window the harness logs and, traced,
    starts the profiler (here held back 1 s more). The schedule's clock
    stands still there: the window starts at the schedule's time where the
    warm-up left it, so nothing falls due in the gap and a traced window
    starts from the same queue as an untraced one."""
    real_start, real_warmup, real_run = \
        tracing.start, OpenLoop.warmup, OpenLoop._run
    clock = {}

    def slow_start(*a, **kw):
        real_start(*a, **kw)
        time.sleep(1.0)

    def warmup(self, rounds):
        real_warmup(self, rounds)
        clock["warm_end"] = time.perf_counter() - self.origin

    def run(self, until, w):
        if w is not None and "t0" not in clock:
            clock["t0"] = w.t0 - self.origin
            clock["gap"] = w.t0 - self.paused
        return real_run(self, until, w)

    monkeypatch.setattr(tracing, "start", slow_start)
    monkeypatch.setattr(OpenLoop, "warmup", warmup)
    monkeypatch.setattr(OpenLoop, "_run", run)
    line = harness.run(_open_cell("bulk", 300.0), 2 ** 31 + 43, 0.5, traced,
                       t_start=time.perf_counter(), out_dir=tmp_path)
    assert line["correct"] is True
    if traced:
        assert clock["gap"] >= 1.0
    # the schedule's time at the window's start is at most where the
    # warm-up returned, however long the gap
    assert clock["t0"] <= clock["warm_end"]
