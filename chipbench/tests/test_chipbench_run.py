"""Whole runs of the harness on the CPU at a small size: the command
refuses to run without a TPU, a sound run is correct, a run whose timed
path is broken underneath (a step that changes nothing, half of each round
left out, a token altered where the fused round produces it) is not, and a
run whose rounds leave the fused device round, or whose window compiles,
prints no result."""
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from chipbench import harness, spec
from chipbench.loop import ClosedLoop

DATA = spec.HERE / "tests" / "data"
BENCH = spec.load_json(spec.ROOT / "BENCHMARK.json")
CELLS = {"bulk": ("tiny-l7route", "tiny-bulk"), "rec": ("tiny-hwktls", "tiny-rec")}


def _cell(kind):
    cfg, mix = CELLS[kind]
    return spec.Cell(f"tiny-{kind}", spec.load_json(DATA / f"{cfg}.json"),
                     spec.load_json(DATA / f"{mix}.json"), 1,
                     BENCH["end_to_end"], BENCH["per_layer"])


def _run(kind, tmp_path, traced=False, seed=2 ** 31 + 11, cell=None):
    return harness.run(cell or _cell(kind), seed, 0.3, traced,
                       t_start=time.perf_counter(), out_dir=tmp_path)


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_command_refuses_to_run_without_a_tpu():
    proc = _command(spec.ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_command_refuses_to_run_without_the_program(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(spec.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    proc = _command(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


@pytest.mark.parametrize("kind", sorted(CELLS))
@pytest.mark.parametrize("traced", [False, True])
def test_sound_run_is_correct(kind, traced, tmp_path, capsys):
    line = _run(kind, tmp_path, traced)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert list(line)[-1] == "checks"
    assert harness.off_path(line["path"]) is None, line["path"]
    names = {m["name"] for m in (BENCH["per_layer"] if traced
                                 else BENCH["end_to_end"])}
    # no chip here: the readers of the device trace find nothing to read
    names -= {"fused_round_roofline", "device_idle_pct"}
    assert names <= set(line["metrics"]), set(line["metrics"])
    harness.emit(line)
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1]) == line
    assert err.strip().splitlines()[-1] == "check leaked_pages = 0 (limit 0)"


def _step_changes_nothing(loop):
    loop.proxy.runtime.step = lambda *a, **kw: 0


def _half_the_round_left_out(loop):
    stack = loop.proxy.stack
    real = stack.forward_batch

    def half(sends, **kw):
        sends = list(sends)
        h = len(sends) // 2
        return real(sends[:h], **kw) + [("ok", 0)] * (len(sends) - h)

    stack.forward_batch = half


def _token_altered_where_produced(loop):
    pool = loop.proxy.stack.pool
    real = pool.fused_round_device

    def altered(*a, **kw):
        verdict, gathered = real(*a, **kw)
        gathered = gathered.copy()
        gathered[:, 0] ^= 1
        return verdict, gathered

    pool.fused_round_device = altered


@pytest.mark.parametrize("kind", sorted(CELLS))
@pytest.mark.parametrize("fault", [_step_changes_nothing,
                                   _half_the_round_left_out,
                                   _token_altered_where_produced])
def test_broken_timed_path_is_not_correct(kind, fault, tmp_path,
                                          monkeypatch):
    real_window = ClosedLoop.window

    def window(self, seconds):
        fault(self)                 # after the warm-up, for the window only
        return real_window(self, seconds)

    monkeypatch.setattr(ClosedLoop, "window", window)
    line = _run(kind, tmp_path)
    assert line["correct"] is False
    assert line["failed"] > 0 or any(
        c["value"] > c["limit"] for c in line["checks"].values())


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_control_reads_not_correct(kind, tmp_path):
    line = harness.run(_cell(kind), 9, 0.3, False,
                       t_start=time.perf_counter(), out_dir=tmp_path,
                       control=True)
    assert line["correct"] is True
    assert any(v > 0 for v in line["control"].values())
    assert np.sum(list(line["control"].values())) >= line["attempted"]


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_round_off_the_device_prints_no_result(kind, tmp_path, monkeypatch,
                                               capsys):
    """A window round that the program serves off the fused device round
    (here every round: the device pool bounces it) is correct but is not
    what the cell measures: the run exits non-zero with no result line."""
    from repro.core.device_pool import DeviceRangeError

    real_window = ClosedLoop.window

    def window(self, seconds):
        def bounce(*a, **kw):
            raise DeviceRangeError("injected")

        self.proxy.stack.pool.fused_round_device = bounce
        return real_window(self, seconds)

    monkeypatch.setattr(ClosedLoop, "window", window)
    line = _run(kind, tmp_path)
    assert line["correct"] is True
    assert line["path"]["device_fallbacks"] > 0
    assert harness.off_path(line["path"]) is not None
    capsys.readouterr()
    assert harness.finish(line) != 0
    out, err = capsys.readouterr()
    assert out == ""
    assert "left the fused device round" in err


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_window_that_compiles_prints_no_result(kind, tmp_path, monkeypatch,
                                               capsys):
    """A program compiled inside the window times the compiler, not the
    cell's path: the run is correct but exits non-zero with no result
    line, and says how many compiles it saw."""
    import jax

    real_window = ClosedLoop.window

    def window(self, seconds):
        jax.jit(lambda x: x * 3 + 1)(np.arange(7)).block_until_ready()
        return real_window(self, seconds)

    monkeypatch.setattr(ClosedLoop, "window", window)
    line = _run(kind, tmp_path)
    assert line["correct"] is True
    n = line["path"]["compiles_in_window"]
    assert n >= 1
    capsys.readouterr()
    assert harness.finish(line) == 4
    out, err = capsys.readouterr()
    assert out == ""
    assert f"{n} compiles in the window" in err


def test_off_path_names_the_compile_count():
    path = {"steps": 5, "fused_rounds": 5, "device_fallbacks": 0,
            "compiles_in_window": 0}
    assert harness.off_path(path) is None
    assert harness.off_path(dict(path, compiles_in_window=3)) == \
        "3 compiles in the window"
    assert "fell back" in harness.off_path(dict(path, device_fallbacks=2))


def test_window_closes_when_the_requests_run_out(tmp_path, capsys):
    """Set-up makes the window's requests and nothing more; where they run
    out the window closes early, and its rate is over the shorter window."""
    cell = _cell("bulk")
    cell.mix = dict(cell.mix, max_rounds_per_s=0.6)     # 3 rounds in 5 s
    line = harness.run(cell, 2 ** 40 + 3, 5.0, False,
                       t_start=time.perf_counter(), out_dir=tmp_path)
    assert line["correct"] is True
    assert line["attempted"] == cell.config["connections"] * 4
    assert line["path"]["steps"] == 4
    assert "ran out" in capsys.readouterr().err
    assert line["metrics"]["msgs_per_s"]["value"] > line["attempted"] / 5.0
