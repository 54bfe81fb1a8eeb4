"""The benchmark finds every cell's configuration, traffic mix and metric
readers by name, and its peaks table knows only the devices it lists."""
import json
import re

import pytest

from chipbench import spec

BENCH = spec.load_json(spec.ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_by_name(name):
    cell = spec.cell(name, BENCH)
    assert cell.chips == 1
    for key in ("connections", "page_bytes", "pool_pages", "batch_impl",
                "batch_tile", "parser_lookahead", "upstreams_per_connection"):
        assert key in cell.config
    assert cell.config["connections"] > 0 and cell.mix["warmup_rounds"] >= 1
    names = {m["name"] for m in cell.end_to_end}
    assert {"setup_s", "msgs_per_s"} <= names
    assert cell.per_layer


@pytest.mark.parametrize(
    "metric", [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
def test_every_metric_has_a_reader(metric):
    assert callable(spec.reader(metric))


def test_unknown_names_raise():
    with pytest.raises(KeyError):
        spec.cell("no-such-cell", BENCH)
    with pytest.raises(FileNotFoundError):
        spec.reader("no_such_metric")


def test_peaks_table_keyed_by_device_kind():
    v5e = spec.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]
    with pytest.raises(KeyError, match="no peaks"):
        spec.peaks("TPU v99 imaginary")


def test_benchmark_file_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(BENCH)) < 64 * 1024
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(BENCH["paths"][0] + "/")
        assert spec.load_json(spec.ROOT / c["file"])["name"] == c["name"]
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {}
    for m in BENCH["per_layer"]:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        layers.setdefault(m["layer"], []).append(m["name"])
    for entry in BENCH["configs"] + BENCH["workloads"] \
            + BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(entry["name"]), entry["name"]
