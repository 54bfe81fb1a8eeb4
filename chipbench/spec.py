"""Find a cell's pieces by name: the cell in ``BENCHMARK.json``, its
configuration in ``configs/<config>.json``, its traffic mix in
``mixes/<traffic>.json``, each metric's reader in ``metrics/<name>.py`` and
the device's peaks in ``peaks.json``. A later cell, mix, configuration or
metric is added by adding files and entries; nothing here names one."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
ARRIVAL_KEYS = ("kind", "rate_msgs_per_s", "warmup_s", "on_s", "off_s")


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    mix: dict
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def check_arrival(mix: dict) -> None:
    """Raise ``ValueError`` where the mix's ``arrival`` is not one the
    traffic generator makes. Every key is required: there are no
    defaults."""
    a = mix.get("arrival")
    if a is None:
        return

    def number(key, least, strict):
        v = a[key]
        if isinstance(v, bool) or not isinstance(v, (int, float)) \
                or v < least or (strict and v == least):
            raise ValueError(f"arrival {key} {v!r}")

    missing = [k for k in ARRIVAL_KEYS if k not in a]
    if missing:
        raise ValueError(f"arrival lacks {missing}")
    if a["kind"] != "open":
        raise ValueError(f"arrival kind {a['kind']!r}: only 'open' is known")
    number("rate_msgs_per_s", 0, True)
    number("warmup_s", 0, False)
    if (a["on_s"] is None) != (a["off_s"] is None):
        raise ValueError("arrival on_s and off_s: both or neither null")
    if a["on_s"] is not None:
        number("on_s", 0, True)
        number("off_s", 0, False)


def cell(name: str, bench: Optional[dict] = None) -> Cell:
    """The cell called ``name`` with its configuration, mix and metrics.
    Raises ``KeyError`` for a cell ``BENCHMARK.json`` does not have, and
    ``ValueError`` for a mix whose ``arrival`` the generator does not
    make."""
    bench = bench if bench is not None else load_json(ROOT / "BENCHMARK.json")
    entry = {w["name"]: w for w in bench["workloads"]}[name]
    cfg = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    mix = load_json(HERE / "mixes" / f"{entry['traffic']}.json")
    check_arrival(mix)
    return Cell(
        name=name,
        config=load_json(ROOT / cfg["file"]),
        mix=mix,
        chips=int(entry["chips"]),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def reader(metric: str) -> Callable:
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_').replace('-', '_')}",
        path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no reader for metric {metric!r}: {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind``. A device the table does not
    hold is an error, never a default."""
    table = load_json(HERE / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
