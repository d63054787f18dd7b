"""Find a cell's configuration, traffic mix, model reference and metric
readers by the names in ``BENCHMARK.json``.

Layout under the checkout root::

    BENCHMARK.json
    bench/configs/<config>.json    the configuration as it is run
    bench/models/<model>.py        its plain reference (data, build, checks)
    bench/traffic/<mix>.json       the mix's parameters; ``kind`` picks the driver
    bench/metrics/<metric>.py      one reader per metric

A new cell is new files plus a ``workloads`` entry: nothing here changes.

A model module must define ``make_data(cfg, data_seed)``,
``build_pool(cfg, data, program_seed)`` and, per traffic kind it serves,
``check_refresh`` (kind ``refresh``) or ``check_serve`` and
``check_chains`` (kind ``open_loop``, whose control in
``bench/harness/faults.py`` also reads ``reference_values``). For
``refresh`` it may also define ``sizes(cfg)`` and
``after_block(resident, block_index, rng)``; ``bench/harness/refresh.py``
says what each is given and what the ``rec`` handed to ``check_refresh``
holds, for a single operator and for a composite cycle. It may define
``draw_scale(cfg)``, each draw leaf's proposal scale, by which the
``altered_draw`` fault shifts a draw (else ``cfg["builder"]["sigma"]``).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType

ROOT = Path(__file__).resolve().parents[2]


@dataclasses.dataclass(frozen=True)
class Metric:
    """One metric of a cell: its ``BENCHMARK.json`` entry and its reader."""

    name: str
    entry: dict
    reader: ModuleType


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict  # the configuration file, plus "name"
    traffic: dict  # the traffic file, plus "name"
    model: ModuleType
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]


def _load_module(path: Path, tag: str) -> ModuleType:
    """Import a file by path under a private module name (metric names
    hold dots, which a plain import would read as packages)."""
    mod_name = "_bench_" + tag + "_" + re.sub(r"\W", "_", path.stem)
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = module
    spec.loader.exec_module(module)
    return module


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(entry: dict, cell_name: str) -> bool:
    return "workloads" not in entry or cell_name in entry["workloads"]


def metric_reader(name: str, root: Path = ROOT) -> ModuleType:
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.exists():
        raise FileNotFoundError(f"no reader for metric {name!r}: {path}")
    return _load_module(path, "metric")


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Everything a run of cell ``name`` needs, found by name."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; cells: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    conf_entry = configs[cell["config"]]
    with open(root / conf_entry["file"]) as f:
        config = dict(json.load(f), name=conf_entry["name"])
    with open(root / "bench" / "traffic" / f"{cell['traffic']}.json") as f:
        traffic = dict(json.load(f), name=cell["traffic"])
    model = _load_module(root / "bench" / "models" / f"{config['model']}.py", "model")

    def metrics(kind: str) -> tuple[Metric, ...]:
        return tuple(
            Metric(m["name"], m, metric_reader(m["name"], root))
            for m in bench[kind] if _applies(m, name)
        )

    return Cell(
        name=name,
        chips=int(cell["chips"]),
        config=config,
        traffic=traffic,
        model=model,
        end_to_end=metrics("end_to_end"),
        per_layer=metrics("per_layer"),
    )
