"""Cells are found by name: a new configuration, traffic mix or metric is
new files plus a ``workloads`` entry, and every reader agrees with the
entry that names it."""
import json
import re
import shutil

import pytest

from bench.harness import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_every_cell_loads(bench):
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        e2e = [m.name for m in cell.end_to_end]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        assert cell.traffic["kind"] in ("refresh", "open_loop")
        assert cell.chips in (1, 4)


def test_readers_agree_with_their_entries(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            assert NAME.match(m["name"])
            reader = spec.metric_reader(m["name"])
            assert reader.UNIT == m["unit"]
            assert reader.TRACED == (kind == "per_layer")
            if kind == "per_layer":
                assert reader.LAYER == m["layer"]
                assert reader.MOVES == m["moves"] and m["moves"] in e2e
                # every cell it names reports the metric it moves
                for cell in m["workloads"]:
                    names = [x.name for x in spec.load_cell(cell).end_to_end]
                    assert m["moves"] in names


def test_one_layer_name_per_layer(bench):
    layers = {}
    for m in bench["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_config_files_name_their_model_and_limits(bench):
    for c in bench["configs"]:
        cfg = json.load(open(spec.ROOT / c["file"]))
        assert (spec.ROOT / "bench" / "models" / f"{cfg['model']}.py").exists()
        assert set(cfg["limits"]) <= {"refresh", "open_loop"}
        for key in c["reduced"]:
            assert NAME.match(key)


def test_a_new_cell_is_new_files_and_an_entry(tmp_path):
    """A throwaway configuration, mix and metric in a temporary checkout
    load without an edit to any existing file."""
    src = spec.ROOT
    for sub in ("models", "metrics"):
        shutil.copytree(src / "bench" / sub, tmp_path / "bench" / sub)
    (tmp_path / "bench" / "configs").mkdir()
    (tmp_path / "bench" / "traffic").mkdir()
    base = json.load(open(src / "bench" / "configs" / "bayeslr_d50_n12k.json"))
    base["data"]["n_train"] = base["builder"]["n_train"] = 4096
    json.dump(base, open(tmp_path / "bench" / "configs" / "tiny_lr.json", "w"))
    json.dump({"kind": "open_loop", "rate_per_s": 5, "rows_min": 1, "rows_max": 2,
               "classes": {"vote": 1.0}},
              open(tmp_path / "bench" / "traffic" / "trickle.json", "w"))
    (tmp_path / "bench" / "metrics" / "trickle_count.py").write_text(
        'UNIT = "requests"\nLAYER = "queue"\nMOVES = "query_p95_ms"\nTRACED = True\n\n'
        'def read(rec):\n    return rec.get("attempted")\n')
    bench = {
        "configs": [{"name": "tiny_lr", "source": "x", "file": "bench/configs/tiny_lr.json",
                     "reduced": ["n_train"], "why": "x"}],
        "workloads": [{"name": "tiny_lr.trickle", "config": "tiny_lr",
                       "traffic": "trickle", "chips": 1, "why": "x"}],
        "end_to_end": [{"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
                        "source": "host_clock"},
                       {"name": "query_p95_ms", "unit": "ms", "better": "lower",
                        "bound": 0.05, "source": "host_clock"}],
        "per_layer": [{"name": "trickle_count", "unit": "requests", "better": "higher",
                       "source": "program_counter", "layer": "queue",
                       "moves": "query_p95_ms", "workloads": ["tiny_lr.trickle"]}],
    }
    json.dump(bench, open(tmp_path / "BENCHMARK.json", "w"))
    cell = spec.load_cell("tiny_lr.trickle", root=tmp_path)
    assert cell.config["data"]["n_train"] == 4096
    assert cell.traffic["rate_per_s"] == 5
    assert [m.name for m in cell.end_to_end] == ["setup_s", "query_p95_ms"]
    (m,) = cell.per_layer
    assert m.reader.read({"attempted": 7}) == 7
    assert hasattr(cell.model, "check_serve")
