"""``bench/run.py --rehearse`` runs every cell end to end on the CPU at
its configuration's tiny sizes, and prints a result line whose numbers
are never under a metric's name; without ``--rehearse`` it refuses the
CPU and prints no result."""
import json
import os
import subprocess
import sys

import pytest

from bench.harness import spec

RUN = str(spec.ROOT / "bench" / "run.py")
CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]


def _run(*args, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, RUN, *args], capture_output=True,
                          text=True, env=env, timeout=600)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", ["0", "1"])
def test_each_cell_rehearses(cell, trace):
    p = _run("--workload", cell, "--seed", "4294967311", "--seconds", "2",
             "--trace", trace, "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(result)[-1] == "checks"
    assert result["correct"] is True, result["checks"]
    assert result["metrics"] == {}
    assert all(k.startswith("cpu_rehearsal.") for k in result["rehearsal"])
    assert result["attempted"] > 0 and result["failed"] == 0
    assert p.stderr.strip().splitlines()[-1].startswith("check ")


def test_without_a_chip_it_refuses_and_prints_nothing():
    p = _run("--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
