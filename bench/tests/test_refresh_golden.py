"""``bayeslr_d50_n12k.refresh`` reads what it read before
``bench/harness/refresh.py`` took composite cycles: at the rehearsal size, one block
(``--seconds 0``) on two seeds, the numbers compared for ``correct`` and
every value a metric reader or ``check_refresh`` uses (keys, shapes,
dtypes, float64 sums and the bytes' SHA-256) equal those recorded in
``refresh_golden.json`` bit for bit.

    python bench/tests/test_refresh_golden.py   # records the file anew
"""
import argparse
import hashlib
import json
import sys
from pathlib import Path

import numpy as np
import pytest

GOLDEN = Path(__file__).with_name("refresh_golden.json")
CELL = "bayeslr_d50_n12k.refresh"
#: (seed, trace): a large seed untraced, another traced.
CASES = [(4294967311, 0), (3000000019, 1)]
#: What the host clock or the device's allocator decides: not compared.
TIMED = ("setup_s", "window_s", "peak_bytes", "checks")


def _describe(rec: dict) -> dict:
    out = {}
    for key, v in sorted(rec.items()):
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[key] = v
            continue
        a = np.ascontiguousarray(v)
        out[key] = {"shape": list(a.shape), "dtype": str(a.dtype),
                    "sum": float(np.sum(a, dtype=np.float64)),
                    "sha256": hashlib.sha256(a.tobytes()).hexdigest()}
    return out


def capture(seed: int, trace: int) -> dict:
    from bench import run as bench_run
    from bench.harness import spec

    model = spec.load_cell(CELL).model
    seen = {}

    def hook(drive):
        def capturing(ctx):
            check = model.check_refresh

            def spy(cfg, host_data, rec, *rest):
                seen["check_rec"] = dict(rec)
                return check(cfg, host_data, rec, *rest)

            model.check_refresh = spy
            try:
                rec = drive(ctx)
            finally:
                model.check_refresh = check
            seen["returned"] = dict(rec)  # before run_cell adds to it
            return rec
        return capturing

    args = argparse.Namespace(workload=CELL, seed=seed, seconds=0, trace=trace)
    result = bench_run.run_cell(args, rehearse=True, driver_hook=hook)
    return {
        "checks": {k: c["value"] for k, c in result["checks"].items()},
        "check_rec": _describe(seen["check_rec"]),
        "returned": _describe({k: v for k, v in seen["returned"].items() if k not in TIMED}),
    }


@pytest.mark.parametrize("seed,trace", CASES)
def test_refresh_cell_reads_as_recorded(seed, trace):
    want = json.loads(GOLDEN.read_text())[f"{seed}/{trace}"]
    assert capture(seed, trace) == want


if __name__ == "__main__":
    root = Path(__file__).resolve().parents[2]
    sys.path[:0] = [str(root), str(root / "src"), str(Path(__file__).parent)]
    import conftest  # noqa: F401  (the CPU and interpret-mode settings)

    GOLDEN.write_text(json.dumps({f"{s}/{t}": capture(s, t) for s, t in CASES},
                                 indent=1, sort_keys=True) + "\n")
    print(f"wrote {GOLDEN}")
