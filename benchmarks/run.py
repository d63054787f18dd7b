"""Benchmark entry point: one bench per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV. Default is the fast (CPU-minutes)
configuration; ``--full`` runs the paper-scale versions.
"""
from __future__ import annotations

import argparse
import sys
import traceback

from repro import compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma list: fig4,fig5,fig6,fig9,kernels,roofline,"
                         "multichain,serving,fleet,subposterior")
    args = ap.parse_args()
    fast = not args.full

    from . import fig4_bayeslr, fig5_sublinear, fig6_jointdpm, fig9_sv
    from . import fleet_bench, kernels_bench, multichain_bench, roofline
    from . import serving_bench, subposterior_bench

    benches = {
        "fig5": fig5_sublinear,
        "fig4": fig4_bayeslr,
        "fig6": fig6_jointdpm,
        "fig9": fig9_sv,
        "kernels": kernels_bench,
        "roofline": roofline,
        "multichain": multichain_bench,
        "serving": serving_bench,
        "fleet": fleet_bench,
        "subposterior": subposterior_bench,
    }
    selected = args.only.split(",") if args.only else list(benches)

    print("name,us_per_call,derived")
    failures = 0
    for name in selected:
        mod = benches[name]
        try:
            rows, _ = mod.main(fast=fast)
            for row_name, us, derived in rows:
                print(f"{row_name},{us:.1f},{derived}", flush=True)
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name},nan,ERROR:{type(e).__name__}:{e}", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(f"{failures} benchmark(s) failed")


if __name__ == "__main__":
    compile_cache.enable()
    main()
