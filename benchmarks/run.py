"""Paper-reproduction entrypoint: one function per paper table/figure,
each printing summary lines of accuracy, not speed (the chip benchmark
is `bench/run.py`).

    PYTHONPATH=src python -m benchmarks.run             # full suite
    PYTHONPATH=src python -m benchmarks.run --only table2,fig4 --fast
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma list: table2,table3,fig3,fig4,fig5")
    ap.add_argument("--fast", action="store_true",
                    help="fewer rounds/seeds (CI budget)")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    only = set(args.only.split(",")) if args.only else None
    os.makedirs(RESULTS_DIR, exist_ok=True)
    outputs = {}

    def want(name):
        return only is None or name in only

    t0 = time.time()

    seeds = (0,) if args.fast else (0, 1)
    rounds = 5 if args.fast else 8

    if want("table2"):
        print("\n# Table 2 — performance comparison")
        from benchmarks import table2_performance
        outputs["table2"] = table2_performance.run(seeds=seeds,
                                                   rounds=rounds)

    if want("table3"):
        print("\n# Table 3 — ablation (LSH / Rank)")
        from benchmarks import table3_ablation
        outputs["table3"] = table3_ablation.run(seeds=seeds, rounds=rounds)

    if want("fig3"):
        print("\n# Fig. 3 — alpha / gamma sensitivity")
        from benchmarks import fig3_hyperparams
        outputs["fig3"] = fig3_hyperparams.run(rounds=rounds)

    if want("fig4"):
        print("\n# Fig. 4 — LSH-cheating attack")
        from benchmarks import fig4_lsh_cheating
        outputs["fig4"] = fig4_lsh_cheating.run(rounds=rounds)

    if want("fig5"):
        print("\n# Fig. 5 — poison attack")
        from benchmarks import fig5_poison
        outputs["fig5"] = fig5_poison.run(rounds=rounds)

    path = os.path.join(RESULTS_DIR, "bench_results.json")
    with open(path, "w") as f:
        json.dump(outputs, f, indent=1, default=str)
    print(f"\n# done in {time.time() - t0:.0f}s -> {path}")


if __name__ == "__main__":
    main()
