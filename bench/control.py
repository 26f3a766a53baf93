#!/usr/bin/env python3
"""Readings that set the output check's limits; not part of a run.

    python bench/control.py --workload <cell> --seeds 1,2,3

For each seed, the cell's data are made as a run makes them, and the
plain reference is compared, by the same numbers a run compares, with

  control     the reference computed in bfloat16 (weights, inputs,
              optimizer moments), in the program's place;
  half_batch  the reference with each local minibatch's loss averaged
              over its first half, in the program's place.

A step that returns its state unchanged reads change_gap 1 by
definition and needs no run. Prints one JSON line per seed and kind.
"""
import argparse
import json
import sys

import run  # noqa: F401  (puts the harness and the program on the path)
import compare

PLANTED = {"control": {"dtype": "bfloat16"},
           "half_batch": {"fault": "half_batch"}}


def readings(cell, seed, kinds=tuple(PLANTED)):
    import jax.numpy as jnp
    wl = cell["wl"]
    data = run.cell_data(cell, seed)
    ref = run.reference_readings(cell, seed, data, wl["check_periods"])
    out = {}
    for kind in kinds:
        planted = dict(PLANTED[kind])
        if "dtype" in planted:
            planted["dtype"] = getattr(jnp, planted["dtype"])
        prog = run.reference_readings(cell, seed, data,
                                      wl["check_periods"], **planted)
        out[kind] = compare.numbers(prog, ref)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    run.device_info(cell["entry"]["chips"])
    run.use_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        for kind, nums in readings(cell, seed).items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "kind": kind, **nums}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
