#!/usr/bin/env python3
"""The control and the planted faults of a training cell's check, at the
cell's own size: the readings that set each limit's upper end.

    python benchmarks/chip/control.py --workload yi-9b.gssgd-c4 --seeds 1 2 3

For each seed it draws the cell's batches, runs the plain reference over the
check's steps, and puts in the program's place:

  * the control: the same reference with every matrix product's operands in
    float8 (e4m3), the precision below the bfloat16 the configuration states;
  * a half batch: the reference with the second half of every batch replaced
    by the first, so the mean runs over half the rows;
  * in a guided cell, the guided correction left out (strategy "none");
  * in a DC-ASGD cell, DC-ASGD's Taylor term left out (lambda 0);

and prints each one's numbers against the reference, and whether the cell's
own limits pass them, one JSON line per seed and kind. A state left unchanged
needs no run: its weights' change is 0, so every change reading's gap is 1.
The program's own readings (the lower end) come from the benchmark's runs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def faults(traffic: dict) -> dict:
    """kind -> keyword arguments of `check.reference_readings` that put the
    control or a planted fault in the program's place."""
    out = {"control_fp8": {"precision": "fp8"}, "half_batch": {"half_batch": True}}
    if traffic["strategy"] == "guided_fused":
        out["no_correction"] = {"traffic": dict(traffic, strategy="none")}
    if traffic["strategy"] == "dc_asgd":
        out["dc_lambda_zero"] = {"traffic": dict(traffic, dc_lambda=0.0)}
    return out


def readings(cell, seed: int) -> dict:
    """kind -> the numbers it reads against the reference."""
    import check
    import generator

    it = generator.batches_for(cell.cfg, cell.traffic, seed)
    batches = [next(it) for _ in range(cell.check_steps)]
    ref = check.reference_readings(cell.cfg, cell.traffic, seed, batches, cell.check_steps)
    out = {}
    for kind, kw in faults(cell.traffic).items():
        kw = dict(kw)
        traffic = kw.pop("traffic", cell.traffic)
        got = check.reference_readings(cell.cfg, traffic, seed, batches,
                                       cell.check_steps, **kw)
        out[kind] = check.numbers(got, ref)
    out["state_unchanged"] = check.numbers(check.unchanged(ref), ref)
    return out


def main(argv=None) -> int:
    import check
    import run

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = run.Cell(args.workload)
    run.device_info(cell.chips)
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    from repro.common.cache import enable_compile_cache

    enable_compile_cache()
    for seed in args.seeds:
        for kind, nums in readings(cell, seed).items():
            correct, _ = check.judge(nums, cell.limits)
            print(json.dumps({"workload": cell.name, "seed": seed, "kind": kind,
                              "numbers": nums, "correct": correct}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
