#!/usr/bin/env python3
"""The chip benchmark: one run of one cell of `BENCHMARK.json`.

    python benchmarks/chip/run.py --workload yi-9b.gssgd-c4 --seed 7 --seconds 30 --trace 0

Everything a cell uses is found by name: its entry in `BENCHMARK.json` names a
configuration (`configs/<name>.json`, with its plain reference under
`references/`) and a traffic mix (`traffic/<name>.json`); its limits are in
`limits/<workload>.json`; each per-layer metric is read by
`metrics/<metric>.py`. A new cell, configuration or metric is new files.

A run: JAX must see a TPU and as many chips as the cell asks for, else the
run exits 2 with no result. Set-up drives the program from the seed through
its first steps (the check reads them) and one warm dispatch; the window then
measures `--seconds` (`--trace 1`: records a few seconds of it with the
profiler instead, for the per-layer metrics). Once the window has closed and
the memory peak is read, the program's state is freed and the reference
follows the same first steps; `correct` says whether every compared number is
within its limit. The last lines of standard error list them, and the last
line of standard output is the result (a JSON object).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Cell:
    """A workload of `BENCHMARK.json` with every file it names, looked up
    under the checkout `root`."""

    def __init__(self, name: str, root: str = ROOT):
        bench = load_json(root, "BENCHMARK.json")
        here = os.path.join(root, bench["paths"][0])
        self.here = here
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(known: {', '.join(sorted(cells))})")
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in bench["configs"]}
        self.cfg = load_json(root, configs[self.entry["config"]]["file"])
        self.traffic = load_json(here, "traffic", f"{self.entry['traffic']}.json")
        limits = os.path.join(here, "limits", f"{name}.json")
        # a cell without a limits file is never correct: every number fails
        limits = load_json(limits) if os.path.exists(limits) else {}
        self.limits = limits.get("limits", {})
        import check

        self.check_steps = int(limits.get("steps", check.CHECK_STEPS))
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]

    def reader(self, metric: str):
        """The module that reads per-layer metric `metric`:
        `metrics/<metric>.py`."""
        path = os.path.join(self.here, "metrics", f"{metric}.py")
        spec = importlib.util.spec_from_file_location(f"metrics.{metric}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod


def device_info(chips: int) -> dict:
    """The devices as JAX reports them; exits 2, with no result, unless JAX
    sees a TPU and at least `chips` of them."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        print(f"run.py: JAX found no TPU (platform {d.platform!r}); the benchmark "
              f"runs on the chip only", file=sys.stderr)
        sys.exit(2)
    if len(devs) < chips:
        print(f"run.py: the cell needs {chips} chips, JAX sees {len(devs)}",
              file=sys.stderr)
        sys.exit(2)
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    peaks = [(dev.memory_stats() or {}).get("peak_bytes_in_use", 0) for dev in jax.devices()]
    return int(max(peaks))


class Traced:
    """What a per-layer reader is given."""

    def __init__(self, cell: Cell, trace, steps: int, tokens: int, peaks: dict):
        self.cfg, self.traffic, self.chips = cell.cfg, cell.traffic, cell.chips
        self.trace, self.traced_steps, self.traced_tokens = trace, steps, tokens
        self.peaks = peaks


def measure(cell: Cell, seed: int, seconds: float, trace: bool, device: dict,
            wrap=None) -> dict:
    """One run without the device check and without printing the result:
    the result dict (the tests rehearse cells on the CPU through here)."""
    import check
    import counts
    import training

    trace_dir = ""
    if trace:
        trace_dir = os.path.join(ROOT, ".cache", "chipbench-trace", cell.name)
        if os.path.isdir(trace_dir):
            import shutil

            shutil.rmtree(trace_dir)
    got = training.run(cell.cfg, cell.traffic, seed, seconds, cell.check_steps,
                       trace_dir, wrap=wrap)
    setup_s = got["t0"] - T_START
    device = dict(device, memory_peak_bytes=memory_peak_bytes())
    print(f"[bench] {cell.name} seed {seed}: set-up {setup_s:.3f} s, window "
          f"{got['t1'] - got['t0']:.3f} s, {got['steps']} steps, "
          f"compilations in the window {got['compiles']}", file=sys.stderr, flush=True)

    metrics, breakdown = {}, None
    if trace:
        import xplane as tr

        t = tr.Trace(tr.find(trace_dir))
        peaks = counts.peaks(device["kind"]) if device["platform"] == "tpu" else None
        run = Traced(cell, t if t.ops else None, got["steps"], got["tokens"], peaks)
        for m in cell.per_layer:
            reader = cell.reader(m["name"])
            value = reader.read(run) if (run.trace is not None and peaks) else None
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if t.ops:
            device["busy_s"] = t.busy_s()
            device["window_s"] = t.window_s()
            breakdown = {"device_ops": t.top_ops(), "idle_gaps": t.idle_gaps()}
    else:
        rate = got["tokens"] / (got["t1"] - got["t0"])
        values = {"train_tokens_per_s": rate, "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    # the program's state is gone with `fit`'s frames; free what is left
    # before the reference takes the chip
    prog = got.pop("readings")
    batches = got.pop("batches")
    gc.collect()
    t_ref = time.perf_counter()
    ref = check.reference_readings(cell.cfg, cell.traffic, seed, batches, cell.check_steps)
    print(f"[bench] the reference took {time.perf_counter() - t_ref:.3f} s",
          file=sys.stderr, flush=True)
    nums = check.numbers(prog, ref)
    nums["compiles_in_window"] = got["compiles"]
    limits = dict(cell.limits, compiles_in_window=0)
    correct, rows = check.judge(nums, limits)
    out = {"correct": bool(correct and got["failed"] == 0),
           "attempted": got["steps"], "failed": got["failed"],
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    # JSON has no infinity: a number that could not be read is past any limit
    finite = lambda v: v if math.isfinite(v) else 1e300
    out["check"] = {k: {"value": finite(v), "limit": lim} for k, v, lim in rows}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = Cell(args.workload)
    device = device_info(cell.chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.common.cache import enable_compile_cache

    enable_compile_cache()
    out = measure(cell, args.seed, args.seconds, bool(args.trace), device)
    for name, c in out["check"].items():
        print(f"check {name}: {c['value']} limit {c['limit']}", file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
