"""The reduction from a profiler trace to busy and idle time, kernel time and
exposed collective time: on a small trace recorded on a TPU v5e, and on
hand-made timelines."""
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import xplane  # noqa: E402
from metrics.guided_update_ms import KERNEL  # noqa: E402

SMALL = os.path.join(HERE, "testdata", "small.xplane.pb")


def test_interval_arithmetic():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert xplane.total([(0, 3), (5, 8)]) == 6
    # the parts of [0,10) and [20,30) outside [2,4), [8,22), [25,26)
    assert xplane.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22), (25, 26)]) == 2 + 4 + 3 + 4


def _trace(ops):
    t = xplane.Trace.__new__(xplane.Trace)
    t.ops = {d: xplane._nest(e) for d, e in ops.items()}
    t.host = [("python: next(batches)", 95, 130)]
    return t


def test_nesting_busy_idle_and_exposed_collectives():
    # a loop holding two ops, an all-reduce half hidden under compute, then
    # an idle gap of 30 ns the host spent drawing a batch
    dev = [("%while.1 = (...) while(...)", 0, 100),
           ("%fusion.1 = f32[4] fusion(...)", 0, 40),
           ("%all-reduce.2 = f32[4] all-reduce(...)", 40, 80),
           ("%fusion.3 = f32[4] fusion(...)", 60, 100),
           ("%fusion.4 = f32[4] fusion(...)", 130, 150)]
    t = _trace({0: dev, 1: dev})
    assert t.window() == (0, 150)
    assert t.busy_s() == pytest.approx(120e-9)
    assert t.collective_exposed_s() == pytest.approx(20e-9)  # 40..60 only
    top = dict(t.top_ops())
    assert top["while.1 (...)"] == pytest.approx(0.0)  # all its time is its ops'
    assert top["fusion.1 f32[4]"] == pytest.approx(40e-9)
    assert t.idle_gaps() == [["host: python: next(batches)", pytest.approx(30e-9)]]
    # the per-layer reader of the exposed exchange, per step of 2 chips
    run = type("Run", (), {"trace": t, "chips": 2, "traced_steps": 2})()
    assert read("collective_exposed_ms")(run) == pytest.approx(10e-6)
    run.chips = 1  # nothing to read on one chip
    assert read("collective_exposed_ms")(run) is None


def read(metric):
    import importlib

    return importlib.import_module(f"metrics.{metric}").read


def test_small_chip_trace_reduces_to_fixed_numbers():
    """Three dispatches of a jitted step (one matmul, two fused sgd update
    kernels on a (1024, 2048) bf16 leaf), traced on a TPU v5e."""
    t = xplane.Trace(SMALL)
    assert t.devices == [0]
    assert os.path.getsize(SMALL) < 1 << 20
    numbers = {"window_s": t.window_s(), "busy_s": t.busy_s(),
               "kernel_s": t.op_seconds(KERNEL),
               "kernels": sum(1 for x in t.ops[0] if re.search(KERNEL, x["name"]))}
    assert numbers == pytest.approx(EXPECTED)


#: what the committed trace reduces to (two update kernels in each of the
#: three dispatches)
EXPECTED = {"window_s": 762.205e-6, "busy_s": 261.242e-6, "kernel_s": 65.7e-6, "kernels": 6}
