"""The per-layer readers of the program's host spans: on a hand-made trace
with spans on two host threads, on the committed chip trace of a program
without spans, and on the spans a fit writes into a real trace."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

import xplane  # noqa: E402

READERS = ("input_wait_ms", "prefetch_busy_ms", "host_dispatch_ms")


def read(metric):
    import importlib

    return importlib.import_module(f"metrics.{metric}").read


def _run(host, steps=4):
    """Two dispatches of two steps each on a device timeline (ns)."""
    t = xplane.Trace.__new__(xplane.Trace)
    t.ops = {0: xplane._nest([("%fusion.1 = bf16[8] fusion(...)", 1_000, 3_000_000),
                              ("%fusion.2 = bf16[8] fusion(...)", 3_010_000, 6_000_000)])}
    t.host = host
    return type("Run", (), {"trace": t, "traced_steps": steps, "chips": 1})()


#: the main thread's loop and the prefetch worker's spans, interleaved in
#: time as the two threads run them, among other host events
SPANS = [
    ("fit.input_wait", 0, 20_000),
    ("fit.dispatch", 20_000, 520_000),
    ("prefetch.make", 30_000, 2_030_000),    # the worker, during the dispatch
    ("fit.on_step", 520_000, 2_900_000),
    ("prefetch.put", 2_030_000, 2_130_000),
    ("fit.input_wait", 3_000_000, 3_010_000),
    ("fit.dispatch", 3_010_000, 3_310_000),
    ("prefetch.make", 3_020_000, 5_020_000),
    ("prefetch.put", 5_020_000, 5_120_000),
    ("$builtins.next", 3_000_500, 3_009_000),  # a Python tracer event
]


def test_readers_sum_their_spans_per_step():
    run = _run(SPANS)
    assert read("input_wait_ms")(run) == pytest.approx((20_000 + 10_000) * 1e-6 / 4)
    assert read("host_dispatch_ms")(run) == pytest.approx((500_000 + 300_000) * 1e-6 / 4)
    assert read("prefetch_busy_ms")(run) == pytest.approx(
        (2_000_000 + 100_000) * 2 * 1e-6 / 4)


@pytest.mark.parametrize("metric", READERS)
def test_readers_read_nothing_without_their_spans(metric):
    others = [s for s in SPANS if s[0] in ("$builtins.next", "fit.on_step")]
    assert read(metric)(_run(others)) is None
    assert read(metric)(_run(SPANS, steps=0)) is None
    # a chip trace recorded from a program that writes no spans
    t = xplane.Trace(os.path.join(HERE, "testdata", "small.xplane.pb"))
    run = type("Run", (), {"trace": t, "traced_steps": 3, "chips": 1})()
    assert read(metric)(run) is None


def test_readers_find_the_spans_a_fit_writes(tmp_path):
    """The names the program writes are the names the readers look for: a
    tiny chunked, prefetched fit on the CPU, traced, read through
    `xplane.Trace` (a CPU trace has no TPU plane, so its device ops stand in)."""
    import jax

    from repro.engine import ExperimentSpec, Trainer

    spec = ExperimentSpec(
        backend="mesh", arch="yi_9b", reduced=True, mode="ssgd", strategy="guided_fused",
        rho=3, lr=5e-2, seed=0, steps=6, seq_len=8, global_batch=4, workers=2,
        chunk_steps=2, prefetch=True,
        model_overrides=(("n_layers", 1), ("d_model", 16), ("d_ff", 32),
                         ("vocab_size", 128), ("n_heads", 2), ("n_kv_heads", 2)))
    with jax.profiler.trace(str(tmp_path)):
        Trainer.from_spec(spec).fit()
    t = xplane.Trace(xplane.find(str(tmp_path)))
    run = _run(t.host, steps=6)
    for metric in READERS:
        assert read(metric)(run) > 0, metric
