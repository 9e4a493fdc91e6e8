"""The chunked/prefetched mesh fit pipeline (repro.engine.trainloop, DESIGN.md §9).

The headline contract: every dispatch is one compiled loop over a chunk of K
train steps, and any K (1 included) with or without the async double-buffered
prefetch gives the same run BIT for bit — params, GuidedState and per-step
history, leaf for leaf, for every registered strategy — while checkpoint
cadence, bit-exact resume (including resume points between natural chunk
boundaries), SIGTERM drain and the on_step contract all survive the
regrouping. Plus the satellites: the chunk schedule, the prefetcher, the
chunk-aware synthetic stream, and needs_correction skipping the second
weighted forward+backward.
"""
import os
import signal
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.engine import ExperimentSpec, Trainer
from repro.engine.trainloop import build_chunk_step, chunk_schedule

# tiny operating point: per-step compute is trivial, so the 6-strategy parity
# matrix stays compile-bound rather than step-bound
TINY = (("n_layers", 1), ("d_model", 16), ("d_ff", 32), ("vocab_size", 128),
        ("n_heads", 2), ("n_kv_heads", 2))


def _spec(strategy="guided_fused", mode="ssgd", **kw):
    base = dict(backend="mesh", arch="yi_9b", reduced=True, mode=mode,
                strategy=strategy, rho=3, staleness=2, lr=5e-2, seed=0, steps=6,
                seq_len=8, global_batch=4, workers=2, model_overrides=TINY)
    base.update(kw)
    return ExperimentSpec(**base)


def _assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------------------------ chunk schedule


def test_chunk_schedule_partitions_and_tail():
    assert chunk_schedule(0, 10, 4) == [4, 4, 2]
    assert chunk_schedule(0, 6, 1) == [1] * 6
    assert chunk_schedule(0, 0, 4) == []
    assert chunk_schedule(0, 3, 64) == [3]


def test_chunk_schedule_splits_at_ckpt_multiples():
    # every multiple of ckpt_every lands on a chunk boundary (split, not shifted)
    assert chunk_schedule(0, 10, 4, ckpt_every=5) == [4, 1, 4, 1]
    assert chunk_schedule(0, 8, 2, ckpt_every=3) == [2, 1, 2, 1, 2]
    # resume mid-cadence re-aligns at the next multiple
    assert chunk_schedule(3, 10, 4, ckpt_every=5) == [2, 4, 1]
    for start, stop, k, every in [(0, 23, 8, 5), (7, 40, 16, 6), (3, 9, 2, 4)]:
        sizes = chunk_schedule(start, stop, k, every)
        assert sum(sizes) == stop - start and all(1 <= s <= k for s in sizes)
        done = start
        boundaries = set()
        for s in sizes:
            done += s
            boundaries.add(done)
        for mult in range(start + 1, stop):
            if mult % every == 0:
                assert mult in boundaries, (start, stop, k, every, mult)


def test_chunk_schedule_rejects_bad_chunk_steps():
    with pytest.raises(ValueError, match="chunk_steps"):
        chunk_schedule(0, 4, 0)
    with pytest.raises(ValueError, match="chunk_steps must be >= 1"):
        ExperimentSpec(backend="mesh", chunk_steps=0)


# ----------------------------------------------------- the bit-exact headline

# every registered strategy under its natural execution mode
STRATEGIES = [
    ("none", "ssgd"),
    ("guided_fused", "ssgd"),
    ("guided_two_pass", "ssgd"),
    ("dc_asgd", "asgd"),
    ("dc_asgd_guided", "asgd"),
    ("gap_aware", "asgd"),
]


@pytest.mark.parametrize("strategy,mode", STRATEGIES)
def test_chunked_matches_stepwise_bit_exact(strategy, mode):
    """fit(6) with chunk_steps=4 (sizes [4, 2]: a full chunk AND an uneven
    tail) reproduces the per-step loop leaf for leaf — params, the whole
    GuidedState, and the per-step history."""
    stepwise = Trainer.from_spec(_spec(strategy, mode)).fit()
    chunked = Trainer.from_spec(_spec(strategy, mode, chunk_steps=4)).fit()
    _assert_trees_equal(stepwise.model, chunked.model)
    _assert_trees_equal(stepwise.state, chunked.state)
    assert stepwise.history == chunked.history  # per-step records, bit-equal
    assert chunked.n_steps == 6


@pytest.mark.parametrize("strategy,mode", STRATEGIES)
def test_chunk_of_one_matches_chunk_of_two_bit_exact(strategy, mode):
    """A chunk of one is the same loop body as a longer chunk: fit(6) in six
    dispatches of one step equals fit(6) in three of two, leaf for leaf."""
    ones = Trainer.from_spec(_spec(strategy, mode)).fit()
    twos = Trainer.from_spec(_spec(strategy, mode, chunk_steps=2)).fit()
    _assert_trees_equal(ones.model, twos.model)
    _assert_trees_equal(ones.state, twos.state)
    assert ones.history == twos.history


def test_chunk_of_one_compiles_to_a_loop():
    """XLA inlines a loop it can count to one, and the inlined step rounds
    differently; the dispatch built at chunk_steps=1 must keep its `while`."""
    from repro.data import make_batch_for
    from repro.engine import mesh as M
    from repro.engine.trainloop import build_dispatch
    from repro.optim import get_optimizer

    spec = _spec()
    cfg, ctx = spec.model_config(), M.build_ctx("local")
    strat = Trainer.from_spec(spec).strategy
    params, _, gstate = M.init_train_state(
        jax.random.PRNGKey(0), cfg, spec.to_guided_config(),
        get_optimizer(spec.optimizer), n_workers=2, strategy=strat)
    block = {k: jnp.asarray(v)[None]
             for k, v in make_batch_for(cfg, 8, 4, seed=0).items()}
    dispatch = build_dispatch(spec, strat, cfg, ctx, 2, spec.steps)
    text = dispatch.lower(params, gstate, block).compile().as_text()
    assert " while(" in text


def test_prefetch_is_bit_exact_chunked_and_stepwise():
    """The async double buffer changes staging, never values: prefetched runs
    equal their synchronous twins on both the chunked and per-step paths."""
    stepwise = Trainer.from_spec(_spec()).fit()
    for kw in (dict(chunk_steps=4, prefetch=True), dict(prefetch=True)):
        got = Trainer.from_spec(_spec(**kw)).fit()
        _assert_trees_equal(stepwise.model, got.model)
        _assert_trees_equal(stepwise.state, got.state)
        assert stepwise.history == got.history
    assert threading.active_count() == 1  # prefetch workers joined


def test_chunked_with_explicit_data_stream():
    """Caller-provided batch iterables stack into blocks identically."""
    from repro.data import make_batch_for

    spec = _spec()
    cfg = spec.model_config()
    batches = [make_batch_for(cfg, 8, 4, seed=i) for i in range(6)]
    a = Trainer.from_spec(spec).fit(data=[dict(b) for b in batches])
    b = Trainer.from_spec(_spec(chunk_steps=3, prefetch=True)).fit(
        data=[dict(bb) for bb in batches])
    _assert_trees_equal(a.model, b.model)
    _assert_trees_equal(a.state, b.state)
    assert a.history == b.history


def test_chunked_short_data_stream_raises():
    with pytest.raises(ValueError, match="exhausted mid-chunk"):
        from repro.data import make_batch_for

        spec = _spec(chunk_steps=4)
        cfg = spec.model_config()
        Trainer.from_spec(spec).fit(
            data=[make_batch_for(cfg, 8, 4, seed=i) for i in range(3)])


# -------------------------------------------------------- cadence interaction


def test_chunked_checkpoints_land_on_stepwise_cadence(tmp_path):
    """ckpt_every=3 misaligned with chunk_steps=2: chunks split so snapshots
    land at exactly the steps the per-step loop would write (3, 6, then the
    final 6-dedupe)."""
    from repro.checkpoint import read_manifest

    da, db = str(tmp_path / "step"), str(tmp_path / "chunk")
    Trainer.from_spec(_spec(ckpt_dir=da, ckpt_every=3, keep_last=0)).fit()
    Trainer.from_spec(_spec(ckpt_dir=db, ckpt_every=3, keep_last=0,
                            chunk_steps=2, prefetch=True)).fit()
    steps_a = [c["step"] for c in read_manifest(da)["ckpts"]]
    steps_b = [c["step"] for c in read_manifest(db)["ckpts"]]
    assert steps_a == steps_b == [3, 6]
    A = np.load(os.path.join(da, "step_00000003.npz"))
    B = np.load(os.path.join(db, "step_00000003.npz"))
    assert sorted(A.files) == sorted(B.files)
    for k in A.files:
        np.testing.assert_array_equal(A[k], B[k], err_msg=k)


@pytest.mark.parametrize("cut", [3, 4])
def test_chunked_resume_bit_exact_on_and_between_boundaries(cut, tmp_path):
    """Resume from a snapshot at step 3 (BETWEEN chunk_steps=2 boundaries of
    the original schedule — only a ckpt-split put a boundary there) and at
    step 4 (ON a natural boundary): both complete bit-exactly."""
    d = str(tmp_path)
    full = Trainer.from_spec(_spec()).fit()  # stepwise reference
    Trainer.from_spec(_spec(chunk_steps=2, steps=cut, ckpt_dir=d)).fit()
    resumed = Trainer.from_spec(_spec(chunk_steps=2, ckpt_dir=d,
                                      prefetch=True)).fit(resume=True)
    assert resumed.start_step == cut and resumed.n_steps == 6 - cut
    _assert_trees_equal(full.model, resumed.model)
    _assert_trees_equal(full.state, resumed.state)
    assert int(resumed.state.step) == 6


def test_sigterm_mid_chunk_drains_and_resumes(tmp_path):
    """SIGTERM while a chunk is in flight: the chunk drains, the snapshot
    holds a consistent (chunk-boundary) step count, resume is bit-exact."""
    from repro.checkpoint import latest_step

    d = str(tmp_path)
    full = Trainer.from_spec(_spec()).fit()

    def kill_in_first_chunk(step, m, params):
        if step <= 3:  # fires at the first chunk's END (step=3 for k=4)
            os.kill(os.getpid(), signal.SIGTERM)

    part = Trainer.from_spec(_spec(chunk_steps=4, prefetch=True, ckpt_dir=d)).fit(
        on_step=kill_in_first_chunk)
    assert part.interrupted
    assert part.n_steps == 4          # the in-flight chunk completed, whole
    assert latest_step(d) == 4        # snapshot at its boundary
    resumed = Trainer.from_spec(_spec(chunk_steps=4, ckpt_dir=d)).fit(resume=True)
    assert resumed.start_step == 4 and not resumed.interrupted
    _assert_trees_equal(full.model, resumed.model)
    _assert_trees_equal(full.state, resumed.state)
    assert threading.active_count() == 1


# ------------------------------------------------------------ on_step contract


def test_on_step_fires_per_chunk_with_stacked_metrics():
    seen = []

    def cb(step, m, params):
        seen.append((step, tuple(getattr(m["loss"], "shape", ()))))

    Trainer.from_spec(_spec(chunk_steps=4)).fit(on_step=cb)
    # one call per chunk, step = LAST step of the chunk, metrics stacked (k,)
    assert seen == [(3, (4,)), (5, (2,))]


def test_on_step_chunk_steps_1_fires_per_step_with_stacked_metrics():
    seen = []

    def cb(step, m, params):
        seen.append((step, tuple(m["loss"].shape)))

    Trainer.from_spec(_spec()).fit(on_step=cb)
    assert seen == [(i, (1,)) for i in range(6)]  # per step, (1,) metrics


def test_launcher_chunked_run_logs_per_step_history(capsys):
    """--chunk-steps/--prefetch thread through the CLI; the launcher's
    log-cadence history is identical to a stepwise run's."""
    from repro.launch.train import main as train_main

    common = ["--arch", "yi_9b", "--reduced", "--steps", "6", "--seq", "8",
              "--batch", "4", "--workers", "2", "--rho", "3",
              "--log-every", "2"]
    h_step = train_main(common)
    h_chunk = train_main(common + ["--chunk-steps", "4", "--prefetch"])
    assert [r["step"] for r in h_chunk] == [0, 2, 4, 5]
    assert h_chunk == h_step


# ------------------------------------------------- chunk-aware batch stream


def test_stack_blocks_preserves_the_per_step_stream():
    """Chunk-aware synthetic generation: stacked (K, ...) blocks unstack to
    exactly the per-step stream (same seed protocol, same draws)."""
    from repro.data import stack_blocks, synthetic_lm_batches

    ref = synthetic_lm_batches(64, 8, 4, seed=3, n_corpora=2)
    chunked = synthetic_lm_batches(64, 8, 4, seed=3, n_corpora=2)
    blocks = list(stack_blocks(chunked, [3, 2, 1]))
    assert [b["tokens"].shape for b in blocks] == [(3, 4, 8), (2, 4, 8), (1, 4, 8)]
    i = 0
    for blk in blocks:
        for j in range(blk["tokens"].shape[0]):
            step = next(ref)
            for key in step:
                np.testing.assert_array_equal(blk[key][j], step[key])
            i += 1
    assert i == 6


def test_stack_blocks_exhaustion_names_the_shortfall():
    from repro.data import stack_blocks

    it = iter([{"x": np.zeros(2)}] * 2)
    with pytest.raises(ValueError, match=r"got 0 of 3"):
        list(stack_blocks(it, [2, 3]))


# ------------------------------------------------------------- the prefetcher


def test_prefetcher_yields_in_order_and_joins():
    from repro.data.prefetch import ChunkPrefetcher

    src = [{"x": np.full((2,), i)} for i in range(7)]
    pf = ChunkPrefetcher(iter(src), put=lambda t: t, depth=2)
    got = [int(item["x"][0]) for item in pf]
    assert got == list(range(7))
    pf.close()
    assert threading.active_count() == 1


def test_prefetcher_propagates_source_errors():
    from repro.data.prefetch import ChunkPrefetcher

    def bad():
        yield {"x": np.zeros(1)}
        raise RuntimeError("generator blew up")

    pf = ChunkPrefetcher(bad(), put=lambda t: t)
    assert int(pf.__next__()["x"][0]) == 0
    with pytest.raises(RuntimeError, match="blew up"):
        next(pf)
    pf.close()


def test_prefetcher_close_mid_stream_unblocks_worker():
    from repro.data.prefetch import ChunkPrefetcher

    def endless():
        i = 0
        while True:
            yield {"x": np.full((1,), i)}
            i += 1

    pf = ChunkPrefetcher(endless(), put=lambda t: t, depth=2)
    next(pf)
    pf.close()  # worker blocked on a full queue must exit
    assert threading.active_count() == 1


def test_prefetcher_worker_death_propagates_transfer_errors():
    """The device-put can die too (OOM, bad dtype), not just the source
    generator: the consumer must see that error, never a silent hang."""
    from repro.data.prefetch import ChunkPrefetcher

    def put(tree):
        if int(tree["x"][0]) == 2:
            raise ValueError("transfer exploded")
        return tree

    src = [{"x": np.full((1,), i)} for i in range(5)]
    pf = ChunkPrefetcher(iter(src), put=put)
    assert [int(next(pf)["x"][0]) for _ in range(2)] == [0, 1]
    with pytest.raises(ValueError, match="transfer exploded"):
        for _ in range(3):
            next(pf)
    pf.close()
    assert threading.active_count() == 1


def test_prefetcher_worker_death_drains_staged_items_first():
    """Items committed before the death still arrive, in order — the error
    surfaces exactly where the stream broke, not earlier."""
    from repro.data.prefetch import ChunkPrefetcher

    def dying():
        for i in range(3):
            yield {"x": np.full((1,), i)}
        raise OSError("source died")

    pf = ChunkPrefetcher(dying(), put=lambda t: t, depth=2)
    assert [int(next(pf)["x"][0]) for _ in range(3)] == [0, 1, 2]
    with pytest.raises(OSError, match="source died"):
        next(pf)
    # the error is consumed: the stream is over, not stuck raising forever
    with pytest.raises(StopIteration):
        next(pf)
    pf.close()
    assert threading.active_count() == 1


def test_prefetcher_worker_death_does_not_hang_consumer():
    """A consumer polling a dead worker gets end-of-stream promptly (the
    is_alive fallback), bounded well under the watchdog horizon."""
    import time

    from repro.data.prefetch import ChunkPrefetcher

    pf = ChunkPrefetcher(iter(()), put=lambda t: t)
    pf._thread.join(timeout=10.0)
    t0 = time.monotonic()
    with pytest.raises(StopIteration):
        next(pf)
    assert time.monotonic() - t0 < 5.0
    pf.close()


def test_batch_put_local_matches_asarray():
    from repro.data.prefetch import batch_put
    from repro.sharding.rules import LOCAL_CTX

    put = batch_put(LOCAL_CTX)
    out = put({"tokens": np.arange(12).reshape(2, 3, 2)})
    assert isinstance(out["tokens"], jax.Array)
    np.testing.assert_array_equal(np.asarray(out["tokens"]),
                                  np.arange(12).reshape(2, 3, 2))


# --------------------------------------------- needs_correction (satellite)


def test_needs_correction_flags():
    from repro.core.guided import GuidedConfig
    from repro.engine import get_compensator

    gs = GuidedConfig(mode="ssgd")
    ga = GuidedConfig(mode="asgd")
    assert not get_compensator("none", gs).needs_correction
    assert not get_compensator("guided_fused", gs).needs_correction
    assert get_compensator("guided_two_pass", gs).needs_correction
    assert not get_compensator("dc_asgd", ga).needs_correction
    assert not get_compensator("gap_aware", ga).needs_correction
    # composed strategy: only its two_pass flavour runs the second update
    fused = GuidedConfig(mode="dc_asgd", guided=True, correction="fused")
    twop = GuidedConfig(mode="dc_asgd", guided=True, correction="two_pass")
    assert not get_compensator("dc_asgd_guided", fused).needs_correction
    assert get_compensator("dc_asgd_guided", twop).needs_correction


@pytest.mark.parametrize("strategy,n_forwards", [
    ("guided_fused", 1),     # replay folded into THIS backward: one forward
    ("guided_two_pass", 2),  # the literal second update traces a second one
])
def test_fused_step_compiles_without_second_forward(strategy, n_forwards):
    """The jitted step of a non-correcting strategy must not trace
    weighted_grad_fn's second forward+backward at all (HLO size / compile
    time), while two_pass still gets its lax.cond'd replay."""
    import repro.models.transformer as T
    from repro.analysis import assert_traces
    from repro.data import make_batch_for
    from repro.engine import mesh as M
    from repro.optim import constant, get_optimizer

    spec = _spec(strategy, "ssgd")
    cfg, gcfg = spec.model_config(), spec.to_guided_config()
    opt = get_optimizer("sgd")
    strat = Trainer.from_spec(spec).strategy
    step = M.build_train_step(cfg, gcfg, opt, M.build_ctx("local"),
                              constant(1e-2), n_workers=2, strategy=strat)
    params, _, gstate = M.init_train_state(
        jax.random.PRNGKey(0), cfg, gcfg, opt, n_workers=2, strategy=strat)
    batch = {k: jnp.asarray(v) for k, v in make_batch_for(cfg, 8, 4, seed=0).items()}
    with assert_traces(n_forwards, (T, "forward_train")):
        jax.make_jaxpr(step)(params, gstate, batch)


# --------------------------------------------- compile/warm split (satellite)


def test_report_splits_compile_from_warm_throughput():
    r = Trainer.from_spec(_spec(chunk_steps=3)).fit()  # sizes [3, 3]
    assert r.compile_time_s > 0
    assert r.warm_steps == 3  # 6 steps minus the first (compiling) dispatch
    # warm time covers the warm dispatches alone: no compile windows, no
    # out-of-loop setup/teardown
    assert 0 < r.warm_time_s < r.wall_time_s - r.compile_time_s
    assert r.steps_per_s == pytest.approx(r.warm_steps / r.warm_time_s)

    # an uneven tail compiles its OWN program: both dispatches of sizes
    # [4, 2] count as compile, warm_steps drops to 0 and steps_per_s falls
    # back to the whole-run average instead of mislabeling a compile as warm
    r2 = Trainer.from_spec(_spec(chunk_steps=4)).fit()
    assert r2.warm_steps == 0
    assert r2.steps_per_s == pytest.approx(r2.n_steps / r2.wall_time_s)


def test_build_chunk_step_shapes():
    """build_chunk_step is usable standalone: (K, ...) stacked batch in,
    (K,)-stacked metrics out, carry threaded through."""

    def toy_step(p, g, batch):
        p = {"w": p["w"] + batch["x"].sum()}
        return p, g + 1, {"loss": batch["x"].mean()}

    chunk = build_chunk_step(toy_step)
    p, g, m = chunk({"w": jnp.zeros(())}, jnp.asarray(0),
                    {"x": jnp.arange(6.0).reshape(3, 2)})
    assert float(p["w"]) == 15.0 and int(g) == 3
    assert m["loss"].shape == (3,)


# ----------------------------------------------- profiler spans, phase scopes


def _host_events(trace_dir):
    """The host events of the trace under `trace_dir`."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    return [e for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:") for line in plane.lines
            for e in line.events]


def test_fit_writes_its_spans_into_a_profiler_trace(tmp_path):
    """Under a profiler session the chunked, prefetched fit (sizes [2, 2, 2],
    a snapshot every 2 steps) writes each host span of the loop and of the
    prefetch worker, as often as the work happens; the trajectory is the one
    the same fit follows with no session."""
    from collections import Counter

    def spec(d):
        return _spec(chunk_steps=2, prefetch=True, ckpt_dir=str(tmp_path / d),
                     ckpt_every=2, keep_last=0)

    def on_step(step, m, params):
        pass

    plain = Trainer.from_spec(spec("plain")).fit(on_step=on_step)
    with jax.profiler.trace(str(tmp_path / "trace")):
        traced = Trainer.from_spec(spec("traced")).fit(on_step=on_step)
    assert traced.history == plain.history

    events = _host_events(str(tmp_path / "trace"))
    n = Counter(e.name for e in events)
    blocks = 3
    assert n["fit.dispatch"] == n["fit.input_wait"] == n["fit.on_step"] == blocks
    assert n["fit.compile"] == 1  # one chunk shape, compiled by the first dispatch
    assert n["fit.checkpoint"] == blocks + 1  # every 2 steps, and the final save
    assert n["prefetch.put"] == blocks
    # the worker's last draw finds the end of the stream, if it gets there
    # before the loop closes the prefetcher
    assert n["prefetch.make"] in (blocks, blocks + 1)
    # each dispatch is a step span numbered by its chunk's first step
    steps = [dict(e.stats)["step_num"] for e in events if e.name == "fit.dispatch"]
    assert sorted(steps) == [0, 2, 4]


def test_train_step_ops_carry_their_phase_scope():
    """Every matmul of the compiled guided_fused SGD step (remat on, as on the
    chip) names its phase in its op_name: the forward (its backward is
    `transpose(jvp(forward))`, its recompute `rematted_computation`), the
    loss head, the update or the guided bookkeeping."""
    import re

    from repro.data import make_batch_for
    from repro.engine import mesh as M
    from repro.optim import constant, get_optimizer

    spec = _spec("guided_fused", "ssgd", model_overrides=TINY + (("remat", "full"),))
    cfg, gcfg = spec.model_config(), spec.to_guided_config()
    opt = get_optimizer("sgd")
    strat = Trainer.from_spec(spec).strategy
    step = M.build_train_step(cfg, gcfg, opt, M.build_ctx("local"),
                              constant(1e-2), n_workers=2, strategy=strat)
    params, _, gstate = M.init_train_state(
        jax.random.PRNGKey(0), cfg, gcfg, opt, n_workers=2, strategy=strat)
    batch = {k: jnp.asarray(v) for k, v in make_batch_for(cfg, 8, 4, seed=0).items()}
    text = jax.jit(step).lower(params, gstate, batch).compile().as_text()
    names = [n for n in re.findall(r'op_name="([^"]*)"', text)
             if n.endswith(("dot_general", "conv_general_dilated"))]
    assert names
    phases = ("/jvp(forward)/", "/transpose(jvp(forward))/", "/loss_head/",
              "/update/", "/guided/")
    for name in names:
        assert any(p in name + "/" for p in phases), name
    for part in ("/loss_head/", "/transpose(jvp(forward))/", "/rematted_computation/"):
        assert any(part in n for n in names), part
