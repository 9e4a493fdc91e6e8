"""Pipelined mesh fit loop: multi-step chunk dispatch + prefetch (DESIGN.md §9).

`fit` runs one compiled program, the chunk: K train steps in a loop over a
stacked `(K, ...)` batch block, with the `(params, gstate)` carry donated
end-to-end and the metrics stacked to `(K,)` arrays on device, so the host
dispatches and syncs once per chunk instead of once per step.

  * `chunk_schedule` partitions the step range into dispatch chunks of at most
    `spec.chunk_steps` steps, split (never shifted) so every `ckpt_every`
    multiple lands on a chunk boundary — the snapshot cadence is preserved
    exactly, and a resume point may land anywhere in the schedule;
  * `build_chunk_step` builds the chunk. Every size, one included, compiles
    the step as a loop body, which is what makes any partition of a run into
    chunks follow the same trajectory;
  * the `repro.data.prefetch` double buffer stages block i+1 (batch
    generation, stacking, and the `jax.device_put` against the data-shard
    sharding) on a worker thread while chunk i computes.

Contracts (locked in tests/test_trainloop.py):

  * partition invariance — fit(N) is the same leaf for leaf (params, gstate,
    and per-step history) whatever `chunk_steps` and `prefetch` are, for
    every registered strategy;
  * checkpoints land on exactly the steps of the `ckpt_every` cadence, and
    resume is bit-exact from any snapshot, including resume points between
    the natural chunk boundaries (the schedule is recomputed from
    `start_step`);
  * SIGTERM drains the in-flight chunk, snapshots the full state at its
    boundary, and returns `Report.interrupted=True`;
  * `on_step(step, metrics, params)` fires once per chunk with the stacked
    `(k,)` device metrics and `step` = the LAST step index of the chunk (at
    `chunk_steps=1`, once per step with `(1,)` metrics). The `params` handed
    over are donated to the next dispatch — read or save them synchronously
    inside the callback.

Profiler spans (`jax.profiler`; no-ops when no trace is being recorded):
`fit.input_wait` (taking the next staged block), `fit.dispatch` (the host
side of one dispatch, a step span numbered by the chunk's first step),
`fit.compile` (a chunk shape's first dispatch through its
`block_until_ready`: the region `Report.compile_time_s` sums), `fit.on_step`
(the callback) and `fit.checkpoint` (each snapshot's device->host copy).
"""
from __future__ import annotations

import contextlib
import time
from typing import Callable, List, Optional

from repro.engine.spec import ExperimentSpec


def chunk_schedule(start: int, stop: int, chunk_steps: int,
                   ckpt_every: int = 0) -> List[int]:
    """Sizes of the consecutive dispatch chunks covering steps [start, stop).

    Each chunk is at most `chunk_steps` long; when `ckpt_every` is set, every
    multiple of it lands on a chunk boundary (chunks are split at the cadence,
    never shifted past it), so snapshots land on exactly the cadence's steps
    whatever `chunk_steps` is. A `start` mid-cadence (resume from a snapshot
    that a split chunk produced) re-aligns at the next multiple.
    """
    if chunk_steps < 1:
        raise ValueError(f"chunk_steps must be >= 1 (got {chunk_steps})")
    sizes = []
    s = start
    while s < stop:
        k = min(chunk_steps, stop - s)
        if ckpt_every:
            k = min(k, ckpt_every - s % ckpt_every)
        sizes.append(k)
        s += k
    return sizes


#: Report/launcher history record name -> raw device-metrics key
_METRIC_KEYS = (("loss", "loss"), ("worker_var", "worker_loss_var"),
                ("corr_w", "corr_weight_sum"))


def step_records(m, first: int, indices=None) -> List[dict]:
    """Materialize per-step history records from ONE dispatch's raw device
    metrics, stacked `(k,)` arrays. `first` is the step index of the
    dispatch's first step; `indices` restricts which in-chunk offsets materialize (None -> all).
    The single host transfer per metric happens here, so callers on a
    logging cadence (the launcher) pass only their log offsets and an empty
    selection never syncs at all.
    """
    import jax

    indices = list(range(m["loss"].shape[0]) if indices is None else indices)
    if not indices:
        return []
    # ONE batched host transfer for all metrics of the dispatch
    vals = jax.device_get(tuple(m[key] for _, key in _METRIC_KEYS))  # lint: allow[host-sync-in-hot-loop] the single per-dispatch sync point
    arrs = dict(zip((name for name, _ in _METRIC_KEYS), vals))
    return [{"step": first + i,
             **{name: float(a[i]) for name, a in arrs.items()}}  # lint: allow[host-sync-in-hot-loop] host np scalars after the batched get
            for i in indices]


def build_chunk_step(step_fn: Callable) -> Callable:
    """Fuse `step_fn(params, gstate, batch) -> (params, gstate, metrics)` into
    `chunk_fn(params, gstate, stacked)`: one compiled loop over the leading
    axis of `stacked` (a `(K, ...)`-stacked batch block) with the train state
    as the carry. Returns the final state plus metrics stacked to `(K,)`
    arrays. Jit it with `donate_argnums=(0, 1)` — the carry is donated
    end-to-end.
    """
    import jax
    import jax.numpy as jnp

    def chunk_fn(params, gstate, stacked):
        def body(carry, batch):
            p, g, m = step_fn(carry[0], carry[1], batch)
            return (p, g), m

        if jax.tree.leaves(stacked)[0].shape[0] > 1:
            (params, gstate), metrics = jax.lax.scan(body, (params, gstate), stacked)
            return params, gstate, metrics

        # K = 1: XLA inlines a scan it can count to one, and folds an index
        # into an axis of one, hoisting the batch's ops out of the loop; either
        # way the step compiles to other roundings. So a chunk of one runs the
        # scan's loop over a block of two, with the trip count of one hidden.
        pair = jax.tree.map(lambda x: jnp.concatenate([x, x]), stacked)

        def loop(i, carry):
            batch = jax.tree.map(
                lambda x: jax.lax.dynamic_index_in_dim(x, i, keepdims=False), pair)
            (p, g), m = body(carry[:2], batch)
            ms = jax.tree.map(
                lambda a, x: jax.lax.dynamic_update_index_in_dim(a, x, i, 0),
                carry[2], m)
            return p, g, ms

        shapes = jax.eval_shape(body, (params, gstate),
                                jax.tree.map(lambda x: x[0], stacked))[1]
        ms = jax.tree.map(lambda s: jnp.zeros((2, *s.shape), s.dtype), shapes)
        trips = jax.lax.optimization_barrier(jnp.int32(1))
        params, gstate, ms = jax.lax.fori_loop(0, trips, loop, (params, gstate, ms))
        return params, gstate, jax.tree.map(lambda a: a[:1], ms)

    return chunk_fn


def synthetic_stream(spec: ExperimentSpec, cfg, c: int):
    """The per-step synthetic batch stream for `data=None` mesh fits (the
    deterministic function of (seed, #draws) that makes the checkpoint data
    cursor replayable)."""
    from repro.data import make_batch_for, synthetic_lm_batches

    if cfg.audio_frontend or cfg.arch_type == "vlm":
        def gen():
            i = 0
            while True:
                yield make_batch_for(cfg, spec.seq_len, spec.global_batch,
                                     seed=spec.seed + i)
                i += 1

        return gen()
    return synthetic_lm_batches(cfg.vocab_size, spec.seq_len, spec.global_batch,
                                seed=spec.seed, n_corpora=c)


def build_dispatch(spec: ExperimentSpec, strategy, cfg, ctx, c: int,
                   n_steps: int):
    """The jitted program `fit` dispatches: the chunk (`build_chunk_step`) of
    the strategy-driven train step (sentinel-guarded when `spec.sentinel`),
    with the `(params, gstate)` carry donated.
    Lowering it on the train state's shapes compiles the step without running
    it (`chip_smoke.py` reads the compiled text and memory from there)."""
    import jax

    from repro.engine import mesh as M
    from repro.optim import for_run, get_optimizer

    # schedule phases partition n_steps (for_run); the wsd endpoint
    # actually reaches final_frac before the run ends
    lr = for_run(spec.schedule, spec.lr, spec.warmup, n_steps)
    step_fn = M.build_train_step(cfg, spec.to_guided_config(),
                                 get_optimizer(spec.optimizer), ctx, lr,
                                 n_micro=spec.micro, n_workers=c,
                                 strategy=strategy)
    if spec.sentinel:
        # divergence sentinel (DESIGN.md §14): screen every step ON DEVICE —
        # a rejected step keeps the previous (params, gstate) carry, so one
        # NaN batch costs a step of progress, never the run; the chunk is
        # unchanged because the guard is part of step_fn itself
        from repro.resilience import wrap_step_sentinel

        step_fn = wrap_step_sentinel(step_fn, spec.sentinel,
                                     spec.sentinel_factor)
    return jax.jit(build_chunk_step(step_fn), donate_argnums=(0, 1))


def fit(spec: ExperimentSpec, strategy, data=None, steps: Optional[int] = None,
        on_step: Optional[Callable] = None, keep_history: bool = True,
        resume: bool = False):
    """The mesh backend's fit loop (what `Trainer.fit` dispatches to).

    Returns a `Report` whose `compile_time_s` sums the compiling dispatches
    (the first occurrence of every chunk shape — the uneven tail and
    ckpt-split chunks each compile their own program), whose `warm_steps`
    counts the steps outside them, and whose `warm_time_s` is the wall time
    of those warm dispatches alone (loop span minus compile windows; setup,
    restore and teardown excluded) — `Report.steps_per_s` is their quotient.
    See the module docstring for the chunk/prefetch contracts.
    """
    import signal
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import StepTraceAnnotation, TraceAnnotation

    from repro import checkpoint as C
    from repro.data.prefetch import ChunkPrefetcher, batch_put, stack_blocks
    from repro.engine import mesh as M
    from repro.engine.trainer import Report
    from repro.optim import get_optimizer

    n_steps = steps or spec.steps
    cfg = spec.model_config()
    ctx = M.build_ctx(spec.mesh)
    gcfg = spec.to_guided_config()
    opt = get_optimizer(spec.optimizer)
    c = spec.workers or max(ctx.n_workers, 1)
    if spec.global_batch % c != 0:
        # a real exception, not an assert (asserts vanish under python -O):
        # per-worker losses need equal data shards
        raise ValueError(
            f"spec.global_batch={spec.global_batch} is not divisible by the "
            f"worker count c={c} (spec.workers={spec.workers}, mesh "
            f"{spec.mesh!r} provides {ctx.n_workers} data shards); the "
            f"per-worker loss reshape needs equal shards — adjust "
            f"spec.global_batch or spec.workers")
    key = jax.random.PRNGKey(spec.seed)
    params, logical, gstate = M.init_train_state(
        key, cfg, gcfg, opt, n_workers=c, strategy=strategy
    )
    if ctx.distributed:
        # start the state where the step keeps it (params and their mirrors
        # sharded by the logical rules), so every dispatch runs one program
        sh = C.train_state_shardings(ctx, logical, params, gstate)
        params, gstate = jax.device_put((params, gstate),
                                        (sh["params"], sh["gstate"]))
    dispatch = build_dispatch(spec, strategy, cfg, ctx, c, n_steps)

    start_step = 0
    if resume:
        if not spec.ckpt_dir:
            raise ValueError("fit(resume=True) needs spec.ckpt_dir to know "
                             "where the snapshots live")
        if C.latest_step(spec.ckpt_dir) is not None:
            # the freshly initialized state is the restore template: same
            # treedef (incl. strategy extra / w_stale presence), so a
            # checkpoint from a different config fails loudly, not subtly
            template = C.snapshot(params, gstate, 0)
            shardings = (C.train_state_shardings(ctx, logical, params, gstate)
                         if ctx.distributed else None)
            # restore_latest re-reads the manifest if retention prunes the
            # step it named between manifest read and archive load
            _, snap = C.restore_latest(spec.ckpt_dir, template,
                                       shardings=shardings)
            params, gstate = snap["params"], snap["gstate"]
            if shardings is None:
                # commit host arrays to device so donation keeps working
                params = jax.tree.map(jnp.asarray, params)
                gstate = jax.tree.map(jnp.asarray, gstate)
            start_step = int(np.asarray(snap["data"]["cursor"]))
            # the fresh-init state lives on only through `template` now that
            # params/gstate are rebound — drop it (and the snapshot dict), or
            # a resumed run holds ~2x the train-state memory of a fresh one
            del template, snap
            if start_step > n_steps:
                raise ValueError(
                    f"checkpoint at step {start_step} is past this run's "
                    f"n_steps={n_steps}; nothing to resume")

    # constructed only once resume validation passed: a failed restore
    # must not strand the writer thread
    ckpt = None
    if spec.ckpt_dir:
        ckpt = C.AsyncCheckpointer(spec.ckpt_dir, keep_last=spec.keep_last,
                                   meta=C.spec_meta(spec))

    batches = iter(data) if data is not None else synthetic_stream(spec, cfg, c)
    for _ in range(start_step):  # replay the data cursor: same rng protocol,
        next(batches)            # so resumed steps see the exact batches

    sizes = chunk_schedule(start_step, n_steps, spec.chunk_steps, spec.ckpt_every)
    # stacked (K, ...) blocks staged by batch_put (sharded H2D placement on
    # distributed meshes): generation, stacking and the copy run wherever
    # the source is consumed — on the prefetch thread when spec.prefetch
    put = batch_put(ctx)
    source = stack_blocks(batches, sizes)
    prefetcher = ChunkPrefetcher(source, put=put) if spec.prefetch else None
    source = prefetcher if prefetcher is not None else map(put, source)

    # SIGTERM-safe: a preempted run drains the in-flight chunk, snapshots
    # full state, and exits cleanly instead of losing the window
    stop = {"sig": None}
    old_handler, installed = None, False
    if ckpt is not None and threading.current_thread() is threading.main_thread():
        def _on_term(signum, frame):
            stop["sig"] = signum

        try:
            # the previous handler can legitimately be None (installed
            # from C) — track installation separately so restore still runs
            old_handler = signal.signal(signal.SIGTERM, _on_term)
            installed = True
        except (ValueError, AttributeError):  # non-main interpreter / platform
            installed = False

    raw = []                   # (first_step, metrics) per kept dispatch
    m = None
    rej = 0                    # device-side rejected-step accumulator
    done = start_step
    compile_time_s = 0.0
    compiled_steps = 0         # steps covered by compiling dispatches
    warm_time_s = 0.0
    seen_sizes = set()
    t_loop = time.perf_counter()   # the loop span: setup/restore excluded
    try:
        for k in sizes:
            with TraceAnnotation("fit.input_wait"):
                block = next(source)
            # every FIRST dispatch of a chunk shape jit-compiles (the uneven
            # tail and ckpt_every-split chunks each get their own program);
            # timing those (one host sync each) is what lets Report split
            # compile time out of the warm steps/s
            is_new = k not in seen_sizes
            if is_new and m is not None:
                # drain queued warm dispatches first, or their execution
                # lands inside the timed window and inflates compile_time
                jax.block_until_ready(m)
            with (TraceAnnotation("fit.compile") if is_new else contextlib.nullcontext()):
                t_dispatch = time.perf_counter()
                with StepTraceAnnotation("fit.dispatch", step_num=done):
                    params, gstate, m = dispatch(params, gstate, block)
                if is_new:
                    jax.block_until_ready(m)
                    compile_time_s += time.perf_counter() - t_dispatch
                    compiled_steps += k
                    seen_sizes.add(k)
            done += k
            if spec.sentinel:
                # stays device-side (async jnp add): ONE host read after the
                # loop, not a sync per dispatch
                rej = rej + m["rejected"].sum()
            if not keep_history:
                raw.clear()
            raw.append((done - k, m))
            if on_step is not None:
                with TraceAnnotation("fit.on_step"):
                    on_step(done - 1, m, params)
            if ckpt is not None and spec.ckpt_every and done % spec.ckpt_every == 0:
                # device->host copy here (chunk boundary, before the next
                # dispatch donates these buffers); serialization is async
                with TraceAnnotation("fit.checkpoint"):
                    ckpt.save(done, C.snapshot(params, gstate, done))
            if stop["sig"] is not None:
                break
        if m is not None:
            # drain the queue so the warm window closes on finished work;
            # warm time = loop span minus the timed compiling windows, so
            # setup, restore and teardown never land in the throughput
            # denominator (Report.steps_per_s = warm_steps / warm_time_s)
            jax.block_until_ready(m)
        warm_time_s = max(time.perf_counter() - t_loop - compile_time_s, 0.0)
    finally:
        if prefetcher is not None:
            prefetcher.close()
        if installed:
            # a None previous handler (installed from C) cannot be
            # re-registered through signal.signal; SIG_DFL beats leaving
            # our dead closure swallowing every later SIGTERM
            signal.signal(signal.SIGTERM,
                          old_handler if old_handler is not None
                          else signal.SIG_DFL)
        if ckpt is not None:
            import sys

            loop_failed = sys.exc_info()[0] is not None
            try:
                try:
                    # final full-state snapshot (dedupes against a periodic
                    # save that already covered `done`)
                    if done > start_step or C.latest_step(spec.ckpt_dir) is None:
                        with TraceAnnotation("fit.checkpoint"):
                            ckpt.save(done, C.snapshot(params, gstate, done))
                finally:
                    ckpt.close()  # drain + join even if the save failed
            except Exception:
                # a training-loop exception outranks checkpoint teardown
                # noise; surface the writer error only on a clean loop
                if not loop_failed:
                    raise
    history = []
    for first, mi in raw:
        history.extend(step_records(mi, first))
    if not keep_history:
        history = history[-1:]
    final = dict(history[-1]) if history else {}
    resilience = {}
    if spec.sentinel:
        resilience = {"sentinel": spec.sentinel,
                      "rejected_steps": int(jax.device_get(rej))}
    return Report(backend="mesh", spec=spec, history=history, final=final,
                  model=params, state=gstate, n_steps=done - start_step,
                  start_step=start_step, interrupted=stop["sig"] is not None,
                  compile_time_s=compile_time_s, warm_time_s=warm_time_s,
                  warm_steps=max(done - start_step - compiled_steps, 0),
                  resilience=resilience)
