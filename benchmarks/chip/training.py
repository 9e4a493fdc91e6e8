"""The timed path of a training cell: `Trainer.from_spec(spec).fit(data=...)`
driven from outside.

`fit` runs a fixed number of steps; the benchmark gives it more than any
window holds and ends it from `on_step` once the window has closed. Set-up is
everything up to the end of the dispatch after the check's: the first (which
compiles, or loads the step from the persistent cache), the rest of those
whose steps the check follows, and one warm dispatch. The window then starts at a dispatch that has completed,
keeps the host at most one dispatch ahead of the device, and ends in
`block_until_ready` on the last dispatch it issued: its rate is every token
of every step issued in it over its wall time.

The check's readings of the program's state come from a tap on the dispatch
`fit` builds (`engine.trainloop.build_dispatch`): it reads the state the
check's dispatches return, before the next one donates it, and passes
through untouched after that. `on_step` alone hands over no optimizer state.
"""
from __future__ import annotations

import time

import jax
import numpy as np

import check
import generator

#: seconds of the window the profiler records in a `--trace 1` run (at least
#: two dispatches)
TRACE_SECONDS = 4.0
#: far more steps than any window holds: the window, not fit, ends the run
MAX_STEPS = 1 << 20


class WindowClosed(Exception):
    pass


#: the guided correction's settings a traffic file may state
GUIDED_KEYS = ("max_consistent", "magnitude_weight", "correction_scale")


def make_spec(cfg: dict, traffic: dict, seed: int):
    from repro.engine import ExperimentSpec

    prog = cfg["program"]
    return ExperimentSpec(
        backend="mesh", arch=prog["arch"], reduced=False,
        model_overrides=tuple(sorted(prog["model_overrides"].items())),
        mode=traffic["mode"], strategy=traffic["strategy"],
        optimizer=traffic["optimizer"], lr=float(traffic["lr"]),
        rho=int(traffic["rho"]), dc_lambda=float(traffic.get("dc_lambda", 0.04)),
        staleness=int(traffic.get("staleness", 0)), workers=int(traffic["workers"]),
        global_batch=int(traffic["global_batch"]), seq_len=int(traffic["seq_len"]),
        chunk_steps=int(traffic["chunk_steps"]), prefetch=bool(traffic["prefetch"]),
        mesh=traffic["mesh"], seed=seed, steps=MAX_STEPS,
        **{k: traffic[k] for k in GUIDED_KEYS if k in traffic})



#: program ModelConfig field -> configuration file key
_SAME = {"d_model": "hidden_size", "n_heads": "num_attention_heads",
         "n_kv_heads": "num_key_value_heads", "d_ff": "intermediate_size",
         "vocab_size": "vocab_size", "n_layers": "num_hidden_layers",
         "tie_embeddings": "tie_word_embeddings", "norm_eps": "rms_norm_eps",
         "rope_theta": "rope_theta", "param_dtype": "torch_dtype"}


def check_model(spec, cfg: dict) -> None:
    """The program must run the configuration the file states."""
    mc = spec.model_config()
    off = {f: (getattr(mc, f), cfg[k]) for f, k in _SAME.items() if getattr(mc, f) != cfg[k]}
    if mc.sliding_window:
        off["sliding_window"] = (mc.sliding_window, 0)
    if off:
        raise ValueError(f"the program's model differs from {cfg['name']}: {off}")


class Tap:
    """Wraps the dispatch `fit` builds; reads the state after the first and
    the last of its first `n_calls` calls, and the losses of all of them."""

    def __init__(self, n_calls: int):
        self.n_calls = n_calls
        self.calls = 0
        self.losses = []
        self.at = {}
        self.w0 = self.prev = None

    def wrap(self, dispatch):
        def tapped(params, gstate, block):
            if self.calls >= self.n_calls:
                return dispatch(params, gstate, block)
            if self.calls == 0:
                self.w0 = jax.device_get(params)
            params, gstate, m = dispatch(params, gstate, block)
            # the readings put leaves beside the state: not beside the
            # step's own buffers
            jax.block_until_ready((params, gstate))
            self.calls += 1
            self.losses.extend(np.asarray(m["loss"], np.float64).reshape(-1).tolist())
            if self.calls == 1:
                self.at["d1"] = check.program_readings(params, gstate.opt_state, self.w0)
            if self.calls == self.n_calls:
                self.at["last"] = check.program_readings(params, gstate.opt_state,
                                                         self.w0, self.prev)
                self.w0 = self.prev = None
            elif self.calls == self.n_calls - 1:
                self.prev = jax.device_get(params)
            return params, gstate, m

        return tapped


class Window:
    """The `on_step` callback: set-up, then the timed (or traced) window."""

    def __init__(self, seconds: float, setup_dispatches: int, trace_dir: str = ""):
        self.seconds = seconds
        self.setup_dispatches = setup_dispatches
        self.trace_dir = trace_dir
        self.dispatches = 0
        self.steps = 0
        self.prev = None
        self.losses = []
        self.t0 = self.t1 = None
        self.compiles = 0
        self._armed = False

    def on_event(self, name, secs, **kw):
        if self._armed and ("compile" in name or "trace" in name):
            self.compiles += 1

    def on_step(self, step, m, params):
        self.dispatches += 1
        if self.dispatches <= self.setup_dispatches:
            jax.block_until_ready(m)
            if self.dispatches == self.setup_dispatches:
                if self.trace_dir:
                    jax.profiler.start_trace(self.trace_dir)
                self._armed = True
                self.t0 = time.perf_counter()
            return
        self.steps += int(np.shape(m["loss"])[0]) if np.ndim(m["loss"]) else 1
        self.losses.append(m["loss"])
        if self.prev is not None:
            jax.block_until_ready(self.prev)
        self.prev = m
        elapsed = time.perf_counter() - self.t0
        limit = min(TRACE_SECONDS, self.seconds) if self.trace_dir else self.seconds
        if elapsed >= limit and (not self.trace_dir or self.dispatches >= self.setup_dispatches + 2):
            jax.block_until_ready(m)
            self.t1 = time.perf_counter()
            self._armed = False
            if self.trace_dir:
                jax.profiler.stop_trace()
            raise WindowClosed

    def failed_steps(self) -> int:
        return int(sum(int(np.sum(~np.isfinite(np.asarray(x)))) for x in self.losses))


class Recorder:
    """Passes a batch stream through and keeps its first `n` batches."""

    def __init__(self, it, n: int):
        self.it, self.n, self.kept = it, n, []

    def __iter__(self):
        return self

    def __next__(self):
        b = next(self.it)
        if len(self.kept) < self.n:
            self.kept.append({k: np.array(v) for k, v in b.items()})
        return b


def run(cfg: dict, traffic: dict, seed: int, seconds: float, check_steps: int,
        trace_dir: str = "", wrap=None) -> dict:
    """Drive the program through set-up and one window; the check reads its
    first `check_steps` steps. `wrap`, if given, wraps the built dispatch
    (tests plant faults with it). Returns what the window measured and what
    the check read."""
    from repro.engine import Trainer, trainloop

    spec = make_spec(cfg, traffic, seed)
    check_model(spec, cfg)
    chunk = spec.chunk_steps
    if check_steps % chunk or check_steps < 2 * chunk:
        raise ValueError(f"the check's {check_steps} steps are not two or more "
                         f"dispatches of chunk_steps={chunk}")
    tap = Tap(check_steps // chunk)
    window = Window(seconds, check_steps // chunk + 1, trace_dir)
    data = Recorder(generator.batches_for(cfg, traffic, seed), check_steps)
    build = trainloop.build_dispatch

    def build_tapped(*a, **kw):
        d = build(*a, **kw)
        return tap.wrap(wrap(d) if wrap is not None else d)

    trainloop.build_dispatch = build_tapped
    jax.monitoring.register_event_duration_secs_listener(window.on_event)
    try:
        Trainer.from_spec(spec).fit(data=data, on_step=window.on_step,
                                    keep_history=False)
    except WindowClosed:
        pass
    finally:
        trainloop.build_dispatch = build
        jax.monitoring.unregister_event_duration_listener(window.on_event)
    if window.t1 is None:
        raise RuntimeError("fit ended before the window closed")
    return {
        "spec": spec, "t0": window.t0, "t1": window.t1, "steps": window.steps,
        "tokens": window.steps * spec.global_batch * spec.seq_len,
        "failed": window.failed_steps(), "compiles": window.compiles,
        "batches": data.kept,
        "readings": {"losses": tap.losses[:check_steps], **tap.at},
    }
