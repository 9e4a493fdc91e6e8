"""Block-size autotuning for the elementwise Pallas kernels.

Every `*_raw` wrapper in `kernels/guided_update` tiles its arrays into blocks
of `block` elements (`repro.kernels._tile_grid`). The historical default (64k
elements) is a good middle of the road, but the sweet spot depends on the
backend and the dtype. This module measures the candidate blocks once per
(kernel, dtype) on the current backend+device and persists the winner, so the
`block=None` default of every `*_raw` entry point resolves to the tuned value:

  * **Only blocks that fit** — `candidates(kernel, dtype)` keeps the entries
    of `CANDIDATES` whose double-buffered in/out blocks fit
    `VMEM_STREAM_BYTES` of the chip's scoped fast memory, from the kernel's
    array count and dtypes (`_STREAMS`). A larger block is refused by the TPU
    compiler, so the sweep never tries one (tests/test_tpu_compile.py compiles
    every allowed block for a v5e).
  * **Sweep on first use** — `tuned_block(kernel, dtype)` times each
    candidate on synthetic data (compiled, `block_until_ready`) and caches the
    fastest.
  * **Persistent JSON cache keyed by backend+device** — winners land in
    `<cache_dir>/<backend>-<device_kind>.json`: `REPRO_AUTOTUNE_CACHE` when
    set, else `.cache/autotune` in the checkout
    (`repro.common.cache.checkout_cache`), so repeat runs — and repeat
    *processes* — skip the sweep entirely.
  * **Interpret backends skip the sweep.** On CPU the kernels run in Pallas
    interpret mode (pure emulation, see `default_interpret`): its wall time
    says nothing about the compiled kernel, so the default block is returned
    unswept and nothing is persisted. `REPRO_AUTOTUNE=force` overrides (used
    to exercise the harness end-to-end); `REPRO_AUTOTUNE=0` disables sweeping
    everywhere.

Resolution is trace-time python (`tuned_block` returns a plain int), so the
tuned block is a static of whatever jit the caller is being traced under.
"""
from __future__ import annotations

import json
import os
import re
import tempfile
import time

#: candidate block sizes (elements): 32k (the smallest full `_tile_grid`
#: block, ROW_BLOCK x LANE_BLOCK) .. 512k
CANDIDATES = (32768, 65536, 131072, 262144, 524288)

#: the pre-autotune default (and the interpret-mode fallback); fits every
#: kernel of the family at every dtype
DEFAULT_BLOCK = 65536

#: fast memory the double-buffered in/out blocks of one kernel may take.
#: Compiling for a v5e accepts every block of the family whose streams come
#: to 12 MiB and refuses every one at 16 MiB: the kernel body's f32
#: temporaries need room beside the streams.
VMEM_STREAM_BYTES = 12 << 20

#: per kernel: (arrays in the weight dtype, arrays in the compute dtype)
#: summed over inputs and outputs — w, g, w_stale in and w out, plus each
#: accumulator in and out
_STREAMS = {
    "guided_sgd_update": (4, 0),
    "guided_momentum_update": (4, 2),
    "guided_rmsprop_update": (4, 2),
    "guided_adam_update": (4, 4),
}

#: elements per timing probe, as a (rows, LANE_BLOCK) matrix so that each
#: candidate tiles it into blocks of its own size — large enough that every
#: candidate runs a multi-step grid (1M = 2..32 grid steps across CANDIDATES)
_PROBE_N = 1 << 20
_PROBE_ITERS = 3

# process-level memo: (cache_path, key) -> block. Refilled from the JSON file
# on first miss, so tuned_block costs a dict hit on the hot path.
_MEMO: dict = {}


def cache_dir() -> str:
    from repro.common.cache import checkout_cache

    return os.environ.get("REPRO_AUTOTUNE_CACHE") or checkout_cache("autotune")


def candidates(kernel: str, dtype) -> tuple:
    """The entries of CANDIDATES whose double-buffered blocks fit
    VMEM_STREAM_BYTES for `kernel` on weights of `dtype`."""
    import jax.numpy as jnp

    try:
        n_w, n_acc = _STREAMS[kernel]
    except KeyError:
        raise KeyError(
            f"no stream table for kernel {kernel!r}; known: {', '.join(_STREAMS)}"
        ) from None
    w = jnp.dtype(dtype).itemsize
    acc = jnp.promote_types(dtype, jnp.float32).itemsize
    per_elem = 2 * (n_w * w + n_acc * acc)  # x2: Pallas double-buffers
    return tuple(b for b in CANDIDATES if b * per_elem <= VMEM_STREAM_BYTES)


def _device_tag() -> str:
    import jax

    kind = "unknown"
    devs = jax.devices()
    if devs:
        kind = getattr(devs[0], "device_kind", "unknown") or "unknown"
    tag = f"{jax.default_backend()}-{kind}"
    return re.sub(r"[^A-Za-z0-9._-]+", "_", tag)


def cache_path(dirname: str = None) -> str:
    """The per-(backend, device-kind) winners file."""
    return os.path.join(dirname or cache_dir(), f"{_device_tag()}.json")


def _load(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _store(path: str, data: dict) -> None:
    """Atomic JSON write (the dir is shared between concurrent runs)."""
    os.makedirs(os.path.dirname(path), exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path), suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(data, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass


def clear_memo() -> None:
    """Drop the in-process memo (tests: simulates a fresh process, forcing the
    next `tuned_block` to re-read the persisted JSON)."""
    _MEMO.clear()


def _default_measure(kernel: str, dtype, block: int) -> float:
    """Wall seconds per call of `kernel` at `block` on synthetic _PROBE_N-
    element data (compiled path; the first call pays the jit and is excluded).
    A block the compiler refuses raises: `candidates` admits none."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import LANE_BLOCK
    from repro.kernels.guided_update import kernel as K

    rng = np.random.default_rng(0)
    w = jnp.asarray(
        rng.standard_normal((_PROBE_N // LANE_BLOCK, LANE_BLOCK)), dtype)
    g = w * 0.01
    ws = w + 0.05
    acc = jnp.abs(w) * 0.1

    runs = {
        "guided_sgd_update": lambda: K.guided_sgd_update_raw(
            w, g, ws, 0.1, 0.04, block=block),
        "guided_momentum_update": lambda: K.guided_momentum_update_raw(
            w, g, ws, acc, 0.1, 0.04, 0.9, block=block),
        "guided_rmsprop_update": lambda: K.guided_rmsprop_update_raw(
            w, g, ws, acc, 0.1, 0.04, 0.9, 1e-8, block=block),
        "guided_adam_update": lambda: K.guided_adam_update_raw(
            w, g, ws, acc, acc, 3, 0.1, 0.04, 0.9, 0.999, 1e-8, block=block),
    }
    try:
        fn = runs[kernel]
    except KeyError:
        raise KeyError(
            f"no autotune probe for kernel {kernel!r}; known: {', '.join(runs)}"
        ) from None
    jax.block_until_ready(fn())  # compile
    t0 = time.perf_counter()
    for _ in range(_PROBE_ITERS):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / _PROBE_ITERS


def _sweep_allowed() -> bool:
    mode = os.environ.get("REPRO_AUTOTUNE", "").lower()
    if mode in ("0", "off", "false"):
        return False
    if mode == "force":
        return True
    from repro.kernels import default_interpret

    # interpret mode emulates the grid sequentially — timing it would tune
    # the emulator, not the kernel
    return not default_interpret()


def tuned_block(kernel: str, dtype, *, dirname: str = None, measure=None) -> int:
    """The autotuned flat-block size for `(kernel, dtype)` on this
    backend+device — from the process memo, else the persisted JSON, else a
    fresh sweep (persisted for the next run). Falls back to `DEFAULT_BLOCK`
    unswept where timing is meaningless (see module docstring).

    `measure(kernel, dtype, block) -> seconds` overrides the probe (tests
    inject a deterministic one); passing it also forces the sweep."""
    import jax.numpy as jnp

    key = f"{kernel}.{jnp.dtype(dtype).name}"
    path = cache_path(dirname)
    memo_key = (path, key)
    hit = _MEMO.get(memo_key)
    if hit is not None:
        return hit

    data = _load(path)
    if key in data:
        block = int(data[key])
        _MEMO[memo_key] = block
        return block

    if measure is None and not _sweep_allowed():
        # no persist: a later run on a kernel-capable backend should sweep
        return DEFAULT_BLOCK

    from concurrent.futures import ThreadPoolExecutor

    probe = measure or _default_measure
    # the first resolution usually happens while a train step is being
    # traced, and JAX's trace state is per thread: probing on a fresh thread
    # runs the candidates on the device instead of staging them into the step
    with ThreadPoolExecutor(1) as pool:
        timings = pool.submit(lambda: {
            b: probe(kernel, dtype, b) for b in candidates(kernel, dtype)}).result()
    block = min(timings, key=timings.get)
    data[key] = block
    _store(path, data)
    _MEMO[memo_key] = block
    return block
