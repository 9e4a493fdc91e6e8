"""Compile the fused guided-update kernels for a TPU v5e that is described,
not attached: what the chip's compiler refuses fails here, at no chip time.

The topology is described inside a module fixture (never at import): only one
process may load the TPU library, and every xdist worker imports this file.
The persistent compilation cache is off around these compiles: an entry
written for a described chip cannot be read back without one.
"""
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro import kernels as RK
from repro.kernels.guided_update import kernel as K

#: yi-9b leaves: the FFN input projection, the embedding table, a norm
LEAVES = [(4096, 11008), (64000, 4096), (4096,)]

#: every distinct parameter leaf `model_init` makes at the benchmark cells'
#: depths (yi-9b at 13 layers, minicpm-2b at 10), with its dtype
CELL_LEAVES = {
    "yi-9b": [((64000, 4096), "bfloat16"), ((4096, 64000), "bfloat16"),
              ((13, 4096, 4096), "bfloat16"), ((13, 4096, 512), "bfloat16"),
              ((13, 4096, 2, 11008), "bfloat16"), ((13, 11008, 4096), "bfloat16"),
              ((13, 4096), "float32")],
    "minicpm-2b": [((122753, 2304), "bfloat16"), ((10, 2304, 2, 5760), "bfloat16"),
                   ((10, 5760, 2304), "bfloat16"), ((10, 2304, 2304), "bfloat16"),
                   ((10, 2304), "float32")],
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _update(name, block, lam=0.04):
    """(fn, n_accumulators) for one kernel, compiled (not interpreted) at
    `block` (None: the derived block)."""
    kw = dict(block=block, interpret=False)
    if name == "sgd":
        return lambda w, g, ws: K.guided_sgd_update_raw(w, g, ws, 0.1, lam, **kw), 0
    if name == "momentum":
        return (lambda w, g, ws, m: K.guided_momentum_update_raw(
            w, g, ws, m, 0.1, lam, 0.9, **kw), 1)
    if name == "rmsprop":
        return (lambda w, g, ws, r: K.guided_rmsprop_update_raw(
            w, g, ws, r, 0.1, lam, 0.9, 1e-8, **kw), 1)
    return (lambda w, g, ws, m, v: K.guided_adam_update_raw(
        w, g, ws, m, v, 3, 0.1, lam, 0.9, 0.999, 1e-8, **kw), 2)


def _compile(name, shape, dtype, block, sharding, lam=0.04):
    """The kernel jitted with `w` and its accumulators donated, as the train
    step's carry donates them: each is updated in its own buffer."""
    fn, n_acc = _update(name, block, lam)
    acc = jnp.promote_types(dtype, jnp.float32)
    args = ([jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)] * 3
            + [jax.ShapeDtypeStruct(shape, acc, sharding=sharding)] * n_acc)
    donate = (0, *range(3, 3 + n_acc))
    return jax.jit(fn, donate_argnums=donate).lower(*args).compile()


def _kernel_lines(compiled):
    """The compiled program's custom calls as a profiler trace names them:
    the whole instruction with every operand's shape."""
    from jax._src.lib import xla_client as xc

    opts = xc._xla.HloPrintOptions()
    opts.print_operand_shape = True
    (module,) = compiled.runtime_executable().hlo_modules()
    return [ln.strip() for ln in module.to_string(opts).splitlines()
            if "tpu_custom_call" in ln]


@pytest.mark.parametrize("shape", LEAVES, ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("name", ["sgd", "momentum", "adam"])
def test_kernel_compiles_at_yi_9b_leaf(topo, one_chip, name, shape):
    compiled = _compile(name, shape, jnp.bfloat16, None, one_chip)
    calls = [ln for ln in compiled.as_text().splitlines() if "tpu_custom_call" in ln]
    assert calls
    # the kernel's own name, which a profiler trace's op event starts with
    for ln in calls:
        assert re.search(rf"%guided_{name}_update(\.\d+)? = ", ln), ln


@pytest.mark.parametrize("leaf", [(m, *x) for m, xs in CELL_LEAVES.items() for x in xs],
                         ids=lambda x: f"{x[0]}-{'x'.join(map(str, x[1]))}")
@pytest.mark.parametrize("name", ["sgd", "adam"])
def test_derived_block_compiles_at_every_cell_leaf(topo, one_chip, monkeypatch, name, leaf):
    """Each leaf of both cells, as its cell runs it (sgd at lambda = 0, adam
    with DC-ASGD's term), compiles at its derived block: the leaf is read in
    its own layout (no copy, reshape or transpose beside the kernel), a split
    last dim is split evenly, and a grid step moves at least 2 MB where the
    leaf is larger than one block."""
    _, shape, dtype = leaf
    seen = {}
    stream_block, tiling = K.stream_block, K.tiling

    def record_streams(dtypes):
        seen["bytes"] = sum(jnp.dtype(d).itemsize for d in dtypes)
        return stream_block(dtypes)

    def record_tiling(shape, block):
        seen["tiling"] = tiling(shape, block)
        return seen["tiling"]

    monkeypatch.setattr(K, "stream_block", record_streams)
    monkeypatch.setattr(K, "tiling", record_tiling)
    compiled = _compile(name, shape, jnp.dtype(dtype), None, one_chip,
                        lam=0.0 if name == "sgd" else 0.04)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert not re.search(r"= \S+ (copy|reshape|transpose)\(", text)
    view, bs, grid = seen["tiling"]
    assert bs[-1] == shape[-1] or (shape[-1] % bs[-1] == 0 and bs[-1] % RK.LANE == 0)
    step = int(np.prod(bs)) * seen["bytes"]
    assert 2 * step <= RK.VMEM_STREAM_BYTES
    if grid != (1, 1, 1):
        assert step >= 2e6, (bs, step)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("name", ["sgd", "momentum", "rmsprop", "adam"])
def test_derived_block_compiles_for_every_kernel(topo, one_chip, name, dtype):
    """Every kernel of the family at either weight dtype, with and without
    its w_stale stream, takes its derived block, with merged rows and with
    the last two dims kept."""
    for shape in ((4096, 11008), (8, 512, 2, 5760)):
        for lam in (0.0, 0.04):
            _compile(name, shape, jnp.dtype(dtype), None, one_chip, lam)


def test_lam_zero_kernel_has_no_w_stale_operand_and_the_reader_sees_it(topo, one_chip):
    """At a static lambda = 0 the sgd kernel streams w and g only: two array
    operands before its f32[2] scalar pack, and the benchmark's
    `guided_update_ms` reader still takes the call for the update's; at
    lambda = 0.04 it streams w_stale too."""
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks", "chip",
                        "metrics", "guided_update_ms.py")
    spec = importlib.util.spec_from_file_location("guided_update_ms", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    for lam, n_arrays in ((0.0, 2), (0.04, 3)):
        compiled = _compile("sgd", (13, 4096, 2, 11008), jnp.bfloat16, None, one_chip, lam)
        (line,) = _kernel_lines(compiled)
        operands = line.split(" custom-call(")[1].split("), custom_call_target")[0]
        shapes = re.findall(r"(?:^|, )(\w+\[[\d,]*\])", operands)
        assert shapes == ["bf16[53248,2,11008]"] * n_arrays + ["f32[2]"], shapes
        assert re.search(reader.KERNEL, line), line


def test_fused_update_compiles_per_shard_on_2x2_mesh(topo, monkeypatch):
    """On a mesh the kernel runs under shard_map on each device's shard of the
    FSDP-sharded leaves (a Pallas call has no partitioning rule)."""
    from repro.configs import get_config
    from repro.engine.mesh import _fused_apply
    from repro.kernels.guided_update.ops import fused_update_for
    from repro.models import transformer as T
    from repro.models.module import split_params
    from repro.sharding.rules import DEFAULT_RULES, ShardCtx, shardings_for

    # the host is a CPU: steer the wrapper onto the compiled kernel
    monkeypatch.setattr(K, "default_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    ctx = ShardCtx(mesh=mesh, rules=DEFAULT_RULES)
    cfg = get_config("yi_9b").replace(n_layers=1)
    params, logical = split_params(
        jax.eval_shape(lambda: T.model_init(jax.random.PRNGKey(0), cfg)))
    sh = shardings_for(logical, params, mesh, DEFAULT_RULES)
    p = jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
                     params, sh)
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=NamedSharding(mesh, PartitionSpec()))
    apply = _fused_apply(fused_update_for("sgd", impl="kernel"), "sgd", 0.04, cfg, ctx)
    text = jax.jit(apply).lower(p, p, p, {}, lr).compile().as_text()
    assert text.count("tpu_custom_call") == len(jax.tree.leaves(params))
    assert "all-gather" not in text  # each kernel reads only its own shard


#: the benchmark's training cells: yi-9b's gSSGD (sgd at lambda = 0) and
#: minicpm-2b's DC-ASGD (adam at lambda = 0.04)
CELLS = ["yi-9b.gssgd-c4", "minicpm-2b.dcasgd-adam-c4"]
_BENCH = os.path.join(os.path.dirname(__file__), "..", "benchmarks", "chip")


def _cell_step(cell, sharding):
    """The dispatch `fit` builds for a benchmark cell (a chunk of its train
    steps, the carry donated), compiled on the train state's shapes. The
    benchmark's directory must be on `sys.path`."""
    import json

    import training

    from repro.engine import mesh as M
    from repro.engine import trainloop
    from repro.optim import get_optimizer

    with open(os.path.join(_BENCH, "..", "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    (wl,) = [w for w in bench["workloads"] if w["name"] == cell]
    (cf,) = [c for c in bench["configs"] if c["name"] == wl["config"]]
    with open(os.path.join(_BENCH, "..", "..", cf["file"])) as f:
        cfg_file = json.load(f)
    with open(os.path.join(_BENCH, "traffic", wl["traffic"] + ".json")) as f:
        traffic = json.load(f)
    spec = training.make_spec(cfg_file, traffic, 0)
    cfg, gcfg = spec.model_config(), spec.to_guided_config()
    strategy = M.resolve_strategy(gcfg, spec.strategy)
    state = jax.eval_shape(lambda: M.init_train_state(
        jax.random.PRNGKey(0), cfg, gcfg, get_optimizer(spec.optimizer),
        spec.workers, strategy)[::2])
    state = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
                         state)
    tok = jax.ShapeDtypeStruct((spec.chunk_steps, spec.global_batch, spec.seq_len), "int32",
                               sharding=sharding)
    dispatch = trainloop.build_dispatch(spec, strategy, cfg, M.build_ctx(spec.mesh),
                                        spec.workers, 100)
    return dispatch.lower(*state, {"tokens": tok, "labels": tok}).compile()


def _in_place_operand_copies(text):
    """The copies of more than 1 MiB that feed (through bitcasts) an operand
    an update kernel writes in place: its first, `w`, and its last arrays
    before the scalar pack, one per accumulator (one per result past `w`)."""
    itemsize = {"bf16": 2, "f32": 4}
    defs, kernels = {}, []
    for ln in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$", ln)
        if not m:
            continue
        name, typ, op, rest = m.groups()
        defs[name] = (typ, op, re.findall(r"%([\w.\-]+)", rest.split("), ")[0]))
        if op == "custom-call" and re.match(r"guided_\w+_update", name):
            kernels.append(name)
    assert kernels
    copies = []
    for k in kernels:
        typ, _, operands = defs[k]
        n_acc = len(re.findall(r"\w+\[[\d,]*\]", typ)) - 1
        arrays = operands[:-1]
        for a in [arrays[0], *arrays[len(arrays) - n_acc:]]:
            while defs[a][1] == "bitcast":
                (a,) = defs[a][2]
            t = re.match(r"(\w+)\[([\d,]*)\]", defs[a][0])
            size = itemsize.get(t.group(1), 4) * int(np.prod([int(d) for d in t.group(2).split(",")]))
            if defs[a][1] == "copy" and size > 2 ** 20:
                copies.append((k, a, size))
    return copies


@pytest.mark.parametrize("cell", CELLS)
def test_cell_step_updates_parameters_and_state_in_place(topo, one_chip, monkeypatch, cell):
    """In a cell's step compiled for a v5e, no copy of more than 1 MiB feeds
    `w` or an accumulator of an update kernel: each kernel writes them in
    place, in the loop-carried buffers of the chunk's scan. Launched with
    fresh outputs (the kernels' aliases dropped) the same step copies each of
    them aside before its kernel, and needs more scratch."""
    from jax.experimental import pallas as pl

    from repro.kernels.guided_update import ops as O
    from repro.models import layers as L

    # the host is a CPU: steer the step onto the chip's kernels
    monkeypatch.setattr(K, "default_interpret", lambda: False)
    monkeypatch.setattr(O, "default_interpret", lambda: False)
    monkeypatch.setattr(L, "_backend", lambda: "tpu")
    monkeypatch.syspath_prepend(_BENCH)
    in_place = _cell_step(cell, one_chip)
    assert _in_place_operand_copies(in_place.as_text()) == []
    call = pl.pallas_call
    monkeypatch.setattr(pl, "pallas_call", lambda *a, input_output_aliases=None, **kw:
                        call(*a, **kw))
    fresh = _cell_step(cell, one_chip)
    assert _in_place_operand_copies(fresh.as_text())
    temp = lambda c: c.memory_analysis().temp_size_in_bytes
    assert temp(in_place) <= temp(fresh)


#: one layer's causal attention in a training cell: (B, S, H, K, d_head) of
#: yi-9b (8 x 1024) and minicpm-2b (4 x 1024)
ATTENTION = {"yi-9b": (8, 1024, 32, 4, 128), "minicpm-2b": (4, 1024, 36, 36, 64)}


@pytest.mark.parametrize("model", list(ATTENTION))
def test_training_attention_compiles_as_splash_kernels(topo, one_chip, monkeypatch, model):
    """The forward and its gradient take the splash kernels on a v5e: no
    float32 score matrix is left, and the scratch stays a fraction of the XLA
    path's 2.15 GB (yi-9b, v5e compile)."""
    from repro.models import layers as L

    B, S, H, K, dh = ATTENTION[model]
    assert dh in L.SPLASH_HEAD_DIMS
    # the host is a CPU: steer the dispatcher onto the TPU's path
    monkeypatch.setattr(L, "_backend", lambda: "tpu")
    q = jax.ShapeDtypeStruct((B, S, H, dh), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, S, K, dh), jnp.bfloat16, sharding=one_chip)

    def fwd(q, k, v):
        return L.attention(q, k, v, n_kv_heads=K, causal=True)

    def grad(q, k, v):
        return jax.grad(lambda *a: jnp.sum(fwd(*a).astype(jnp.float32)), argnums=(0, 1, 2))(q, k, v)

    for fn, phases in ((fwd, {"fwd"}), (grad, {"fwd", "dq", "dkv"})):
        compiled = jax.jit(fn).lower(q, kv, kv).compile()
        text = compiled.as_text()
        named = set(re.findall(r"%splash_mqa_(fwd|dq|dkv)_[\w.]+ = ", text))
        assert named == phases, named
        assert not re.search(rf"f32\[[\d,]*{S},{S}\]", text)
        assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9
