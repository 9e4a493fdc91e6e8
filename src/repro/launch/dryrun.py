"""Multi-pod dry-run: prove every (architecture x input-shape x mesh) lowers,
compiles, fits, and extract the roofline terms from the compiled artifact.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod both]

Per combination this:
  1. builds the production mesh (16x16, or 2x16x16 with --multi-pod),
  2. lowers + compiles the right step (train/prefill/decode) with full
     shardings and the guided-SSGD optimizer in-graph (for train),
  3. records memory_analysis() (proves it fits), cost_analysis() FLOPs/bytes,
     and the collective schedule parsed from the compiled HLO,
  4. separately lowers ONE layer super-block to get per-layer FLOPs/bytes/
     collective bytes: XLA's cost analysis counts a lax.scan body ONCE
     regardless of trip count, so whole-step numbers must be corrected by
     n_super x block terms (see EXPERIMENTS.md §Roofline for the arithmetic),
  5. writes results/dryrun/<arch>__<shape>__<mesh>[__<rules>].json.
"""
# The placeholder devices MUST be configured before jax initializes. 512
# covers the production meshes (16x16 and 2x16x16); REPRO_DRYRUN_DEVICES
# overrides it so small-mesh self-generation (--small, used by the roofline
# benchmark on CI) doesn't pay 512 threadpools for an 8-device mesh.
import os

_FORCED_DEVICES = int(os.environ.get("REPRO_DRYRUN_DEVICES", "512"))
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={_FORCED_DEVICES} "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse
import dataclasses
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import INPUT_SHAPES, ARCH_IDS, get_config
from repro.core.guided import GuidedConfig
from repro.engine import mesh as M
from repro.launch.mesh import make_production_mesh, make_small_mesh
from repro.models import transformer as T
from repro.models.module import split_params
from repro.optim import constant, get_optimizer
from repro.sharding.rules import DEFAULT_RULES, MULTIPOD_RULES, SERVE_TP_ONLY_RULES, ShardCtx
from repro.train import steps as S

# ----------------------------------------------------------------- hardware
# TPU v5e-class chip constants (targets; this host only compiles).
PEAK_FLOPS = 197e12     # bf16 / chip
HBM_BW = 819e9          # bytes/s / chip
LINK_BW = 50e9          # bytes/s / ICI link

DTYPE_BYTES = {"bf16": 2, "f32": 4, "f16": 2, "f64": 8, "s32": 4, "u32": 4,
               "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8, "f8e4m3": 1,
               "f8e5m2": 1, "s16": 2, "u16": 2, "c64": 8}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute")

RULE_SETS = {
    "default": (DEFAULT_RULES, MULTIPOD_RULES),
    "serve_tp": (SERVE_TP_ONLY_RULES, SERVE_TP_ONLY_RULES.replace(batch=("pod", "data"))),
    "no_seqkv": (DEFAULT_RULES.replace(seq_kv=()), MULTIPOD_RULES.replace(seq_kv=())),
    "fsdp_pods": (DEFAULT_RULES, MULTIPOD_RULES.replace(fsdp=("pod", "data"))),
    # sequence parallelism: inter-block activations sharded over `model`
    "seqpar": (DEFAULT_RULES.replace(seq=("model",)), MULTIPOD_RULES.replace(seq=("model",))),
    "seqpar_tp": (SERVE_TP_ONLY_RULES.replace(seq=("model",)),
                  SERVE_TP_ONLY_RULES.replace(batch=("pod", "data"), seq=("model",))),
}


# ----------------------------------------------------------------- planning


def plan(arch: str, shape_name: str):
    """Returns (cfg, kind, note) or (None, None, skip_reason)."""
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    note = ""
    if shape.kind == "decode" and not cfg.supports_decode:
        return None, None, f"{cfg.name} is encoder-only: no decode step (DESIGN.md §5)"
    if shape_name == "long_500k":
        if not cfg.supports_long_context():
            if cfg.arch_type in ("dense", "moe", "vlm"):
                cfg = cfg.replace(sliding_window=8192)
                note = "sliding-window-8192 variant (sub-quadratic requirement)"
            else:
                return None, None, f"{cfg.name}: no sub-quadratic attention path"
    return cfg, shape.kind, note


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def batch_struct(cfg, seq_len: int, global_batch: int):
    if cfg.audio_frontend:
        return {
            "frames": _sds((global_batch, seq_len, cfg.d_model), jnp.bfloat16),
            "mask_positions": _sds((global_batch, seq_len), jnp.bool_),
            "labels": _sds((global_batch, seq_len), jnp.int32),
            "mask": _sds((global_batch, seq_len), jnp.float32),
        }
    b = {
        "tokens": _sds((global_batch, seq_len), jnp.int32),
        "labels": _sds((global_batch, seq_len), jnp.int32),
    }
    if cfg.arch_type == "vlm" and cfg.n_patches:
        b["patches"] = _sds((global_batch, cfg.n_patches, cfg.d_model), cfg.dtype)
    return b


# ----------------------------------------------------------- HLO collectives


def collective_bytes_from_hlo(txt: str) -> dict:
    """Sum result-shape bytes of every collective op in the per-device module.
    all-reduce counts 2x (ring reduce-scatter + all-gather equivalent)."""
    out = {k: 0.0 for k in COLLECTIVES}
    counts = {k: 0 for k in COLLECTIVES}
    # result shapes: `bf16[8,128,2048]{...} all-gather(` and tuple variants
    pat = re.compile(
        r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+([a-z\-]+)(?:-start|-done)?\(")
    shape_pat = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
    for m in pat.finditer(txt):
        shapes, op = m.group(1), m.group(2)
        base = None
        for k in COLLECTIVES:
            if op == k or op == k + "-start":
                base = k
        if base is None:
            continue
        nbytes = 0
        for sm in shape_pat.finditer(shapes):
            dt, dims = sm.group(1), sm.group(2)
            if dt not in DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * DTYPE_BYTES[dt]
        mult = 2.0 if base == "all-reduce" else 1.0
        out[base] += mult * nbytes
        counts[base] += 1
    return {"bytes": out, "counts": counts, "total_bytes": sum(out.values())}


def _dedup_start_done(txt: str) -> str:
    # drop `-done` lines so async collectives are not double counted
    return "\n".join(l for l in txt.splitlines() if "-done(" not in l and "-done.(" not in l)


# ----------------------------------------------------------------- analysis


def analyze_compiled(compiled):
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):  # older jax: one dict per device
        ca = ca[0] if ca else {}
    ma = compiled.memory_analysis()
    txt = _dedup_start_done(compiled.as_text())
    coll = collective_bytes_from_hlo(txt)
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_estimate_bytes": ma.argument_size_in_bytes
            + ma.output_size_in_bytes
            + ma.temp_size_in_bytes
            - ma.alias_size_in_bytes,
        },
        "collectives": coll,
    }


def model_flops_analytic(cfg, n_tokens: int, kind: str) -> float:
    """MODEL_FLOPS = 6*N*D (train) / 2*N*D (inference), N = active params."""
    # active params: replace expert count with topk in MoE ffn weights
    d, L, V = cfg.d_model, cfg.n_layers, cfg.vocab_size
    H, K, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    per_layer = 0.0
    for i in range(T.period(cfg)):
        mk = T.mixer_kind(cfg, i)
        if mk == "attn":
            per_layer += d * (H * dh + 2 * K * dh) + H * dh * d
        elif mk == "mamba":
            ed = cfg.ssm.expand * d
            r = max(1, int(np.ceil(d / 16)))
            per_layer += d * 2 * ed + ed * (r + 2 * cfg.ssm.d_state) + r * ed + ed * d
        elif mk in ("mlstm", "slstm"):
            di = int((cfg.xlstm.mlstm_proj_factor if mk == "mlstm" else 1.0) * d)
            per_layer += 2 * d * di + 3 * di * di + di * d
            if mk == "slstm":
                per_layer += d * int(cfg.xlstm.slstm_proj_factor * d) * 3
        fk = T.ffn_kind(cfg, i)
        if fk == "dense":
            per_layer += (3 if cfg.mlp_gated else 2) * d * cfg.d_ff
        elif fk == "moe":
            per_layer += cfg.moe.topk * 3 * d * cfg.d_ff + d * cfg.moe.n_experts
    n_active = (L // T.period(cfg)) * per_layer + 2 * V * d
    mult = 6.0 if kind == "train" else 2.0
    return mult * n_active * n_tokens


# ------------------------------------------------------------------ lowering


def build_ctx(mesh, multi_pod: bool, rules_name: str, moe_impl: str = "gather") -> ShardCtx:
    single, multi = RULE_SETS[rules_name]
    return ShardCtx(
        mesh=mesh,
        rules=multi if multi_pod else single,
        data_axes=("pod", "data") if multi_pod else ("data",),
        moe_impl=moe_impl,
    )


def lower_train(cfg, ctx, gcfg, opt_name, n_micro: int = 1):
    from repro.core.guided import guided_init

    opt = get_optimizer(opt_name)
    key = jax.random.PRNGKey(0)
    p_struct_boxed = jax.eval_shape(lambda: T.model_init(key, cfg))
    params_struct, logical = split_params(p_struct_boxed)
    p_sh = S.param_shardings(cfg, ctx, logical)(params_struct)
    gstate_struct = jax.eval_shape(
        lambda ps: guided_init(gcfg, ps, opt, ctx.n_workers), params_struct
    )
    g_sh = S.state_shardings(gcfg, opt, p_sh, ctx.mesh)
    step = M.build_train_step(cfg, gcfg, opt, ctx, constant(1e-2), n_micro=n_micro)
    return step, (params_struct, p_sh), (gstate_struct, g_sh)


def run_one(arch, shape_name, multi_pod, rules_name="default", opt_name="sgd",
            correction="fused", out_dir="results/dryrun", block_too=True,
            moe_impl="gather", micro_override=0, attn_impl="", kv_cache="",
            small=False):
    t0 = time.time()
    mesh_name = "mesh4x2" if small else ("pod2x16x16" if multi_pod else "pod16x16")
    variant = "" if rules_name == "default" else f"__{rules_name}"
    if moe_impl != "gather":
        variant += f"__moe-{moe_impl}"
    if micro_override:
        variant += f"__micro{micro_override}"
    if attn_impl:
        variant += f"__attn-{attn_impl}"
    if kv_cache:
        variant += f"__kv-{kv_cache}"
    tag = f"{arch}__{shape_name}__{mesh_name}" + variant
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, tag + ".json")

    cfg, kind, note = plan(arch, shape_name)
    if cfg is not None and attn_impl:
        cfg = cfg.replace(attn_impl=attn_impl)
    if cfg is not None and kv_cache:
        cfg = cfg.replace(kv_cache_dtype=kv_cache)
    record = {"arch": arch, "shape": shape_name, "mesh": mesh_name, "rules": rules_name,
              "moe_impl": moe_impl, "micro_override": micro_override,
              "attn_impl": attn_impl or "xla",
              "kind": kind, "note": note, "ok": False}
    if cfg is None:
        record.update({"skipped": True, "ok": True})
        with open(path, "w") as f:
            json.dump(record, f, indent=1)
        print(f"[dryrun] {tag}: SKIP ({note})")
        return record

    shape = INPUT_SHAPES[shape_name]
    if small:
        # --small: compile the reduced config on an 8-chip (4x2) mesh with a
        # shrunk shape so the whole dry-run finishes in seconds on a CPU host
        # (REPRO_DRYRUN_DEVICES=8). Same lowering path, same record format —
        # only mesh_name/"mesh4x2" distinguishes these from production runs.
        cfg = cfg.reduced()
        shape = dataclasses.replace(
            shape,
            seq_len=min(shape.seq_len, 128 if kind == "train" else 256),
            global_batch=min(shape.global_batch, 8))
        mesh = make_small_mesh()
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    ctx = build_ctx(mesh, multi_pod, rules_name, moe_impl)
    chips = int(np.prod(list(mesh.shape.values())))

    try:
        from jax.sharding import NamedSharding, PartitionSpec as P

        repl = NamedSharding(mesh, P())
        if kind == "train":
            gcfg = GuidedConfig(mode="ssgd", guided=True, correction=correction)
            # microbatch to per-worker rows of 1: remat-saved activations per
            # layer then hold a single example row per device (DESIGN.md §4)
            n_micro = micro_override or max(1, shape.global_batch // max(ctx.n_workers, 1))
            record["n_micro"] = n_micro
            step, (ps, p_sh), (gs, g_sh) = lower_train(cfg, ctx, gcfg, opt_name, n_micro)
            bs = batch_struct(cfg, shape.seq_len, shape.global_batch)
            b_sh = S.batch_shardings(cfg, ctx, bs)
            lowered = jax.jit(
                step,
                in_shardings=(p_sh, g_sh, b_sh),
                out_shardings=(p_sh, g_sh, jax.tree.map(lambda _: repl, {"loss": 0, "worker_loss_var": 0, "corr_weight_sum": 0, "lr": 0, "step": 0})),
                donate_argnums=(0, 1),
            ).lower(ps, gs, bs)
            n_tokens = shape.global_batch * shape.seq_len
        elif kind == "prefill":
            step = S.build_prefill_step(cfg, ctx)
            key = jax.random.PRNGKey(0)
            p_struct_boxed = jax.eval_shape(lambda: T.model_init(key, cfg))
            ps, logical = split_params(p_struct_boxed)
            p_sh = S.param_shardings(cfg, ctx, logical)(ps)
            bs = batch_struct(cfg, shape.seq_len, shape.global_batch)
            bs.pop("labels", None)
            bs.pop("mask", None)
            b_sh = S.batch_shardings(cfg, ctx, bs)
            cache_struct = jax.eval_shape(lambda: T.init_caches(cfg, shape.global_batch, shape.seq_len))
            c_sh = S.cache_shardings(cfg, ctx, cache_struct)
            logits_sh = S.batch_shardings(cfg, ctx, {"x": _sds((shape.global_batch, 8), jnp.float32)})["x"]
            lowered = jax.jit(
                step, in_shardings=(p_sh, b_sh), out_shardings=(logits_sh, c_sh)
            ).lower(ps, bs)
            n_tokens = shape.global_batch * shape.seq_len
        else:  # decode
            step = S.build_decode_step(cfg, ctx)
            key = jax.random.PRNGKey(0)
            p_struct_boxed = jax.eval_shape(lambda: T.model_init(key, cfg))
            ps, logical = split_params(p_struct_boxed)
            p_sh = S.param_shardings(cfg, ctx, logical)(ps)
            cache_struct = jax.eval_shape(lambda: T.init_caches(cfg, shape.global_batch, shape.seq_len))
            c_sh = S.cache_shardings(cfg, ctx, cache_struct)
            toks = _sds((shape.global_batch, 1), jnp.int32)
            tok_sh = S.batch_shardings(cfg, ctx, {"t": toks})["t"]
            t_struct = _sds((), jnp.int32)
            logits_sh = S.batch_shardings(cfg, ctx, {"x": _sds((shape.global_batch, 8), jnp.float32)})["x"]
            lowered = jax.jit(
                step,
                in_shardings=(p_sh, c_sh, tok_sh, repl),
                out_shardings=(logits_sh, c_sh),
                donate_argnums=(1,),
            ).lower(ps, cache_struct, toks, t_struct)
            n_tokens = shape.global_batch  # one new token per sequence

        compiled = lowered.compile()
        full = analyze_compiled(compiled)
        record["full_step"] = full

        # ---- per-super-block lowering (scan-body trip-count correction).
        # For train the block is lowered at the MICRO batch and scaled by
        # n_super * n_micro: weight-proportional collectives (FSDP gathers,
        # grad reductions) repeat per microbatch, token-proportional ones
        # scale with tokens — lowering at micro scale gets both right.
        n_sup = T.n_super(cfg)
        record["n_super"] = n_sup
        n_micro_eff = record.get("n_micro", 1) if kind == "train" else 1
        if block_too:
            record["block"] = lower_block(cfg, ctx, kind, shape, n_micro_eff)

        # ---- roofline terms (per-chip seconds; see EXPERIMENTS.md §Roofline)
        blk = record.get("block") or {}
        n_bodies = n_sup * n_micro_eff
        flops_c = full["flops"] + max(n_bodies - 1, 0) * blk.get("flops", 0.0)
        bytes_c = full["bytes_accessed"] + max(n_bodies - 1, 0) * blk.get("bytes_accessed", 0.0)
        coll_c = full["collectives"]["total_bytes"] + max(n_bodies - 1, 0) * blk.get("coll_bytes", 0.0)
        terms = {
            "compute_s": flops_c / PEAK_FLOPS,
            "memory_s": bytes_c / HBM_BW,
            "collective_s": coll_c / LINK_BW,
            "flops_corrected": flops_c,
            "bytes_corrected": bytes_c,
            "collective_bytes_corrected": coll_c,
        }
        terms["dominant"] = max(("compute_s", "memory_s", "collective_s"), key=lambda k: terms[k])
        mf = model_flops_analytic(cfg, n_tokens, kind)
        terms["model_flops_total"] = mf
        terms["model_flops_per_chip"] = mf / chips
        terms["useful_ratio"] = (mf / chips) / max(flops_c, 1.0)
        record["roofline"] = terms
        record["ok"] = True
        record["compile_s"] = round(time.time() - t0, 1)
        mem_gb = full["memory"]["peak_estimate_bytes"] / 2**30
        print(f"[dryrun] {tag}: OK mem/dev={mem_gb:.2f}GiB "
              f"compute={terms['compute_s']*1e3:.2f}ms memory={terms['memory_s']*1e3:.2f}ms "
              f"coll={terms['collective_s']*1e3:.2f}ms dom={terms['dominant']} "
              f"useful={terms['useful_ratio']:.2f} ({record['compile_s']}s)")
    except Exception as e:  # noqa
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
        print(f"[dryrun] {tag}: FAIL {record['error'][:300]}")
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    return record


def lower_block(cfg, ctx, kind, shape, n_micro: int = 1):
    """Lower one layer super-block standalone for per-layer roofline terms.
    For train, B is the microbatch (see run_one)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    key = jax.random.PRNGKey(0)
    bp_boxed = jax.eval_shape(lambda: T.block_init(key, cfg))
    bp_struct, logical = split_params(bp_boxed)
    bp_sh = S.param_shardings(cfg, ctx, logical)(bp_struct)
    B = max(shape.global_batch // n_micro, 1)
    Sq = 1 if kind == "decode" else shape.seq_len
    x = _sds((B, Sq, cfg.d_model), cfg.dtype)
    x_sh = S.batch_shardings(cfg, ctx, {"x": x})["x"]
    pos = _sds((B, Sq), jnp.int32)
    repl = NamedSharding(ctx.mesh, P())

    if kind == "train":
        def f(bp, xv, p):
            y, aux, _ = T.block_apply(bp, xv, cfg, ctx, p)
            return jnp.sum(y.astype(jnp.float32)) + aux

        g = jax.jit(jax.grad(f), in_shardings=(bp_sh, x_sh, S.batch_shardings(cfg, ctx, {"p": pos})["p"]),
                    out_shardings=bp_sh)
        lowered = g.lower(bp_struct, x, pos)
    else:
        caches = None
        if kind == "decode":
            one = {f"l{i}": T.layer_cache_init(cfg, i, B, T.cache_len_for(cfg, shape.seq_len)) for i in range(T.period(cfg))}
            cache_struct = jax.eval_shape(lambda: one)
            c_log = {k: v for k, v in T.cache_logical(cfg).items()}
            c_log = jax.tree.map(lambda t: tuple(t[1:]), c_log,
                                 is_leaf=lambda v: isinstance(v, tuple) and all(isinstance(e, (str, type(None))) for e in v))
            c_sh = jax.tree.map(
                lambda log, leaf: NamedSharding(ctx.mesh, __import__("repro.sharding.rules", fromlist=["logical_to_spec"]).logical_to_spec(log, ctx.rules, ctx.mesh, leaf.shape)),
                c_log, cache_struct,
                is_leaf=lambda v: isinstance(v, tuple) and all(isinstance(e, (str, type(None))) for e in v))

            def f(bp, xv, p, c):
                y, aux, nc = T.block_apply(bp, xv, cfg, ctx, p, caches=c, t=jnp.asarray(17, jnp.int32))
                return y, nc

            lowered = jax.jit(f, in_shardings=(bp_sh, x_sh, repl, c_sh),
                              out_shardings=(x_sh, c_sh)).lower(bp_struct, x, pos, cache_struct)
        else:
            def f(bp, xv, p):
                y, aux, _ = T.block_apply(bp, xv, cfg, ctx, p)
                return y

            lowered = jax.jit(f, in_shardings=(bp_sh, x_sh, S.batch_shardings(cfg, ctx, {"p": pos})["p"]),
                              out_shardings=x_sh).lower(bp_struct, x, pos)
    compiled = lowered.compile()
    a = analyze_compiled(compiled)
    return {"flops": a["flops"], "bytes_accessed": a["bytes_accessed"],
            "coll_bytes": a["collectives"]["total_bytes"],
            "collectives": a["collectives"]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", choices=["off", "on", "both"], default="off")
    ap.add_argument("--rules", default="default", choices=list(RULE_SETS))
    ap.add_argument("--optimizer", default="sgd")
    ap.add_argument("--correction", default="fused", choices=["fused", "two_pass"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--moe-impl", default="gather", choices=["gather", "alltoall"])
    ap.add_argument("--micro", type=int, default=0, help="override n_micro for train")
    ap.add_argument("--attn-impl", default="", choices=["", "xla", "xla_chunked"])
    ap.add_argument("--kv", default="", choices=["", "native", "int8"])
    ap.add_argument("--no-block", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--small", action="store_true",
                    help="reduced config on a 4x2 mesh with shrunk shapes "
                         "(set REPRO_DRYRUN_DEVICES=8; used by bench_roofline)")
    args = ap.parse_args()

    archs = [a for a in ARCH_IDS if a != "paper_logreg"] if args.all or not args.arch else [args.arch.replace("-", "_")]
    shapes = list(INPUT_SHAPES) if args.all or not args.shape else [args.shape]
    pods = {"off": [False], "on": [True], "both": [False, True]}[args.multi_pod]
    if args.small:
        pods = [False]  # the small mesh has no pod axis

    failures = 0
    for mp in pods:
        for arch in archs:
            for shp in shapes:
                mesh_name = "mesh4x2" if args.small else ("pod2x16x16" if mp else "pod16x16")
                variant = "" if args.rules == "default" else f"__{args.rules}"
                if args.moe_impl != "gather":
                    variant += f"__moe-{args.moe_impl}"
                if args.micro:
                    variant += f"__micro{args.micro}"
                if args.attn_impl:
                    variant += f"__attn-{args.attn_impl}"
                if args.kv:
                    variant += f"__kv-{args.kv}"
                tag = f"{arch}__{shp}__{mesh_name}" + variant
                if args.skip_existing and os.path.exists(os.path.join(args.out, tag + ".json")):
                    with open(os.path.join(args.out, tag + ".json")) as f:
                        if json.load(f).get("ok"):
                            print(f"[dryrun] {tag}: cached")
                            continue
                rec = run_one(arch, shp, mp, args.rules, args.optimizer, args.correction,
                              args.out, block_too=not args.no_block,
                              moe_impl=args.moe_impl, micro_override=args.micro,
                              attn_impl=args.attn_impl, kv_cache=args.kv,
                              small=args.small)
                failures += 0 if rec.get("ok") else 1
    print(f"[dryrun] done, failures={failures}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
