"""Serving launcher: thin client over the continuous-batching ServeEngine.

Submits a batch of synthetic requests to `repro.serve.ServeEngine` (slot pool
+ persistent ring-buffer KV caches + per-slot decode positions, DESIGN.md §7)
and prints per-request streams plus aggregate throughput. `--stagger` varies
prompt and generation lengths across requests so slot recycling is visible;
`--lockstep` runs the fixed-batch barriered baseline instead.

Sampling is real now: `--sampling greedy|temperature|topk` (+ `--temperature`,
`--top-k`) replaces the old dead `--greedy` flag (which was
action="store_true" with default=True — impossible to disable, and no sampler
existed behind it).

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --reduced \
      --batch 4 --requests 8 --prompt-len 64 --gen 32 --stagger \
      --sampling topk --top-k 40 --temperature 0.8
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.common.cache import enable_compile_cache
from repro.configs import get_config
from repro.engine import build_ctx  # shared mesh-kind -> ShardCtx resolution
from repro.models import transformer as T
from repro.models.module import split_params
from repro.serve import Request, SamplingParams, ServeEngine, lockstep_generate


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4, help="engine slot-pool size")
    ap.add_argument("--requests", type=int, default=0,
                    help="number of requests (default: --batch)")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--stagger", action="store_true",
                    help="heterogeneous prompt/gen lengths across requests")
    ap.add_argument("--mesh", default="local")
    ap.add_argument("--sampling", choices=("greedy", "temperature", "topk"),
                    default="greedy")
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--top-k", type=int, default=40)
    ap.add_argument("--lockstep", action="store_true",
                    help="run the fixed-batch barriered baseline instead")
    ap.add_argument("--ckpt-dir", default="",
                    help="warm-start from a training checkpoint (full-state "
                         "snapshot; only the params subtree is restored)")
    ap.add_argument("--ckpt-step", type=int, default=0,
                    help="checkpoint step to serve (default: latest manifest entry)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.ckpt_dir:
        # the manifest's recorded config is authoritative for its snapshot —
        # serving a reduced-trained checkpoint must not silently build the
        # full-size model because a flag was forgotten
        from repro.checkpoint import model_config_from_manifest

        try:
            ckpt_cfg = model_config_from_manifest(args.ckpt_dir,
                                                  args.ckpt_step or None)
        except (FileNotFoundError, ValueError):
            ckpt_cfg = None  # v1 dir / no metadata: trust the flags
        if ckpt_cfg is not None:
            if (ckpt_cfg.name, ckpt_cfg.n_layers, ckpt_cfg.d_model) != (
                    cfg.name, cfg.n_layers, cfg.d_model):
                print(f"using checkpoint config {ckpt_cfg.name} "
                      f"(layers={ckpt_cfg.n_layers}, d_model={ckpt_cfg.d_model}) "
                      f"from the manifest over the CLI flags")
            cfg = ckpt_cfg
    if not cfg.supports_decode:
        raise SystemExit(f"{cfg.name} is encoder-only; no decode (see DESIGN.md §5)")
    ctx = build_ctx(args.mesh)

    params = (None if args.ckpt_dir else
              split_params(T.model_init(jax.random.PRNGKey(args.seed), cfg))[0])

    n_req = args.requests or args.batch
    rng = np.random.default_rng(args.seed)
    # vlm archs splice per-request image-patch embeddings into the prompt
    # (lockstep baseline is token-only, like the engine's decode path)
    n_patches = cfg.n_patches if cfg.arch_type == "vlm" and not args.lockstep else 0
    min_len = max(1, n_patches + 2)
    reqs = []
    max_prompt = 0
    for i in range(n_req):
        if args.stagger:
            L = int(rng.integers(max(1, args.prompt_len // 4), args.prompt_len + 1))
            gen = int(rng.integers(max(1, args.gen // 4), args.gen + 1))
        else:
            L, gen = args.prompt_len, args.gen
        L = max(L, min_len)
        max_prompt = max(max_prompt, L)
        sp = SamplingParams(method=args.sampling, temperature=args.temperature,
                            top_k=args.top_k, seed=args.seed + i)
        prompt = rng.integers(0, cfg.vocab_size, (L,)).tolist()
        patches = (rng.standard_normal((n_patches, cfg.d_model)).astype(np.float32)
                   if n_patches else None)
        reqs.append(Request(prompt, max_new_tokens=gen, sampling=sp, patches=patches))

    max_len = max(args.prompt_len, max_prompt) + args.gen
    if args.ckpt_dir:
        # one restore path for API and CLI: ServeEngine.from_checkpoint owns
        # the manifest lookup, params-subtree restore and mesh placement
        engine = ServeEngine.from_checkpoint(
            args.ckpt_dir, cfg, ctx, step=args.ckpt_step or None,
            max_batch=args.batch, max_len=max_len)
        from repro.checkpoint import latest_step

        print(f"serving training snapshot step "
              f"{args.ckpt_step or latest_step(args.ckpt_dir)} from {args.ckpt_dir}")
    else:
        engine = ServeEngine(params, cfg, ctx, max_batch=args.batch, max_len=max_len)

    if args.lockstep:
        comps, stats = lockstep_generate(engine, reqs)
    else:
        comps = engine.run(reqs)
        stats = engine.stats()

    print(f"prefill: {stats.get('prefill_calls', len(comps))} calls, "
          f"pool={args.batch} slots, max_len={max_len}")
    print(f"decode:  {stats['decode_steps']} steps in {stats['wall_s']:.2f}s "
          f"({stats['tokens_per_s']:.1f} tok/s, occupancy {stats['occupancy']:.2f})")
    for c in sorted(comps, key=lambda c: c.request_id)[:2]:
        print(f"  request {c.request_id} ({c.prompt_len}+{c.new_tokens}, "
              f"{c.finish_reason}): {c.tokens[:16]}...")
    return comps


if __name__ == "__main__":
    main()
