"""Distributed-semantics tests on 8 virtual CPU devices (subprocess, because
XLA device count is locked at first jax init in the main test process).

Verifies the numerics that the 512-device dry-run only type-checks:
  * MoE gather vs all-to-all dispatch vs single-device reference agree;
  * sequence-sharded flash-decode == single-device decode attention;
  * the distributed guided train step matches the single-device train step
    (same c workers, same data -> same losses).
"""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"  # skip the 60s+ TPU-probe stall
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.models import moe as MOE
    from repro.models import transformer as T
    from repro.models.module import split_params
    from repro.sharding.rules import ShardCtx, DEFAULT_RULES, LOCAL_CTX

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 2), ("data", "model"))

    # ---------------- MoE: local vs gather vs all-to-all ----------------
    cfg = get_config("qwen3_moe_235b_a22b").reduced()  # 4 experts top-2
    key = jax.random.PRNGKey(0)
    params, _ = split_params(MOE.moe_init(key, cfg))
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, cfg.d_model), jnp.float32)

    # capacity_factor = n_experts -> C clips at N: no token ever drops, so the
    # local reference and the per-shard dispatch see identical routing.
    CF = float(cfg.moe.n_experts)
    y_ref, aux_ref = MOE.moe_apply(params, x, cfg, LOCAL_CTX, capacity_factor=CF)

    for impl in ("gather", "alltoall"):
        ctx = ShardCtx(mesh=mesh, rules=DEFAULT_RULES, moe_impl=impl)
        xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
        y, aux = jax.jit(lambda p, xv: MOE.moe_apply(p, xv, cfg, ctx, capacity_factor=CF))(params, xs)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=2e-4, rtol=2e-4)
        # aux: per-shard load-balance estimator (mean of shard-local E*f_e*P_e)
        # differs from the global product by O(inter-shard routing variance)
        np.testing.assert_allclose(float(aux), float(aux_ref), rtol=5e-2)
        print(f"moe {impl} OK")

    # ------------- sequence-sharded flash decode vs local ---------------
    cfg2 = get_config("yi_9b").reduced()
    B, S_c = 8, 64
    K, dh = cfg2.n_kv_heads, cfg2.d_head
    H = cfg2.n_heads
    q = jax.random.normal(jax.random.PRNGKey(2), (B, 1, H, dh), jnp.float32)
    kc = jax.random.normal(jax.random.PRNGKey(3), (B, S_c, K, dh), jnp.float32)
    vc = jax.random.normal(jax.random.PRNGKey(4), (B, S_c, K, dh), jnp.float32)
    clen = jnp.asarray(np.random.default_rng(0).integers(1, S_c + 1, (B,)), jnp.int32)

    from repro.models import layers as L
    ref = L.decode_attention(q, kc, vc, clen, n_kv_heads=K)
    ctx2 = ShardCtx(mesh=mesh, rules=DEFAULT_RULES)
    kc_s = jax.device_put(kc, NamedSharding(mesh, P(None, "model", None, None)))
    vc_s = jax.device_put(vc, NamedSharding(mesh, P(None, "model", None, None)))
    out = jax.jit(lambda *a: T.sharded_decode_attention(*a, cfg2, ctx2))(q, kc_s, vc_s, clen)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4, rtol=2e-4)
    print("sharded decode OK")

    # --------- distributed guided train step == local train step --------
    from repro.core.guided import GuidedConfig
    from repro.optim import constant, get_optimizer
    from repro.engine import mesh as M
    from repro.data import make_batch_for

    cfg3 = get_config("yi_9b").reduced()
    cfg3 = cfg3.replace(param_dtype="float32", compute_dtype="float32")
    gcfg = GuidedConfig(mode="ssgd", guided=True, rho=2)
    opt = get_optimizer("sgd")
    batch = {k: jnp.asarray(v) for k, v in make_batch_for(cfg3, 16, 8, seed=0).items()}

    losses = {}
    for name, ctx3 in (("local", LOCAL_CTX), ("mesh", ShardCtx(mesh=mesh, rules=DEFAULT_RULES))):
        p3, _, g3 = M.init_train_state(jax.random.PRNGKey(0), cfg3, gcfg, opt, n_workers=4)
        step = jax.jit(M.build_train_step(cfg3, gcfg, opt, ctx3, constant(1e-2), n_workers=4))
        ls = []
        for _ in range(4):
            p3, g3, m = step(p3, g3, batch)
            ls.append(float(m["loss"]))
        losses[name] = ls
    np.testing.assert_allclose(losses["local"], losses["mesh"], rtol=2e-4, atol=2e-4)
    print("distributed train step OK")
    """
)


@pytest.mark.slow
def test_distributed_semantics():
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root"},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-3000:])
    assert "moe gather OK" in out.stdout
    assert "moe alltoall OK" in out.stdout
    assert "sharded decode OK" in out.stdout
    assert "distributed train step OK" in out.stdout


HOST_MESH_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax, numpy as np
    import repro.kernels.guided_update.ops as OPS
    # the fused update through the Pallas kernel (interpreted on the CPU), as
    # on a TPU, so the mesh run takes its per-shard shard_map path
    OPS.default_interpret = lambda: False
    from repro.engine import ExperimentSpec, Trainer

    base = ExperimentSpec(backend="mesh", arch="yi_9b", reduced=True,
                          mode="ssgd", strategy="guided_fused", rho=2,
                          global_batch=8, seq_len=32, steps=6, chunk_steps=2,
                          prefetch=True, lr=0.05)
    runs = {}
    for name, spec in (("local", base.replace(mesh="local", workers=4)),
                       ("host", base.replace(mesh="host"))):
        r = Trainer.from_spec(spec).fit()
        runs[name] = r
    host, local = runs["host"], runs["local"]
    assert [h["corr_w"] for h in host.history] == [h["corr_w"] for h in local.history]
    np.testing.assert_allclose([h["loss"] for h in host.history],
                               [h["loss"] for h in local.history], rtol=1e-5)
    for a, b in zip(jax.tree.leaves(host.model), jax.tree.leaves(local.model)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6)
    specs = {str(x.sharding.spec) for x in jax.tree.leaves(host.model)}
    assert any("data" in s for s in specs), specs   # FSDP-sharded leaves
    print("host mesh fused update OK")
    """
)


def test_host_mesh_fused_update_matches_one_device():
    """gSSGD on a 4-device host mesh (c = 4 data shards, params FSDP-sharded,
    the fused update kernel per shard) == c = 4 workers on one device."""
    out = subprocess.run(
        [sys.executable, "-c", HOST_MESH_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": os.environ.get("PATH", "/usr/bin:/bin")},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-3000:])
    assert "host mesh fused update OK" in out.stdout
