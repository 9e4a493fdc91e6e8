"""MiniCPM's numerics (scale_emb, scaled residual branches, scaled logits;
configs/minicpm_2b.py) against the plain reference that decides the chip
benchmark's `correct` (benchmarks/chip/references/minicpm_lm.py), at a tiny
size in float32 on seeded random weights:

  * training: the per-example loss and every leaf's gradient;
  * serving: prefill, then decoding through the cache in `ServeEngine`,
    against the reference's full forward pass, in logits;
  * each of the three multipliers left out of the program fails both.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "chip"))

from references import dense_lm, minicpm_lm  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs import minicpm_2b  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.models.module import split_params  # noqa: E402
from repro.serve import Request, ServeEngine  # noqa: E402
from repro.serve import engine as serve_engine  # noqa: E402

#: float32 on both sides (the program's reduced config computes in float32,
#: the reference at precision "highest"): what is left is summation order,
#: ~1e-6 of a norm over two layers, so 1e-4 leaves 100x of room; a missing
#: multiplier moves the worst gradient leaf and the logits by more than half
#: of their norm
TOL = 1e-4
SCALARS = ("scale_emb", "residual_scale", "logit_scale")


@pytest.fixture(scope="module")
def model():
    """The reduced MiniCPM with full causal attention and the published rope
    base, as the chip cell runs it; its weights from seed 0."""
    cfg = get_config("minicpm_2b").reduced().replace(sliding_window=0, rope_theta=10000.0)
    params = split_params(T.model_init(jax.random.PRNGKey(0), cfg))[0]
    return cfg, params


def ref_config(cfg) -> dict:
    """The reference's configuration of the program's model: its sizes, and
    MiniCPM's published scalars at the published depth and width."""
    return {"hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "intermediate_size": cfg.d_ff,
            "vocab_size": cfg.vocab_size, "num_hidden_layers": cfg.n_layers,
            "tie_word_embeddings": cfg.tie_embeddings, "rms_norm_eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta, "torch_dtype": cfg.param_dtype,
            "scale_emb": minicpm_2b.SCALE_EMB, "scale_depth": minicpm_2b.SCALE_DEPTH,
            "dim_model_base": minicpm_2b.DIM_MODEL_BASE,
            "multipliers_at": {"num_hidden_layers": minicpm_2b.N_LAYERS,
                               "hidden_size": minicpm_2b.D_MODEL}}


def ref_weights(params) -> dict:
    """The program's weights in the reference's layout (one dict per layer)."""
    blocks = params["blocks"]["l0"]
    n = jax.tree.leaves(blocks)[0].shape[0]
    return {"layers": [jax.tree.map(lambda a, i=i: a[i], blocks) for i in range(n)],
            "embed": dict(params["embed"]), "final_norm": params["final_norm"]}


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def train_gaps(cfg, params, rcfg) -> dict:
    """The program's per-example loss and gradients against the reference's,
    on one seeded batch: the loss's gap and the worst leaf's."""
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, cfg.vocab_size, (4, 16)).astype(np.int32)
    labels = rng.integers(0, cfg.vocab_size, (4, 16)).astype(np.int32)
    row_w = np.array([0.1, 0.2, 0.3, 0.4], np.float32)
    batch = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels)}

    def objective(p):
        per_ex, _, _ = T.forward_train(p, batch, cfg)
        return jnp.sum(row_w * per_ex), per_ex

    (_, loss), grads = jax.value_and_grad(objective, has_aux=True)(params)

    got = {}
    sink = lambda where, g: got.__setitem__(where, g)
    ref_model = minicpm_lm.Model(dense_lm.Dims(rcfg), minicpm_lm.Scales(rcfg))
    ref_loss = ref_model.loss_and_grads(ref_weights(params), batch, sink, row_w)

    gaps = {"loss": rel(loss, ref_loss),
            "embed.table": rel(grads["embed"]["table"], got["embed"]["table"]),
            "final_norm": rel(grads["final_norm"], got["head"]["final_norm"])}
    for i in range(cfg.n_layers):
        prog = jax.tree.map(lambda a: a[i], grads["blocks"]["l0"])
        for path, g in jax.tree_util.tree_flatten_with_path(prog)[0]:
            ref = got[i]
            for k in path:
                ref = ref[k.key]
            gaps[f"{'.'.join(k.key for k in path)}.{i}"] = rel(g, ref)
    return gaps


def serve_gap(cfg, params, rcfg, monkeypatch) -> float:
    """Greedy generation through `ServeEngine` (a padded prefill into a pool
    slot, then per-slot cached decode steps), the logits each step sampled
    from recorded; the worst step's gap to the reference's full forward pass
    over the prompt and the generated tokens."""
    seen = []
    sample = serve_engine.sample_tokens

    def recording(logits, *a):
        jax.debug.callback(lambda x: seen.append(np.asarray(x)), logits)
        return sample(logits, *a)

    monkeypatch.setattr(serve_engine, "sample_tokens", recording)
    prompt = np.random.default_rng(2).integers(0, cfg.vocab_size, (5,)).tolist()
    engine = ServeEngine(params, cfg, max_batch=2, max_len=16)
    (done,) = engine.run([Request(prompt, max_new_tokens=6)])
    jax.effects_barrier()
    n = len(done.tokens)
    # the prefill's (1, V), then one (2, V) per decode step: the request's slot
    steps = [seen[0][0]] + [x[done.slot] for x in seen[1:n]]
    seq = jnp.asarray([prompt + done.tokens[:-1]], jnp.int32)
    ref = minicpm_lm.logits(rcfg, ref_weights(params), seq)[0, len(prompt) - 1:]
    assert len(steps) == n == ref.shape[0]
    return max(rel(a, b) for a, b in zip(steps, np.asarray(ref)))


def test_train_loss_and_every_gradient_match_reference(model):
    cfg, params = model
    gaps = train_gaps(cfg, params, ref_config(cfg))
    assert len(gaps) == 3 + 8 * cfg.n_layers
    assert max(gaps.values()) < TOL, gaps


def test_serve_prefill_and_cached_decode_match_reference(model, monkeypatch):
    cfg, params = model
    assert serve_gap(cfg, params, ref_config(cfg), monkeypatch) < TOL


@pytest.mark.parametrize("path", ["train", "serve"])
@pytest.mark.parametrize("absent", SCALARS)
def test_each_multiplier_left_out_fails(model, absent, path, monkeypatch):
    cfg, params = model
    broken = cfg.replace(**{absent: None})
    if path == "train":
        gap = max(train_gaps(broken, params, ref_config(cfg)).values())
    else:
        gap = serve_gap(broken, params, ref_config(cfg), monkeypatch)
    assert gap > 100 * TOL, (absent, path, gap)


def test_multipliers_are_the_published_models_at_any_depth():
    """A depth or width override (the chip cell's 10 layers, the reduced
    config) keeps the multipliers of the published 40 x 2304 model; every
    other model has none."""
    full = get_config("minicpm_2b")
    for cfg in (full, full.replace(n_layers=10), full.reduced()):
        assert cfg.scale_emb == 12.0
        assert cfg.residual_scale == pytest.approx(1.4 / np.sqrt(40))
        assert cfg.logit_scale == pytest.approx(256 / 2304)
    yi = get_config("yi_9b")
    assert (yi.scale_emb, yi.residual_scale, yi.logit_scale) == (None, None, None)
