"""Plain reference of MiniCPM (openbmb/MiniCPM-2B-sft-bf16, arXiv:2404.06395)
and of the training step the benchmark's MiniCPM cells time.

The block is `dense_lm`'s (RMSNorm, rotary position embedding, causal
attention, SwiGLU feed-forward, tied head), with MiniCPM's three published
scalings, as in the published `modeling_minicpm.py`:

  * the embeddings times `scale_emb` (12);
  * each residual branch, attention and feed-forward, times
    scale_depth / sqrt(num_hidden_layers) (1.4 / sqrt(40)):
    x <- x + branch(x) * scale_depth / sqrt(40);
  * the final normed state times dim_model_base / hidden_size (256 / 2304)
    before the tied head.

The multipliers are taken at the depth and width the configuration file
states under `multipliers_at` (the published 40 and 2304): a cell that runs
fewer layers runs a stage of the published model, whose multipliers do not
change with the cut.

Departures from the published model, shared with the program under test:
weights are random from the seed (`dense_lm.init`, not MiniCPM's init std);
one learning rate for every matrix (not MiniCPM's muP per-matrix rates, a
training recipe and not a layer equation); the cell's depth (the
configuration's `reduced`).

Everything is computed in float32 with `precision="highest"` matrix products,
the scalings too, layer by layer, with `dense_lm`'s update rules (SGD or
Adam, DC-ASGD's Taylor term against stale weights and their refresh, the
guided correction) and its `precision` of "f32" or "fp8" (matrix operands in
float8_e4m3fn: the control). It imports nothing of the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from references import dense_lm as D
from references.dense_lm import named_leaves  # noqa: F401  (the readings' leaf names)


class Scales:
    """MiniCPM's three multipliers, read from a configuration file."""

    def __init__(self, cfg: dict):
        at = cfg["multipliers_at"]
        self.emb = float(cfg["scale_emb"])
        self.residual = float(cfg["scale_depth"]) / math.sqrt(int(at["num_hidden_layers"]))
        self.logit = float(cfg["dim_model_base"]) / int(at["hidden_size"])


def _layer(lp, x, m: D.Dims, s: Scales, precision):
    """One block on float32 activations x (B, S, d), each residual branch
    times `s.residual`."""
    f32 = lambda a: a.astype(jnp.float32)
    B, S, d = x.shape
    G = m.H // m.K
    h = D._rmsnorm(x, f32(lp["norm1"]), m.eps)
    q = D._mm("bsd,de->bse", h, f32(lp["mixer"]["wq"]), precision).reshape(B, S, m.H, m.dh)
    k = D._mm("bsd,de->bse", h, f32(lp["mixer"]["wk"]), precision).reshape(B, S, m.K, m.dh)
    v = D._mm("bsd,de->bse", h, f32(lp["mixer"]["wv"]), precision).reshape(B, S, m.K, m.dh)
    q, k = D._rope(q, m.theta), D._rope(k, m.theta)
    q = q.reshape(B, S, m.K, G, m.dh)
    scores = D._mm("bqkgd,bskd->bkgqs", q, k, precision) / np.sqrt(m.dh)
    causal = np.tril(np.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = D._mm("bkgqs,bskd->bqkgd", p, v, precision).reshape(B, S, m.H * m.dh)
    x = x + s.residual * D._mm("bse,ed->bsd", o, f32(lp["mixer"]["wo"]), precision)
    h = D._rmsnorm(x, f32(lp["norm2"]), m.eps)
    gu = D._mm("bsd,dtf->bstf", h, f32(lp["ffn"]["wi"]), precision)
    a = jax.nn.silu(gu[:, :, 0]) * gu[:, :, 1]
    return x + s.residual * D._mm("bsf,fd->bsd", a, f32(lp["ffn"]["wo"]), precision)


def _logits(hp, x, m: D.Dims, s: Scales, precision):
    """Logits (B, S, V): the final normed state times `s.logit`, then the
    tied head."""
    x = s.logit * D._rmsnorm(x, hp["final_norm"].astype(jnp.float32), m.eps)
    w = hp["head"] if "head" in hp else hp["table"].T
    return D._mm("bsd,dv->bsv", x, w.astype(jnp.float32), precision)


def _head_loss(hp, x, labels, m: D.Dims, s: Scales, precision):
    """Mean next-token cross-entropy per row (B,)."""
    logits = _logits(hp, x, m, s, precision)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    true = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - true, axis=-1)


def logits(cfg: dict, w, tokens, precision: str = "f32"):
    """The full forward pass of weights `w` (`dense_lm.init`'s layout) on
    tokens (B, S): float32 logits (B, S, V), what serving is compared with."""
    m, s = D.Dims(cfg), Scales(cfg)
    x = s.emb * w["embed"]["table"][tokens].astype(jnp.float32)
    for lp in w["layers"]:
        x = _layer(lp, x, m, s, precision)
    out = "table" if m.tied else "head"
    return _logits({"final_norm": w["final_norm"], out: w["embed"][out]}, x, m, s, precision)


class Model(D.Model):
    """`dense_lm.Model`'s loss and gradients, one layer and one block of rows
    at a time, with MiniCPM's block, head and embedding."""

    def __init__(self, m: D.Dims, s: Scales, precision: str = "f32"):
        super().__init__(m, precision)
        P = precision
        self._fwd = jax.jit(lambda lp, x: _layer(lp, x, m, s, P))

        def layer_vjp(lp, x, dy):
            _, pull = jax.vjp(lambda lp_, x_: _layer(lp_, x_, m, s, P), D._f32(lp), x)
            return pull(dy)

        self._bwd = jax.jit(layer_vjp)

        def head(hp, x, labels, row_w):
            def share(hp_, x_):
                per_row = _head_loss(hp_, x_, labels, m, s, P)
                return jnp.sum(row_w * per_row), per_row

            (_, per_row), grads = jax.value_and_grad(
                share, argnums=(0, 1), has_aux=True)(D._f32(hp), x)
            return per_row, grads

        self._head = jax.jit(head)
        # x0 = scale_emb * table[tok], so the table's gradient is scale_emb * dx0
        self._embed = jax.jit(lambda table, tok: s.emb * table[tok].astype(jnp.float32))
        self._embed_grad = jax.jit(
            lambda acc, tok, dx: acc.at[tok].add(s.emb * dx), donate_argnums=0)


class Trainer(D.Trainer):
    """`dense_lm.Trainer`'s step (workers, staleness and its refresh, DC-ASGD,
    the guided correction, SGD or Adam) on MiniCPM's model.

    Adam's float32 m and v live in host memory and visit the device one part
    at a time, in the update of that part: beside the weights, the stale
    weights and the float32 head, they would not fit one chip's 16 GB at the
    cell's size. Where they live changes no number."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, precision: str = "f32"):
        super().__init__(cfg, traffic, seed, precision)
        self.model = Model(self.m, Scales(cfg), precision)
        if self.opt == "adam":
            host = lambda: jax.tree.map(lambda a: np.zeros(a.shape, np.float32), self.w)
            self.mom, self.vel = host(), host()

    def train_step(self, batch) -> float:
        """`dense_lm.Trainer.train_step`, with Adam's state brought back to
        the host after each part's update."""
        at = self.w_stale if self.stale else self.w
        t = jnp.asarray(self.step + 1, jnp.int32)
        lam_ws = self.w_stale if self.lam else None

        def sink(where, g):
            w, m, v = D._apply(D._part(self.w, where), g, D._part(lam_ws, where),
                               D._part(self.mom, where), D._part(self.vel, where), t,
                               self.lr, lam=self.lam, opt=self.opt)
            D._set_part(self.w, where, w)
            if self.opt == "adam":
                D._set_part(self.mom, where, jax.device_get(m))
                D._set_part(self.vel, where, jax.device_get(v))

        B = batch["tokens"].shape[0]
        rows = B // self.c
        row_w = 1.0 / B + np.repeat(self.correction_weights(), rows) / rows
        worker = self.model.loss_and_grads(at, batch, sink, row_w).reshape(self.c, rows).mean(1)
        avg = float(worker.mean())
        if self.stale and self.step % self.period == 0:
            self.w_stale = jax.tree.map(jnp.copy, self.w)
        if self.guided:
            self._advance_scores(worker, avg)
        self.step += 1
        return avg
