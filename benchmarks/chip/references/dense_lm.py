"""Plain reference of a dense decoder-only language model and of the
data-parallel training step the benchmark's training cells time.

Written from the published architecture (Llama-style blocks: RMSNorm, rotary
position embedding, grouped-query causal attention, SwiGLU feed-forward, an
output head that is either its own matrix or the tied embedding table) and
from the update rules the cells state (SGD or Adam, DC-ASGD's Taylor term
against stale weights, the paper's guided correction at each window end). It
imports nothing of the program under test.

Weights are made from the seed with the same recipe a configuration states:
`jax.random` normals under one key tree, scaled and stored in the parameter
dtype. Everything is computed in float32 with `precision="highest"` matrix
products, layer by layer (the backward pass is one `jax.vjp` per layer), so
that a model at published widths fits one chip beside its state.

`precision` selects the operand precision of every matrix product: "f32"
(the reference) or "fp8" (operands rounded to float8_e4m3fn, the control: the
nearest precision below the bfloat16 the configurations state). Accumulation
stays float32 in both.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
_OPERAND = {"f32": None, "fp8": jnp.float8_e4m3fn}


def _round(x, precision):
    dt = _OPERAND[precision]
    return x if dt is None else x.astype(dt).astype(jnp.float32)


def _mm(subscripts, a, b, precision):
    return jnp.einsum(subscripts, _round(a, precision), _round(b, precision),
                      precision=HIGHEST, preferred_element_type=jnp.float32)


# ------------------------------------------------------------------ shapes


class Dims:
    """The sizes the reference needs, read from a configuration file."""

    def __init__(self, cfg: dict):
        self.d = int(cfg["hidden_size"])
        self.H = int(cfg["num_attention_heads"])
        self.K = int(cfg["num_key_value_heads"])
        self.f = int(cfg["intermediate_size"])
        self.V = int(cfg["vocab_size"])
        self.L = int(cfg["num_hidden_layers"])
        self.dh = self.d // self.H
        self.tied = bool(cfg["tie_word_embeddings"])
        self.eps = float(cfg["rms_norm_eps"])
        self.theta = float(cfg["rope_theta"])
        self.dtype = jnp.dtype(cfg["torch_dtype"])

    def _key(self):
        return tuple(sorted((k, str(v)) for k, v in vars(self).items()))

    def __eq__(self, other):
        return isinstance(other, Dims) and self._key() == other._key()

    def __hash__(self):
        return hash(self._key())


# -------------------------------------------------------------------- init


def _lecun(key, shape, fan_in, dtype):
    scale = 1.0 / np.sqrt(max(fan_in, 1))
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _layer_init(key, m: Dims):
    ki = jax.random.split(jax.random.split(key, 1)[0], 3)
    ka = jax.random.split(ki[0], 4)
    kf = jax.random.split(ki[1])
    d, dt = m.d, m.dtype
    return {
        "mixer": {"wq": _lecun(ka[0], (d, m.H * m.dh), d, dt),
                  "wk": _lecun(ka[1], (d, m.K * m.dh), d, dt),
                  "wv": _lecun(ka[2], (d, m.K * m.dh), d, dt),
                  "wo": _lecun(ka[3], (m.H * m.dh, d), m.H * m.dh, dt)},
        "ffn": {"wi": _lecun(kf[0], (d, 2, m.f), d, dt),
                "wo": _lecun(kf[1], (m.f, d), m.f, dt)},
        "norm1": jnp.ones((d,), jnp.float32),
        "norm2": jnp.ones((d,), jnp.float32),
    }


def init(m: Dims, key):
    """The weights of `key` (`jax.random.PRNGKey(seed)`): {"layers": [one
    dict per layer], "embed": {"table"[, "head"]}, "final_norm"}, matrices in
    the configuration's dtype, norm scales in float32."""
    k_embed, k_blocks = jax.random.split(key)
    k1, k2 = jax.random.split(k_embed)
    embed = {"table": (0.02 * jax.random.normal(k1, (m.V, m.d))).astype(m.dtype)}
    if not m.tied:
        embed["head"] = _lecun(k2, (m.d, m.V), m.d, m.dtype)
    layers = [_layer_init(k, m) for k in jax.random.split(k_blocks, m.L)]
    return {"layers": layers, "embed": embed,
            "final_norm": jnp.ones((m.d,), jnp.float32)}


def named_leaves(w) -> dict:
    """Leaf name -> array, layer leaves as `blocks.l0.<path>.<layer>`: the
    names the benchmark's readings use for every model of this kind."""
    out = {}
    for i, lp in enumerate(w["layers"]):
        for path, x in jax.tree_util.tree_flatten_with_path(lp)[0]:
            out["blocks.l0." + ".".join(k.key for k in path) + f".{i}"] = x
    for k, x in w["embed"].items():
        out[f"embed.{k}"] = x
    out["final_norm"] = w["final_norm"]
    return out


# ----------------------------------------------------------------- forward


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (B, S, heads, dh). Rotates the two halves of each head."""
    S, dh = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh))
    ang = np.arange(S, dtype=np.float64)[:, None] * freqs[None]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(lp, x, m: Dims, precision):
    """One block on float32 activations x (B, S, d)."""
    f32 = lambda a: a.astype(jnp.float32)
    B, S, d = x.shape
    G = m.H // m.K
    h = _rmsnorm(x, f32(lp["norm1"]), m.eps)
    q = _mm("bsd,de->bse", h, f32(lp["mixer"]["wq"]), precision).reshape(B, S, m.H, m.dh)
    k = _mm("bsd,de->bse", h, f32(lp["mixer"]["wk"]), precision).reshape(B, S, m.K, m.dh)
    v = _mm("bsd,de->bse", h, f32(lp["mixer"]["wv"]), precision).reshape(B, S, m.K, m.dh)
    q, k = _rope(q, m.theta), _rope(k, m.theta)
    q = q.reshape(B, S, m.K, G, m.dh)
    s = _mm("bqkgd,bskd->bkgqs", q, k, precision) / np.sqrt(m.dh)
    causal = np.tril(np.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm("bkgqs,bskd->bqkgd", p, v, precision).reshape(B, S, m.H * m.dh)
    x = x + _mm("bse,ed->bsd", o, f32(lp["mixer"]["wo"]), precision)
    h = _rmsnorm(x, f32(lp["norm2"]), m.eps)
    gu = _mm("bsd,dtf->bstf", h, f32(lp["ffn"]["wi"]), precision)
    a = jax.nn.silu(gu[:, :, 0]) * gu[:, :, 1]
    return x + _mm("bsf,fd->bsd", a, f32(lp["ffn"]["wo"]), precision)


def _head_loss(hp, x, labels, m: Dims, precision):
    """Mean next-token cross-entropy per row: (B,)."""
    x = _rmsnorm(x, hp["final_norm"].astype(jnp.float32), m.eps)
    w = hp["head"] if "head" in hp else hp["table"].T
    logits = _mm("bsd,dv->bsv", x, w.astype(jnp.float32), precision)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    true = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - true, axis=-1)


# ------------------------------------------------------------ loss + grads


#: bytes of float32 scores or logits one row block may hold at a time
_BLOCK_BYTES = 1 << 30


def rows_per_block(m: Dims, batch: int, seq: int) -> int:
    """Rows processed together: as many as keep the attention scores
    (H x S x S) and the logits (S x V) of the block within `_BLOCK_BYTES`."""
    per_row = 4 * seq * max(m.H * seq, m.V)
    rb = max(1, min(batch, _BLOCK_BYTES // per_row))
    while batch % rb:
        rb -= 1
    return rb


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


class Model:
    """Loss and gradients of the configuration's model, one layer and one
    block of rows at a time. The jitted pieces are built once per (dims,
    precision)."""

    def __init__(self, m: Dims, precision: str = "f32"):
        self.m = m
        self.precision = precision
        P = precision
        self._fwd = jax.jit(lambda lp, x: _layer(lp, x, m, P))

        def layer_vjp(lp, x, dy):
            # gradients in float32, not in the weights' stored dtype
            _, pull = jax.vjp(lambda lp_, x_: _layer(lp_, x_, m, P), _f32(lp), x)
            return pull(dy)

        self._bwd = jax.jit(layer_vjp)

        def head(hp, x, labels, row_w):
            # this block's share of the objective: its rows' losses, each
            # with its weight
            def share(hp_, x_):
                per_row = _head_loss(hp_, x_, labels, m, P)
                return jnp.sum(row_w * per_row), per_row

            (_, per_row), grads = jax.value_and_grad(
                share, argnums=(0, 1), has_aux=True)(_f32(hp), x)
            return per_row, grads

        self._head = jax.jit(head)
        self._embed = jax.jit(lambda table, tok: table[tok].astype(jnp.float32))
        self._embed_grad = jax.jit(
            lambda acc, tok, dx: acc.at[tok].add(dx), donate_argnums=0)
        self._add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                            donate_argnums=0)

    def loss_and_grads(self, w, batch, sink, row_w) -> np.ndarray:
        """Per-row mean losses (B,) at weights `w` on `batch` = {"tokens",
        "labels"} of (B, S) int32, and the float32 gradients of the objective
        sum_r row_w[r] * loss_r. They go to `sink` as soon as each part is
        final, so that the caller can update that part at once:
        `sink("head", {"final_norm"[, "head"]})`, `sink(i, layer i's dict)`
        from the last layer down, then `sink("embed", {"table"})`. A part's
        weights are never read again by the step once its gradient is sunk."""
        tok, labels = batch["tokens"], batch["labels"]
        B, S = tok.shape
        rb = rows_per_block(self.m, B, S)
        rows = [slice(i, i + rb) for i in range(0, B, rb)]
        row_w = jnp.asarray(row_w, jnp.float32)
        # xs[i][r]: the input of layer i for row block r
        xs = [[self._embed(w["embed"]["table"], tok[r]) for r in rows]]
        for lp in w["layers"]:
            xs.append([self._fwd(lp, x) for x in xs[-1]])
        out = "table" if self.m.tied else "head"
        hp = {"final_norm": w["final_norm"], out: w["embed"][out]}
        per_row, g_head, dxs = [], None, []
        for r, x in zip(rows, xs.pop()):
            l_r, (g_r, dx) = self._head(hp, x, labels[r], row_w[r])
            per_row.append(l_r)
            dxs.append(dx)
            g_head = g_r if g_head is None else self._add(g_head, g_r)
        g_table = g_head.pop("table") if self.m.tied else \
            jnp.zeros(w["embed"]["table"].shape, jnp.float32)
        sink("head", g_head)
        for i in reversed(range(self.m.L)):
            acc = None
            for j, x in enumerate(xs.pop()):
                g, dxs[j] = self._bwd(w["layers"][i], x, dxs[j])
                acc = g if acc is None else self._add(acc, g)
            sink(i, acc)
        for r, dx in zip(rows, dxs):
            g_table = self._embed_grad(g_table, tok[r], dx)
        sink("embed", {"table": g_table})
        return np.concatenate(jax.device_get(per_row)).astype(np.float64)


# ------------------------------------------------------------------ update


@functools.partial(jax.jit, static_argnames=("lam", "opt"))
def _apply(w, g, w_stale, m, v, t, lr, *, lam, opt):
    """One part of the weights: DC-ASGD's Taylor term (lam != 0) then SGD or
    Adam; new weights rounded once to their stored dtype."""
    b1, b2, eps = 0.9, 0.999, 1e-8
    tf = t.astype(jnp.float32)

    def one(w, g, ws, m, v):
        wc, gc = w.astype(jnp.float32), g.astype(jnp.float32)
        if lam:
            gc = gc + lam * gc * gc * (wc - ws.astype(jnp.float32))
        if opt == "sgd":
            return (wc - lr * gc).astype(w.dtype), m, v
        m = b1 * m + (1 - b1) * gc
        v = b2 * v + (1 - b2) * gc * gc
        step = m / (1 - b1 ** tf) / (jnp.sqrt(v / (1 - b2 ** tf)) + eps)
        return (wc - lr * step).astype(w.dtype), m, v

    none = jax.tree.map(lambda _: None, w)
    out = jax.tree.map(one, w, g, none if w_stale is None else w_stale,
                       none if m is None else m, none if v is None else v,
                       is_leaf=lambda x: x is None)
    pick = lambda i: jax.tree.map(lambda o: o[i], out, is_leaf=lambda x: isinstance(x, tuple))
    return pick(0), pick(1), pick(2)


def _part(tree, where):
    """The part of a weight-shaped tree that a sink key names."""
    if tree is None:
        return None
    if where == "head":
        return {k: tree["embed"][k] if k != "final_norm" else tree[k]
                for k in (["final_norm"] + (["head"] if "head" in tree["embed"] else []))}
    if where == "embed":
        return {"table": tree["embed"]["table"]}
    return tree["layers"][where]


def _set_part(tree, where, part):
    if where == "head":
        tree["final_norm"] = part["final_norm"]
        if "head" in part:
            tree["embed"]["head"] = part["head"]
    elif where == "embed":
        tree["embed"]["table"] = part["table"]
    else:
        tree["layers"][where] = part


class Trainer:
    """The cell's data-parallel step on one reference model: c workers' rows
    in one batch (worker i holds the i-th of c equal blocks of rows), the
    mean loss over all of them, gradients at the stale weights where the mode
    is asynchronous, DC-ASGD's Taylor term where the strategy is dc_asgd,
    then the optimizer.

    The paper's guided correction (strategy guided_fused): a worker's step
    is consistent when its own loss and the average loss both fell since the
    step before; it then scores 1 plus `magnitude_weight` times its loss's
    relative fall (at most 1). Scores add up over a window of `rho` steps. In
    the window's last step, the `max_consistent` best-scored workers' losses
    join the objective, each weighted by its share of their summed scores
    (times `correction_scale`), so the step also re-applies their gradients;
    the scores then start again from zero."""

    STRATEGIES = ("none", "guided_fused", "dc_asgd")

    def __init__(self, cfg: dict, traffic: dict, seed: int, precision: str = "f32"):
        self.m = Dims(cfg)
        self.model = Model(self.m, precision)
        self.opt = traffic["optimizer"]
        if self.opt not in ("sgd", "adam"):
            raise ValueError(f"the reference has no optimizer {self.opt!r}")
        strategy = traffic["strategy"]
        if strategy not in self.STRATEGIES:
            raise ValueError(f"the reference has no strategy {strategy!r}")
        self.stale = traffic["mode"] == "asgd"
        self.lam = float(traffic["dc_lambda"]) if strategy == "dc_asgd" else 0.0
        self.rho = int(traffic["rho"])
        self.period = int(traffic.get("staleness", 0) or self.rho)
        self.c = c = int(traffic["workers"])
        self.guided = strategy == "guided_fused"
        if self.guided:
            self.max_consistent = int(traffic["max_consistent"])
            self.magnitude = float(traffic["magnitude_weight"])
            self.corr_scale = float(traffic["correction_scale"])
        self.score = np.zeros(c)
        self.prev_worker, self.prev_avg = np.full(c, np.inf), np.inf
        self.lr = np.float32(traffic["lr"]) * np.float32(c if traffic["mode"] != "seq" else 1)
        self._init = jax.jit(init, static_argnums=0)
        self._key = jax.random.PRNGKey(seed)
        self.w = self.initial_weights()
        copy = lambda t: jax.tree.map(jnp.copy, t)
        self.w_stale = copy(self.w) if self.stale else None
        zeros = lambda: jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), self.w)
        self.mom = zeros() if self.opt == "adam" else None
        self.vel = zeros() if self.opt == "adam" else None
        self.step = 0

    def _window_end(self) -> bool:
        return (self.step + 1) % self.rho == 0

    def correction_weights(self) -> np.ndarray:
        """(c,) weights of the workers' losses in this step's objective."""
        if not (self.guided and self._window_end()):
            return np.zeros(self.c)
        top = np.argsort(-self.score, kind="stable")[:min(self.max_consistent, self.c)]
        w = np.zeros(self.c)
        w[top] = self.score[top]
        total = w.sum()
        return self.corr_scale * w / total if total > 0 else np.zeros(self.c)

    def _advance_scores(self, worker: np.ndarray, avg: float) -> None:
        finite = np.isfinite(self.prev_worker) & np.isfinite(self.prev_avg)
        d_worker = worker - np.where(finite, self.prev_worker, 0.0)
        both = finite & (d_worker < 0) & (avg - self.prev_avg < 0)
        rel = np.clip(-d_worker / (np.abs(self.prev_worker) + 1e-8), 0.0, 1.0)
        self.score = self.score + np.where(both, 1.0 + self.magnitude * rel, 0.0)
        if self._window_end():
            self.score = np.zeros(self.c)
        self.prev_worker, self.prev_avg = worker, avg

    def train_step(self, batch) -> float:
        """One step; returns the mean loss at the weights the gradient is
        taken at."""
        at = self.w_stale if self.stale else self.w
        # the step reads `at` until each part's gradient is sunk; where `at`
        # is the live weights, the update of a part replaces it only then
        t = jnp.asarray(self.step + 1, jnp.int32)
        lam_ws = self.w_stale if self.lam else None

        def sink(where, g):
            w, m, v = _apply(_part(self.w, where), g, _part(lam_ws, where),
                             _part(self.mom, where), _part(self.vel, where), t,
                             self.lr, lam=self.lam, opt=self.opt)
            _set_part(self.w, where, w)
            if self.opt == "adam":
                _set_part(self.mom, where, m)
                _set_part(self.vel, where, v)

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

    def initial_weights(self):
        """The weights the trainer started from (made again: the same
        compiled program on the same key gives the same bits)."""
        return self._init(self.m, self._key)

    def optimizer_state(self) -> dict:
        """The optimizer's per-leaf state, by name ({} for SGD)."""
        return {} if self.opt == "sgd" else {"m": self.mom, "v": self.vel}
