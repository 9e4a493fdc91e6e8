"""Serving-engine tests (repro.serve, DESIGN.md §7):

  * token-for-token parity of the continuous engine vs. the lockstep loop for
    equal-length requests (seeded temperature AND top-k sampling — the two
    paths share the key-split protocol);
  * completion / slot-recycling with staggered prompt lengths, max-token
    limits and EOS;
  * per-slot position decode equals per-request sequential decode (pool of
    heterogeneous-depth requests vs. each request run alone);

Greedy decoding of the random-weight reduced MiniCPM repeats the last prompt
token (its embedding, times scale_emb, dominates the residual stream and the
tied head scores it highest), whatever the cache holds; and its logits are so
flat (the head's 256/2304 scale) that a seeded draw hardly depends on them.
So the stream comparisons draw with per-request seeded sampling, assert that
every compared stream varies, and compare the logits each token was drawn
from, found by the request's own key chain whatever slot or batch it ran in.
  * the sampling layer (greedy = temperature 0 = top-k 1 argmax; top-k draws
    stay inside the top-k set; determinism; parameter validation).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as T
from repro.models.module import split_params
from repro.serve import (
    Request,
    SamplingParams,
    ServeEngine,
    lockstep_generate,
    sample_tokens,
)
from repro.serve import engine as serve_engine

#: seeds of the compared requests' draws: clear of the all-zero key of an
#: empty pool slot (PRNGKey(0)) and of the chain it advances along
SEED0 = 100


@pytest.fixture(scope="module")
def dense():
    """Small dense arch: row-independent layers, padded-prefill eligible."""
    cfg = get_config("minicpm-2b").reduced()
    params = split_params(T.model_init(jax.random.PRNGKey(0), cfg))[0]
    return cfg, params


@pytest.fixture(scope="module")
def xlstm():
    """Recurrent arch: exercises the exact-length prefill path."""
    cfg = get_config("xlstm-350m").reduced()
    params = split_params(T.model_init(jax.random.PRNGKey(1), cfg))[0]
    return cfg, params


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (L,)).tolist() for L in lens]


def _by_id(comps):
    return {c.request_id: c for c in comps}


def _seeded(i, **kw):
    """Request i's own seeded draw at temperature 1 (`kw` overrides)."""
    return SamplingParams(**{"method": "temperature", "temperature": 1.0,
                             "seed": SEED0 + i, **kw})


def _varied(tokens):
    """A compared stream must not be one token repeated: a constant stream
    matches whether or not the cache and the slots are right."""
    assert len(set(tokens)) > 1, tokens


class _LogitsBySeededKey:
    """Records every row of logits the engine samples from under the key it
    draws with (prefill, admission and decode all sample through
    `serve.engine.sample_tokens`). A request's keys are its own chain from
    PRNGKey(seed), split once per token, so `rows(seed, n)` finds its n rows
    in whatever slot and batch they ran."""

    def __init__(self, monkeypatch):
        self.seen = {}
        sample = serve_engine.sample_tokens

        def recording(logits, keys, *a):
            jax.debug.callback(self._put, logits, keys)
            return sample(logits, keys, *a)

        monkeypatch.setattr(serve_engine, "sample_tokens", recording)

    def _put(self, logits, keys):
        for row, key in zip(np.asarray(logits), np.asarray(keys, np.uint32)):
            self.seen.setdefault(key.tobytes(), []).append(row)

    def take(self):
        """The rows recorded since the last take."""
        jax.effects_barrier()
        seen, self.seen = self.seen, {}
        return seen

    @staticmethod
    def rows(seen, seed, n):
        key, out = jax.random.PRNGKey(seed), []
        for _ in range(n):
            (row,) = seen[np.asarray(key, np.uint32).tobytes()]
            out.append(row)
            key = jax.random.split(key)[0]
        return np.stack(out)


def _same_logits(a, b):
    """Both sides compute each row in float32 from the same weights; only the
    batch they ran in differs, which moves a row by float32 rounding (at most
    1.4e-6 of its norm here). A request's cache in another slot, or another
    slot's length read for it, moves a row by 0.3 of its norm or more."""
    gap = np.linalg.norm(a - b, axis=-1) / np.linalg.norm(b, axis=-1)
    assert gap.max() < 1e-5, gap


# ------------------------------------------------- (a) lockstep parity


@pytest.mark.parametrize("sampling", [
    {},  # temperature 1
    {"method": "topk", "top_k": 20, "temperature": 0.8},
])
def test_continuous_matches_lockstep_equal_lengths(dense, sampling, monkeypatch):
    """With equal prompt lengths the barriered loop has no padding flaw, so
    the continuous engine must reproduce it token for token — stochastic
    sampling included, which shares the per-request key-split protocol. (The
    sampler's greedy branch: test_sampling_greedy_paths_agree.)"""
    cfg, params = dense
    prompts = _prompts(cfg, [12, 12, 12, 12])

    def reqs():
        return [Request(list(p), max_new_tokens=6, sampling=_seeded(i, **sampling),
                        request_id=i)
                for i, p in enumerate(prompts)]

    rec = _LogitsBySeededKey(monkeypatch)
    engine = ServeEngine(params, cfg, max_batch=4, max_len=32)
    cont = _by_id(engine.run(reqs()))
    cont_logits = rec.take()
    lock = _by_id(lockstep_generate(engine, reqs())[0])
    lock_logits = rec.take()
    assert set(cont) == set(lock) == {0, 1, 2, 3}
    for i in cont:
        _varied(cont[i].tokens)
        assert cont[i].tokens == lock[i].tokens, i
        _same_logits(rec.rows(cont_logits, SEED0 + i, 6), rec.rows(lock_logits, SEED0 + i, 6))


# --------------------------------- (b) staggered completion / recycling


def test_slot_recycling_staggered_lengths(dense):
    cfg, params = dense
    lens = [5, 9, 12, 7, 16, 3]
    gens = [6, 4, 8, 3, 5, 7]
    reqs = [Request(p, max_new_tokens=g, request_id=i)
            for i, (p, g) in enumerate(zip(_prompts(cfg, lens), gens))]
    engine = ServeEngine(params, cfg, max_batch=2, max_len=32)
    comps = engine.run(reqs)

    assert len(comps) == len(reqs)
    by_id = _by_id(comps)
    for i, g in enumerate(gens):
        assert by_id[i].finish_reason == "length"
        assert by_id[i].new_tokens == g
        assert by_id[i].prompt_len == lens[i]
    # 6 requests through 2 slots: both slots recycled
    slots = [c.slot for c in comps]
    assert set(slots) <= {0, 1}
    assert min(slots.count(0), slots.count(1)) >= 2
    st = engine.stats()
    assert st["n_completed"] == 6
    assert st["new_tokens"] == sum(gens)
    assert 0 < st["occupancy"] <= 1
    assert not engine.has_work


def test_eos_frees_slot_early(dense):
    cfg, params = dense
    (prompt,) = _prompts(cfg, [10])
    sampling = _seeded(0)
    engine = ServeEngine(params, cfg, max_batch=1, max_len=64)
    (full,) = engine.run([Request(list(prompt), max_new_tokens=8, sampling=sampling)])
    assert full.finish_reason == "length"
    # rerun with EOS set to the 4th generated token: must stop there
    eos = full.tokens[3]
    assert eos not in full.tokens[:3], full.tokens
    engine2 = ServeEngine(params, cfg, max_batch=1, max_len=64, eos_id=eos)
    (cut,) = engine2.run([Request(list(prompt), max_new_tokens=8, sampling=sampling)])
    assert cut.finish_reason == "eos"
    assert cut.tokens == full.tokens[:4]


def test_streaming_callback_matches_completion(xlstm):
    cfg, params = xlstm
    streams = {}
    reqs = [Request(p, max_new_tokens=4, request_id=i,
                    on_token=lambda rid, tok: streams.setdefault(rid, []).append(tok))
            for i, p in enumerate(_prompts(cfg, [6, 11, 8]))]
    engine = ServeEngine(params, cfg, max_batch=2, max_len=32)
    comps = engine.run(reqs)
    assert len(comps) == 3
    for c in comps:
        assert streams[c.request_id] == c.tokens


# -------------------------- (c) per-slot decode == sequential decode


@pytest.mark.parametrize("arch_fixture", ["dense", "xlstm"])
def test_per_slot_decode_matches_sequential(request, arch_fixture, monkeypatch):
    """A pool of requests at heterogeneous depths (per-slot position vector)
    must produce exactly the tokens each request gets when decoded alone
    (pool of 1): cross-slot isolation of the batched decode."""
    cfg, params = request.getfixturevalue(arch_fixture)
    lens = [5, 9, 12, 7, 16]
    gens = [6, 4, 8, 3, 5]
    reqs = [Request(p, max_new_tokens=g, sampling=_seeded(i), request_id=i)
            for i, (p, g) in enumerate(zip(_prompts(cfg, lens), gens))]
    rec = _LogitsBySeededKey(monkeypatch)
    pool = ServeEngine(params, cfg, max_batch=3, max_len=32)
    pooled = _by_id(pool.run(reqs))
    pooled_logits = rec.take()

    solo_engine = ServeEngine(params, cfg, max_batch=1, max_len=32)
    for i, (p, g) in enumerate(zip(_prompts(cfg, lens), gens)):
        (solo,) = solo_engine.run([Request(p, max_new_tokens=g, sampling=_seeded(i),
                                           request_id=i)])
        _varied(pooled[i].tokens)
        assert pooled[i].tokens == solo.tokens, i
        _same_logits(rec.rows(pooled_logits, SEED0 + i, g), rec.rows(rec.take(), SEED0 + i, g))


def test_decode_step_accepts_scalar_and_vector_t(dense):
    """Back-compat: scalar t must equal a constant (B,) position vector."""
    cfg, params = dense
    B, L = 2, 8
    toks = np.asarray(_prompts(cfg, [L, L], seed=3), np.int32)
    _, caches = T.prefill(params, {"tokens": jnp.asarray(toks)}, cfg, total_len=16)
    nxt = jnp.asarray([[1], [2]], jnp.int32)
    lo_s, c_s = T.decode_step(params, caches, nxt, jnp.asarray(L, jnp.int32), cfg)
    lo_v, c_v = T.decode_step(params, caches, nxt, jnp.full((B,), L, jnp.int32), cfg)
    np.testing.assert_array_equal(np.asarray(lo_s), np.asarray(lo_v))
    for a, b in zip(jax.tree.leaves(c_s), jax.tree.leaves(c_v)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------- sampling layer


def test_sampling_greedy_paths_agree():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.standard_normal((3, 64)), jnp.float32)
    keys = jnp.asarray(np.stack([np.asarray(jax.random.PRNGKey(s)) for s in range(3)]),
                       jnp.uint32)
    argmax = np.argmax(np.asarray(logits), axis=-1)
    # temperature 0 (greedy), and top_k=1 at temperature 1: both == argmax
    t0, _ = sample_tokens(logits, keys, jnp.zeros((3,)), jnp.zeros((3,), jnp.int32))
    k1, _ = sample_tokens(logits, keys, jnp.ones((3,)), jnp.ones((3,), jnp.int32))
    np.testing.assert_array_equal(np.asarray(t0), argmax)
    np.testing.assert_array_equal(np.asarray(k1), argmax)


def test_sampling_topk_stays_in_topk_and_is_deterministic():
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.standard_normal((4, 128)), jnp.float32)
    keys = jnp.asarray(np.stack([np.asarray(jax.random.PRNGKey(s)) for s in range(4)]),
                       jnp.uint32)
    temp = jnp.full((4,), 1.3)
    topk = jnp.full((4,), 5, jnp.int32)
    tok_a, keys_a = sample_tokens(logits, keys, temp, topk)
    tok_b, keys_b = sample_tokens(logits, keys, temp, topk)
    np.testing.assert_array_equal(np.asarray(tok_a), np.asarray(tok_b))
    np.testing.assert_array_equal(np.asarray(keys_a), np.asarray(keys_b))
    top5 = np.argsort(np.asarray(logits), axis=-1)[:, -5:]
    for i, t in enumerate(np.asarray(tok_a)):
        assert t in top5[i]
    # the returned keys advance the chain: they differ from the inputs
    assert not np.array_equal(np.asarray(keys_a), np.asarray(keys))


def test_sampling_params_validation():
    with pytest.raises(ValueError, match="method"):
        SamplingParams(method="nucleus")
    with pytest.raises(ValueError, match="top_k"):
        SamplingParams(method="topk", top_k=0)
    with pytest.raises(ValueError, match="temperature"):
        SamplingParams(temperature=-0.1)
    assert SamplingParams().eff_temperature == 0.0
    assert SamplingParams(method="temperature", temperature=0.7).eff_temperature == 0.7
    assert SamplingParams(method="temperature", top_k=9).eff_top_k == 0


# ------------------------------------------------------- engine guards


def test_engine_rejects_bad_requests(dense):
    cfg, params = dense
    engine = ServeEngine(params, cfg, max_batch=1, max_len=16)
    with pytest.raises(ValueError, match="max_len"):
        engine.submit(Request(list(range(10)), max_new_tokens=10))
    with pytest.raises(ValueError, match="prompt"):
        engine.submit(Request([], max_new_tokens=2))
    with pytest.raises(ValueError, match="max_new_tokens"):
        engine.submit(Request([1, 2], max_new_tokens=0))


def test_engine_rejects_encoder_only():
    cfg = get_config("hubert-xlarge").reduced()
    with pytest.raises(ValueError, match="encoder-only"):
        ServeEngine({}, cfg, max_batch=1, max_len=16)


def test_vlm_patches_reach_the_prompt_and_are_validated(dense):
    cfg = get_config("llava-next-mistral-7b").reduced()
    params = split_params(T.model_init(jax.random.PRNGKey(2), cfg))[0]
    engine = ServeEngine(params, cfg, max_batch=1, max_len=48)
    rng = np.random.default_rng(5)
    P = cfg.n_patches
    prompt = rng.integers(0, cfg.vocab_size, (P + 6,)).tolist()
    patches_a = rng.standard_normal((P, cfg.d_model)).astype(np.float32)
    patches_b = rng.standard_normal((P, cfg.d_model)).astype(np.float32)
    (a,) = engine.run([Request(list(prompt), max_new_tokens=5, patches=patches_a)])
    (b,) = engine.run([Request(list(prompt), max_new_tokens=5, patches=patches_b)])
    assert a.tokens != b.tokens  # the spliced embeddings steer the stream
    with pytest.raises(ValueError, match="splice"):  # prompt shorter than patches
        engine.submit(Request(list(prompt[:P]), max_new_tokens=2, patches=patches_a))
    dense_cfg, dense_params = dense
    with pytest.raises(ValueError, match="vlm"):  # patches on a non-vlm arch
        ServeEngine(dense_params, dense_cfg, max_batch=1, max_len=48).submit(
            Request(list(prompt), max_new_tokens=2, patches=patches_a))
    with pytest.raises(ValueError, match="token-only"):  # lockstep can't take them
        lockstep_generate(engine, [Request(list(prompt), max_new_tokens=2,
                                           patches=patches_a)])
