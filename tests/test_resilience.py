"""The self-healing layer's units (repro.resilience, DESIGN.md §14).

GradScreen / DivergenceDetector / SentinelPolicy vet gradients and
trajectories deterministically; wrap_step_sentinel fuses the same screen
into a jitted mesh step without touching an accepted trajectory; Supervisor
+ LeaseTable implement the RUNNING -> DOWN -> RESPAWNED/EVICTED machine with
capped jittered backoff (driven here via poll(now=...), no wall clock); the
spec validates every resilience knob at construction; and the chief's
close() names wedged connection threads instead of leaking them. End-to-end
fault runs live in tests/test_chaos.py.
"""
import threading
import time

import numpy as np
import pytest

from repro.engine import ExperimentSpec
from repro.resilience import (
    DivergenceDetector,
    GradScreen,
    LeaseTable,
    SentinelPolicy,
    Supervisor,
    wrap_step_sentinel,
)
from repro.resilience.sentinel import NORM_WARMUP


def _policy(**kw):
    base = dict(level="full", factor=10.0, quarantine_steps=100,
                quarantine_after=2)
    base.update(kw)
    return SentinelPolicy(**base)


# -------------------------------------------------------------- GradScreen


def test_screen_accepts_finite_gradients():
    s = GradScreen(_policy())
    for v in range(5):
        assert s.admit(0, np.ones(3) * (v + 1), v) is None
    c = s.counters()
    assert c["rejections"] == 0 and c["quarantines"] == 0


def test_screen_rejects_non_finite_and_counts_reason():
    s = GradScreen(_policy(quarantine_after=99))
    assert s.admit(0, np.array([1.0, np.nan]), 0) == "non-finite"
    assert s.admit(1, np.array([np.inf, 0.0]), 1) == "non-finite"
    c = s.counters()
    assert c["rejections"] == 2
    assert c["rejections_by_worker"] == {0: 1, 1: 1}
    assert c["rejection_reasons"] == {"non-finite": 2}


def test_consecutive_rejections_quarantine_the_worker():
    s = GradScreen(_policy(quarantine_after=2, quarantine_steps=50))
    s.admit(0, np.array([np.nan]), 0)
    s.admit(0, np.array([np.nan]), 1)          # second in a row -> quarantine
    assert s.counters()["quarantines"] == 1
    assert s.admit(0, np.ones(1), 5) == "quarantined"   # even a sane push
    assert s.admit(0, np.ones(1), 1 + 50) is None       # ban lifts by version
    # an accept in between resets the streak: no quarantine
    s2 = GradScreen(_policy(quarantine_after=2, quarantine_steps=50))
    s2.admit(1, np.array([np.nan]), 0)
    s2.admit(1, np.ones(1), 1)
    s2.admit(1, np.array([np.nan]), 2)
    assert s2.counters()["quarantines"] == 0


def test_norm_screen_trips_only_after_warmup_and_only_at_full():
    s = GradScreen(_policy(level="full", factor=10.0))
    g = np.ones(4)                              # norm 2.0
    for v in range(NORM_WARMUP):
        assert s.admit(0, g, v) is None
    assert s.admit(0, g * 1e6, NORM_WARMUP) == "norm-exploded"
    assert s.admit(0, g * 1.5, NORM_WARMUP + 1) is None  # near the EMA: fine
    # level "finite" has no norm screen: the same explosion sails through
    s2 = GradScreen(_policy(level="finite"))
    for v in range(NORM_WARMUP + 1):
        assert s2.admit(0, g, v) is None
    assert s2.admit(0, g * 1e6, NORM_WARMUP + 2) is None


def test_quarantine_steps_zero_never_bans():
    s = GradScreen(_policy(quarantine_after=1, quarantine_steps=0))
    s.admit(0, np.array([np.nan]), 0)
    assert s.counters()["quarantines"] == 0
    assert s.admit(0, np.ones(1), 1) is None


# ------------------------------------------------------ DivergenceDetector


def test_detector_trips_on_non_finite_and_spikes():
    d = DivergenceDetector(factor=10.0)
    assert not d.update(0.7)
    assert not d.update(0.5)                 # best tracks the minimum
    assert not d.update(4.9)                 # < 10 x 0.5: tolerated wobble
    assert d.update(5.1)                     # > 10 x best: diverged
    assert d.update(float("nan"))
    assert d.update(float("inf"))
    assert d.best == 0.5                     # a diverged sample never updates best


def test_policy_from_spec_round_trips_the_knobs():
    spec = ExperimentSpec(backend="dist", dist_mode="live", mode="asgd",
                          sentinel="full", sentinel_factor=7.0, rollback=True,
                          max_rollbacks=2, lr_backoff=0.25,
                          quarantine_steps=40, quarantine_after=4)
    p = SentinelPolicy.from_spec(spec)
    assert (p.level, p.factor, p.rollback) == ("full", 7.0, True)
    assert (p.max_rollbacks, p.lr_backoff) == (2, 0.25)
    assert (p.quarantine_steps, p.quarantine_after) == (40, 4)
    assert p.screening and p.norm_screen
    assert not SentinelPolicy(level="").screening
    assert not SentinelPolicy(level="finite").norm_screen


# ------------------------------------------------------ wrap_step_sentinel


def test_mesh_sentinel_keeps_the_previous_carry_on_a_bad_step():
    import jax.numpy as jnp

    def step(params, gstate, batch):
        return params + 1.0, gstate + 1.0, {"loss": batch.sum()}

    guarded = wrap_step_sentinel(step, "finite", 10.0)
    p, g, m = guarded(jnp.zeros(2), jnp.zeros(1), jnp.array([1.0]))
    assert int(m["rejected"]) == 0
    np.testing.assert_array_equal(np.asarray(p), 1.0)
    p2, g2, m2 = guarded(p, g, jnp.array([jnp.nan]))   # NaN loss -> rejected
    assert int(m2["rejected"]) == 1
    np.testing.assert_array_equal(np.asarray(p2), np.asarray(p))
    np.testing.assert_array_equal(np.asarray(g2), np.asarray(g))


def test_mesh_sentinel_full_rejects_spikes_and_bad_leaves():
    import collections

    import jax.numpy as jnp

    GS = collections.namedtuple("GS", ["prev_avg_loss", "x"])

    def step(params, gstate, batch):
        return params * batch, gstate._replace(x=gstate.x + 1), \
            {"loss": jnp.abs(batch.sum())}

    guarded = wrap_step_sentinel(step, "full", 10.0)
    gs = GS(prev_avg_loss=jnp.float32(1.0), x=jnp.zeros(1))
    # loss 100 > 10 x prev_avg_loss 1.0 -> spike rejection
    p, g, m = guarded(jnp.ones(2), gs, jnp.array([50.0, 50.0]))
    assert int(m["rejected"]) == 1
    np.testing.assert_array_equal(np.asarray(p), 1.0)
    # sane loss but a non-finite updated leaf -> rejected at "full"
    p, g, m = guarded(jnp.array([1.0, np.inf]), gs, jnp.array([2.0, 0.0]))
    assert int(m["rejected"]) == 1
    # inf prev_avg_loss (the GuidedState init) passes the first sane steps
    gs0 = GS(prev_avg_loss=jnp.float32(np.inf), x=jnp.zeros(1))
    p, g, m = guarded(jnp.ones(2), gs0, jnp.array([2.0, 0.0]))
    assert int(m["rejected"]) == 0
    np.testing.assert_array_equal(np.asarray(p), np.asarray([2.0, 0.0]))


TINY = (("n_layers", 1), ("d_model", 16), ("d_ff", 32), ("vocab_size", 128),
        ("n_heads", 2), ("n_kv_heads", 2))


def _mesh_spec(**kw):
    base = dict(backend="mesh", arch="yi_9b", reduced=True, mode="ssgd",
                strategy="guided_fused", rho=3, staleness=2, lr=5e-2, seed=0,
                steps=6, seq_len=8, global_batch=4, workers=2,
                model_overrides=TINY)
    base.update(kw)
    return ExperimentSpec(**base)


def test_mesh_sentinel_is_bit_exact_on_a_clean_run():
    """Arming the sentinel must not perturb a healthy trajectory: jnp.where
    with an all-true keep is the identity, leaf for leaf."""
    import jax

    from repro.engine import Trainer

    off = Trainer.from_spec(_mesh_spec()).fit()
    on = Trainer.from_spec(_mesh_spec(sentinel="finite")).fit()
    for a, b in zip(jax.tree.leaves(off.model), jax.tree.leaves(on.model)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert on.resilience == {"sentinel": "finite", "rejected_steps": 0}
    assert off.resilience == {}


def test_mesh_sentinel_full_keeps_params_finite_through_divergence():
    """lr=5000 on the tiny LM blows up within a few steps; at level 'full'
    every poisoning step is rejected on device (previous carry re-threaded),
    so the final params stay finite — identically under chunked dispatch."""
    import jax

    from repro.engine import Trainer

    diverging = _mesh_spec(lr=5000.0, steps=10, sentinel="full")
    r = Trainer.from_spec(diverging).fit()
    assert r.resilience["rejected_steps"] >= 1
    assert all(np.all(np.isfinite(np.asarray(x)))
               for x in jax.tree.leaves(r.model))
    r2 = Trainer.from_spec(diverging.replace(chunk_steps=4)).fit()
    assert r2.resilience["rejected_steps"] == r.resilience["rejected_steps"]
    for a, b in zip(jax.tree.leaves(r.model), jax.tree.leaves(r2.model)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------- Supervisor + LeaseTable


def test_lease_table_expiry_and_touch():
    lt = LeaseTable(0.5)
    now = time.monotonic()
    assert not lt.expired(0, now)            # never seen: may be connecting
    lt.touch(0)
    assert not lt.expired(0, time.monotonic())
    assert lt.expired(0, time.monotonic() + 1.0)
    t0 = time.monotonic() - 10.0
    assert lt.touched_since(0, t0)
    assert not lt.touched_since(1, t0)
    lt.drop(0)
    assert not lt.expired(0, time.monotonic() + 1.0)
    assert not LeaseTable(0.0).expired(0, now)   # leases off: never expired


class _FakeProc:
    def __init__(self, wid):
        self.wid = wid
        self.dead = False
        self.kills = 0

    def alive(self):
        return not self.dead

    def kill(self):
        self.dead = True
        self.kills += 1

    def cleanup(self):
        pass

    def stderr_tail(self, n=5):
        return ""


def _sup(**kw):
    spawned = []

    def spawn(wid):
        p = _FakeProc(wid)
        spawned.append(p)
        return p

    kw.setdefault("n_workers", 1)
    kw.setdefault("max_respawns", 2)
    sup = Supervisor(spawn, **kw)
    sup.start()
    sup.stop_polling()   # drive poll(now=...) by hand: deterministic clock
    return sup, spawned


def test_supervisor_respawns_after_backoff_and_records_recovery():
    sup, spawned = _sup()
    spawned[0].dead = True
    t = 100.0
    sup.poll(now=t)                          # death detected, backoff starts
    assert len(spawned) == 1                 # not yet: backoff not elapsed
    sup.poll(now=t)
    assert len(spawned) == 1
    sup.poll(now=t + 10.0)                   # way past any backoff
    assert len(spawned) == 2
    assert sup.stats()["respawns"] == 1
    sup.poll(now=t + 11.0)                   # replacement alive, no leases ->
    st = sup.stats()                         # healthy immediately
    assert len(st["recoveries"]) == 1
    assert st["recoveries"][0][0] == 0
    sup.close()


def test_supervisor_evicts_after_respawn_budget():
    sup, spawned = _sup(max_respawns=0)
    spawned[0].dead = True
    sup.poll(now=50.0)                       # streak 1 > budget 0: evicted
    sup.poll(now=500.0)
    assert len(spawned) == 1                 # never respawned
    assert sup.stats()["evicted"] == [0]
    sup.close()


def test_supervisor_backoff_is_capped_and_jittered():
    sup, _ = _sup(backoff_base=0.05, backoff_cap=1.0)
    b1 = sup._backoff(1)
    assert 0.05 <= b1 <= 0.10                # base x (1..2) full jitter
    assert sup._backoff(20) <= 2.0           # capped at cap x 2
    assert sup._backoff(3) >= sup._backoff(1) / 2   # grows (modulo jitter)
    sup.close()


def test_supervisor_lease_expiry_converts_hang_to_death():
    lt = LeaseTable(0.5)
    sup, spawned = _sup(leases=lt)
    lt.touch(0)
    sup.poll(now=time.monotonic())           # fresh lease: healthy
    assert spawned[0].kills == 0
    sup.poll(now=time.monotonic() + 5.0)     # expired: hung -> killed
    assert spawned[0].kills == 1
    assert sup.stats()["lease_expiries"] == 1
    sup.close()


def test_respawn_now_is_an_injected_op_not_a_failure():
    sup, spawned = _sup()
    sup.respawn_now(0)
    assert len(spawned) == 2 and spawned[0].dead
    st = sup.stats()
    assert st["respawns"] == 1 and st["evicted"] == []
    sup.poll(now=1e9)                        # no pending down/heal state
    assert len(spawned) == 2
    sup.close()


def test_supervisor_close_kills_the_fleet():
    sup, spawned = _sup(n_workers=2)
    sup.spawn_extra()
    sup.close()
    assert all(p.dead for p in spawned)
    assert len(sup.procs()) == 3


# ------------------------------------------------------- spec validation


def test_spec_rejects_bad_resilience_knobs():
    live = dict(backend="dist", dist_mode="live", mode="asgd")
    with pytest.raises(ValueError, match="unknown sentinel"):
        ExperimentSpec(sentinel="paranoid", **live)
    with pytest.raises(ValueError, match="sentinel_factor"):
        ExperimentSpec(sentinel="finite", sentinel_factor=1.0, **live)
    with pytest.raises(ValueError, match="neither"):
        ExperimentSpec(backend="scan", sentinel="finite")
    with pytest.raises(ValueError, match="replay"):
        ExperimentSpec(backend="dist", dist_mode="replay", sentinel="finite")
    with pytest.raises(ValueError, match="rollback / quarantine"):
        ExperimentSpec(backend="mesh", sentinel="finite", rollback=True)
    with pytest.raises(ValueError, match="need a sentinel"):
        ExperimentSpec(rollback=True, **live)
    with pytest.raises(ValueError, match="quarantine_after"):
        ExperimentSpec(sentinel="finite", quarantine_after=0, **live)
    with pytest.raises(ValueError, match="lr_backoff"):
        ExperimentSpec(sentinel="finite", rollback=True, lr_backoff=0.0, **live)
    with pytest.raises(ValueError, match="dist_lease_s"):
        ExperimentSpec(dist_lease_s=-1.0, **live)
    # the happy path constructs
    ExperimentSpec(sentinel="full", rollback=True, quarantine_steps=10, **live)


# ------------------------------- chief close() leak report + connect backoff


class _StubStore:
    """Just enough ParameterStore surface for a Chief serving no real run."""

    W = np.zeros(3)

    def __init__(self):
        self.exits = 0
        self.bad = 0

    def record_worker_exit(self):
        self.exits += 1

    def record_bad_frame(self, wid, exc):
        self.bad += 1

    def record_join(self):
        pass

    def record_hello(self, wid):
        pass

    def progress(self):
        return 0


def test_chief_close_names_wedged_connection_threads():
    from repro.dist import protocol
    from repro.dist.chief import Chief

    store = _StubStore()
    chief = Chief(store, {"n_workers": 1})
    conn = protocol.connect(chief.address)
    conn.send(("hello", 0))
    assert conn.recv()[0] == "welcome"
    # the worker now sits silent: its connection thread is parked in recv()
    with pytest.warns(RuntimeWarning, match="leaked 1 unjoined"):
        chief.close(timeout=0.3)
    assert chief.leaked_threads == ["dist-chief-conn"]
    with pytest.raises(RuntimeError, match="leaked"):
        chief.close(timeout=0.2, strict=True)
    conn.close()             # unwedge: the thread exits via EOF
    for _ in range(100):
        if store.exits == 1 and not any(
                t.name == "dist-chief-conn" for t in threading.enumerate()):
            break
        time.sleep(0.02)
    assert store.exits == 1


def test_chief_close_is_clean_after_bye():
    from repro.dist import protocol
    from repro.dist.chief import Chief

    chief = Chief(_StubStore(), {"n_workers": 1})
    conn = protocol.connect(chief.address)
    conn.send(("hello", 0))
    conn.recv()
    conn.send(("bye", 0))
    conn.close()
    chief.close(timeout=5.0, strict=True)    # strict: a leak would raise
    assert chief.leaked_threads == []


def test_connect_backoff_reports_attempts_and_elapsed():
    import socket

    from repro.dist import protocol

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()                                # nothing listens here any more
    t0 = time.monotonic()
    with pytest.raises(ConnectionError, match=r"attempts over .*s \(last"):
        protocol.connect(("127.0.0.1", port), timeout=0.4)
    assert time.monotonic() - t0 >= 0.35     # it really retried to deadline
