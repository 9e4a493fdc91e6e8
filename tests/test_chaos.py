"""Chaos harness acceptance (`repro.chaos` + `make chaos`, DESIGN.md §14).

Every test runs a real 2-process live parameter-server fit under one seeded
`ChaosPlan` fault and asserts the run SELF-HEALS: it completes its full step
budget, trains to tolerance of the no-fault baseline, and `Report.dist`
records the remediation that did it (respawns, rejections, quarantines,
rollbacks, reset/bad-frame counts). Thresholds are store versions, not wall
times, and the store holds the other workers at each one until its fault has
landed, so every plan fires deterministically mid-run.

Kept deliberately small (18 server steps per run) so the whole module stays
well under the 90s chaos-gate budget on a loaded CI box.
"""
import numpy as np
import pytest

from repro.chaos import ChaosPlan, slow_disk, truncate_newest
from repro.dist import launcher
from repro.engine import ExperimentSpec

W0_LOSS = 0.6931  # ~ln 2: near-zero initial weights on a binary task


def _toy(n=120, d=5, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d))
    w = rng.standard_normal((d,))
    y = (X @ w > 0).astype(np.int64)
    return X, y, 2

# every planted fault lands by construction: from its version on, the store
# holds the other workers until it has landed and been remediated
# (ChaosPlan.holds); dist_time_scale only paces worker compute (~10ms/step)
COMMON = dict(backend="dist", dist_mode="live", mode="asgd", strategy="none",
              epochs=3, batch_size=16, rho=2, lr=0.2, seed=0, workers=2,
              dist_time_scale=0.01, dist_timeout=60.0)


def _run(spec, chaos=None):
    X, y, k = _toy()
    return launcher.run_local(spec, X, y, k, chaos=chaos)


def _assert_healed(res):
    """The common self-healing bar: full budget, genuinely trained."""
    assert res["n_steps"] == res["schedule"].n_steps > 0
    assert np.isfinite(res["val_loss"])
    assert res["val_loss"] < 0.8 * W0_LOSS


# ------------------------------------------------------------- the plan API


def test_chaos_plan_tables_and_worker_meta():
    plan = ChaosPlan(kills=((0, 6),), resets={1: 5},
                     nan_grad=((0, 4),), corrupt_frame=((1, 3),))
    assert plan.kill_events() == {0: 6}
    assert plan.reset_events() == ((1, 5),)
    assert plan.worker_meta() == {"nan_grad": {0: 4}, "corrupt_frame": {1: 3}}
    assert ChaosPlan().worker_meta() is None
    assert ChaosPlan().kill_events() == {}


def test_truncate_newest_on_empty_dir_is_none(tmp_path):
    assert truncate_newest(str(tmp_path)) is None


# -------------------------------------------------------- the fault matrix


def test_sigkill_mid_run_respawns_and_completes():
    res = _run(ExperimentSpec(**COMMON), ChaosPlan(kills=((0, 6),)))
    _assert_healed(res)
    assert res["dist"]["worker_exits"] >= 1          # the kill landed
    sup = res["dist"]["supervisor"]
    assert sup["respawns"] >= 1                      # ...and was healed
    assert sup["evicted"] == []


def test_connection_reset_recovers():
    # paced 3x slower than the other tests; the hold at the reset's version
    # keeps the respawn INSIDE the run whatever the pace
    spec = ExperimentSpec(**COMMON).replace(dist_time_scale=0.03)
    res = _run(spec, ChaosPlan(resets=((0, 4),)))
    _assert_healed(res)
    assert res["dist"]["resets"] == 1                # the chief dropped it
    assert res["dist"]["supervisor"]["respawns"] >= 1


def test_corrupt_frame_counted_and_tolerated():
    res = _run(ExperimentSpec(**COMMON), ChaosPlan(corrupt_frame=((1, 4),)))
    _assert_healed(res)
    assert res["dist"]["bad_frames"] >= 1            # dropped, not crashed


def test_nan_gradient_worker_screened_and_quarantined():
    spec = ExperimentSpec(**COMMON).replace(
        sentinel="finite", quarantine_steps=10_000, quarantine_after=2)
    res = _run(spec, ChaosPlan(nan_grad=((0, 4),)))
    _assert_healed(res)                              # worker 1 fills the budget
    d = res["dist"]
    assert d["rejection_reasons"].get("non-finite", 0) >= 2
    assert d["rejections"] >= 2
    assert d["quarantines"] >= 1
    # the poison NEVER reached W: rejections don't bump the version, and the
    # final weights are finite and trained
    assert np.all(np.isfinite(np.asarray(res["model"].W)))


def test_exploding_gradient_rolls_back_to_verified_checkpoint(tmp_path):
    spec = ExperimentSpec(**COMMON).replace(
        sentinel="finite", rollback=True, max_rollbacks=3, lr_backoff=0.5,
        quarantine_steps=10_000, quarantine_after=2,
        ckpt_dir=str(tmp_path), ckpt_every=2, keep_last=0)
    res = _run(spec, ChaosPlan(boom_grad=((0, 8),)))
    d = res["dist"]
    assert d["diverged"] >= 1                        # the detector tripped
    assert d["rollbacks"] >= 1                       # remediated, not fatal
    assert d["lr_scale"] < 1.0                       # lr backoff applied
    assert d["rollback_log"]
    assert d["rollback_log"][0][2] == "post-apply divergence"
    # healed: the full budget completes on finite, trained weights
    assert res["n_steps"] == res["schedule"].n_steps
    assert np.isfinite(res["val_loss"]) and res["val_loss"] < W0_LOSS
    assert np.all(np.isfinite(np.asarray(res["model"].W)))


def test_truncated_checkpoint_mid_run_and_fallback_restore(tmp_path):
    from repro.checkpoint import dist_restore

    d = str(tmp_path)
    spec = ExperimentSpec(**COMMON).replace(
        ckpt_dir=d, ckpt_every=3, keep_last=0)
    res = _run(spec, ChaosPlan(truncate_at=5))       # tear an archive mid-run
    _assert_healed(res)
    # the final snapshot is intact: restore lands on the final version
    snap = dist_restore(d)
    assert int(snap["version"]) == res["n_steps"]
    # now tear the NEWEST archive post-run: dist_restore verifies the
    # checksum, skips it, and falls back to the next intact manifest entry
    torn_step, _path = truncate_newest(d)
    assert torn_step == res["n_steps"]
    snap2 = dist_restore(d)
    assert int(snap2["version"]) < res["n_steps"]
    assert np.all(np.isfinite(snap2["W"]))


def test_slow_disk_writer_does_not_stall_training(tmp_path):
    from repro.checkpoint import read_manifest

    d = str(tmp_path)
    spec = ExperimentSpec(**COMMON).replace(ckpt_dir=d, ckpt_every=2)
    with slow_disk(0.05):
        res = _run(spec)
    _assert_healed(res)                              # async writer absorbed it
    man = read_manifest(d)
    assert man is not None and man["latest"] == res["n_steps"]


def test_compound_chaos_kill_plus_nan_worker():
    """Two faults at once: worker 0 goes NaN (quarantined), worker 1 is
    SIGKILLed (respawned) — the run still completes on the healed fleet."""
    spec = ExperimentSpec(**COMMON).replace(
        sentinel="finite", quarantine_steps=10_000, quarantine_after=2)
    res = _run(spec, ChaosPlan(nan_grad=((0, 4),), kills=((1, 8),)))
    _assert_healed(res)
    d = res["dist"]
    assert d["rejections"] >= 1
    assert d["supervisor"]["respawns"] >= 1
