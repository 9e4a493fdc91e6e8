"""The MiniCPM cell, `minicpm-2b.dcasgd-adam-c4`: it runs the configuration
with MiniCPM's numerics against `references/minicpm_lm.py`, its counts are
the program's, and its check fails faults planted underneath the timed path,
at a tiny size on the CPU."""
import os
import sys

import jax
import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import check  # noqa: E402
import counts  # noqa: E402
import run  # noqa: E402
from test_chipbench_harness import rehearsal_root, tiny  # noqa: E402

CELL = "minicpm-2b.dcasgd-adam-c4"
SEEDS = (2**31 + 11, 23)


def rehearse(seed, wrap=None, absent=None):
    """The tiny cell on the CPU, its program without the ModelConfig field
    `absent` where given: the result dict."""
    cell = tiny(run.Cell(CELL, root=rehearsal_root()))
    if absent is not None:
        cell.cfg["program"]["model_overrides"][absent] = None
    return run.measure(cell, seed, 0.5, False, {"platform": "cpu", "kind": "cpu", "count": 1},
                       wrap=wrap)


def test_cell_runs_minicpm_numerics_against_its_reference():
    """BENCHMARK.json's entry, not the test harness's copy, names the
    configuration: MiniCPM's published scalars, its own reference, 12 check
    steps (past the refresh of w_stale after step 10) and Adam's state_gap."""
    cell = run.Cell(CELL, root=rehearsal_root())
    assert cell.cfg["reference"] == "minicpm_lm"
    assert (cell.cfg["scale_emb"], cell.cfg["scale_depth"], cell.cfg["dim_model_base"]) == (12, 1.4, 256)
    assert cell.cfg["multipliers_at"]["num_hidden_layers"] == 40
    assert cell.check_steps == 12
    assert set(cell.limits) == {"loss_gap", "grad_gap", "change_gap", "last_gap", "state_gap"}


def test_minicpm_cell_counts():
    """10 x (21,233,664 attention + 39,813,120 ffn) + 48,384 norm + the tied
    282,822,912 table; 6 FLOP per matmul weight (layers and head) plus
    6 L d S for causal attention; 24 B per bf16 parameter for DC-ASGD + Adam
    (w, g, w_stale read, w written, f32 m and v read and written), 32 B per
    f32 norm scale. The counted parameters are the ones the program makes."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import numpy as np

    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.models.module import value_tree

    cell = run.Cell(CELL, root=rehearsal_root())
    p = counts.params(cell.cfg)
    assert p["total"] == 893_339_136
    assert counts.flops_per_token(cell.cfg, 1024) == 5_501_302_272
    assert counts.update_bytes(cell.cfg, cell.traffic) == 21_440_526_336
    prog = get_config(cell.cfg["program"]["arch"]).replace(
        **cell.cfg["program"]["model_overrides"])
    shapes = jax.eval_shape(lambda: value_tree(T.model_init(jax.random.PRNGKey(0), prog)))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) == p["total"]


@pytest.mark.parametrize("absent", ["scale_emb", "residual_scale", "logit_scale"])
def test_program_without_a_multiplier_is_not_correct(absent):
    """The program with one of MiniCPM's scalars left out (Llama's numerics
    for that part) against the reference, under the tiny rehearsal's limits."""
    out = rehearse(SEEDS[0], absent=absent)
    assert not out["correct"], out["check"]


def stale_frozen(dispatch):
    """w_stale handed back at its initial value after every dispatch: the
    refreshes inside a dispatch (after steps 0 and 10) last until its end."""
    kept = []

    def broken(params, gstate, block):
        if not kept:
            kept.append(jax.device_get(gstate.w_stale))
        params, gstate, metrics = dispatch(params, gstate, block)
        for a in jax.tree.leaves(gstate.w_stale):  # at the cell's size both copies do not fit
            a.delete()
        return params, gstate._replace(w_stale=jax.tree.map(jnp.asarray, kept[0])), metrics

    return broken


def test_stale_refresh_fault_is_not_correct():
    """The 12-step check sees the refresh of w_stale. At the tiny size one
    Adam step moves a weight by ~0.3% of its size (~2% at the published
    width), so the fault moves the readings by 2x to 10x and passes the tiny
    rehearsal's loose limits; under limits set as the cell's are, above the
    largest sound reading with room (here 3x, over the seeds), it fails. At
    the cell's size it fails the cell's own limits (PERF.md, section 2)."""
    sound = [rehearse(s) for s in SEEDS]
    faulty = [rehearse(s, wrap=stale_frozen) for s in SEEDS]
    nums = lambda out: {k: c["value"] for k, c in out["check"].items()}
    limits = {k: 3 * max(nums(o)[k] for o in sound) for k in nums(sound[0])}
    limits["compiles_in_window"] = 0
    for o in sound:
        assert check.judge(nums(o), limits)[0], (nums(o), limits)
    for o in faulty:
        assert not check.judge(nums(o), limits)[0], (nums(o), limits)
