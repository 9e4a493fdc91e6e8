"""The check fails what it must: the control (the reference in float8 put
in the program's place) and faults planted underneath the timed path, at a
tiny size on the CPU under the tiny rehearsal's limits."""
import os
import sys

import jax
import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import check  # noqa: E402
import control  # noqa: E402
import run  # noqa: E402
from test_chipbench_harness import ONE_CHIP, TINY_LIMITS, rehearse, rehearsal_root, tiny  # noqa: E402


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_control_and_reference_faults_fail(workload):
    cell = tiny(run.Cell(workload, root=rehearsal_root()))
    got = control.readings(cell, seed=17)
    kinds = {"control_fp8", "half_batch", "state_unchanged"}
    kinds |= {"no_correction"} if cell.traffic["strategy"] == "guided_fused" else set()
    kinds |= {"dc_lambda_zero"} if cell.traffic["strategy"] == "dc_asgd" else set()
    assert set(got) == kinds
    for kind in kinds - {"dc_lambda_zero"}:
        ok, rows = check.judge(got[kind], cell.limits)
        assert not ok, (kind, rows)


def state_unchanged(dispatch):
    """A step that returns the state it was given."""
    def broken(params, gstate, block):
        keep = jax.tree.map(jnp.copy, (params, gstate))
        _, _, metrics = dispatch(params, gstate, block)
        return (*keep, metrics)

    return broken


def half_batch(dispatch):
    """Half of every batch left out: its rows replaced by the other half's,
    so the mean runs over the rest."""
    def broken(params, gstate, block):
        h = block["tokens"].shape[1] // 2
        block = {k: v.at[:, h:].set(v[:, :h]) for k, v in block.items()}
        return dispatch(params, gstate, block)

    return broken


@pytest.mark.parametrize("fault", [state_unchanged, half_batch], ids=lambda f: f.__name__)
@pytest.mark.parametrize("workload", ONE_CHIP)
def test_planted_fault_is_not_correct(workload, fault):
    out = rehearse(workload, seed=23, wrap=fault)
    assert not out["correct"], out["check"]
    assert TINY_LIMITS  # the same limits under which sound runs pass
