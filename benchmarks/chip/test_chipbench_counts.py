"""Operation and byte counts, the peaks table, and the benchmark's refusal to
run without a chip."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import counts  # noqa: E402


def config(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def traffic(name):
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def test_yi_9b_six_layers_counts():
    """The hand-worked counts of yi-9b's widths at 6 layers."""
    cfg = dict(config("yi-9b"), num_hidden_layers=6)
    p = counts.params(cfg)
    # 6 x (37,748,736 attention + 135,266,304 ffn) + 53,248 norm + 2 x 262,144,000
    assert p["total"] == 1_562_431_488  # 1.562 B
    assert p["matmul"] == 1_300_234_240  # 1.300 B: layers and head, no lookup
    assert counts.flops_per_token(cfg, 1024) == pytest.approx(7.95e9, rel=2e-3)


def test_yi_9b_cell_counts():
    """The cell's 13 layers: 13 x 173,015,040 layer matrices + 110,592 norm
    + 2 x 262,144,000 embedding and head; 6 FLOP per matmul weight plus
    6 L d S for causal attention at S = 1024."""
    cfg = config("yi-9b")
    p = counts.params(cfg)
    assert p["total"] == 2_773_594_112
    assert p["matmul"] == 2_511_339_520
    assert counts.flops_per_token(cfg, 1024) == 6 * 2_511_339_520 + 6 * 13 * 4096 * 1024


def test_counts_match_the_program_model():
    """The counted parameters are the ones the program makes."""
    import jax
    import numpy as np

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.models.module import value_tree

    for name in ("yi-9b", "minicpm-2b"):
        cfg = config(name)
        prog = get_config(cfg["program"]["arch"]).replace(**cfg["program"]["model_overrides"])
        shapes = jax.eval_shape(lambda: value_tree(T.model_init(jax.random.PRNGKey(0), prog)))
        n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
        assert n == counts.params(cfg)["total"], name


def test_update_bytes_per_parameter():
    """What the update needs per bf16 parameter: SGD with lambda = 0 reads w
    and g and writes w (6 B); DC-ASGD adds a read of w_stale; Adam adds m and
    v in f32, read and written (24 B with lambda != 0). Norm scales are f32."""
    cfg = config("yi-9b")
    p = counts.params(cfg)
    sgd = traffic("gssgd-c4")
    assert counts.update_bytes(cfg, sgd) == 6 * p["matrix"] + 12 * p["norm"]
    dc_adam = traffic("dcasgd-adam-c4")
    assert counts.update_bytes(cfg, dc_adam) == 24 * p["matrix"] + 32 * p["norm"]
    assert counts.update_bytes(cfg, dict(dc_adam, dc_lambda=0.0)) == \
        22 * p["matrix"] + 28 * p["norm"]


def test_peaks_known_and_unknown_device():
    v5e = counts.peaks("TPU v5 lite")
    assert v5e == {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
    with pytest.raises(KeyError, match="no peaks"):
        counts.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("workload", ["yi-9b.gssgd-c4", "minicpm-2b.dcasgd-adam-c4"])
def test_no_tpu_exits_nonzero_without_result(workload):
    from test_chipbench_harness import rehearsal_root

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run_py = os.path.join(rehearsal_root(), "benchmarks", "chip", "run.py")
    p = subprocess.run([sys.executable, run_py, "--workload", workload,
                        "--seed", "3000000000", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
