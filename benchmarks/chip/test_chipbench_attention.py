"""The attention_ms reader: device time of the splash attention kernels, on a
hand-made timeline and on the committed v5e trace, which holds none."""
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import xplane  # noqa: E402
from metrics.guided_update_ms import KERNEL  # noqa: E402
from test_chipbench_trace import SMALL, _trace, read  # noqa: E402

#: a splash kernel's custom call as the v5e compiler names it (outputs and
#: operands of `splash_mqa_fwd_residuals` at yi-9b's training shapes; the
#: mask tables, q, k, v and the query positions)
SPLASH = ("%splash_mqa_fwd_residuals.{n} = (f32[8,4,512,128]{{3,2,1,0:T(8,128)}}, "
          "bf16[8,4,8,1024,128]{{4,3,2,1,0:T(8,128)(2,1)}}, f32[8,4,8,1024,128]{{4,3,2,1,0:T(8,128)}}) "
          "custom-call(%copy-done.1, %copy-done, %bitcast.10, %copy_bitcast_fusion.1, "
          "%copy_bitcast_fusion, /*index=5*/%iota.1), custom_call_target=\"tpu_custom_call\"")


def test_attention_ms_reads_the_splash_kernels_only():
    ops = [(SPLASH.format(n=1), 0, 30), ("%fusion.2 = f32[4] fusion(...)", 30, 60),
           (SPLASH.format(n=3).replace("fwd_residuals", "dkv_no_residuals"), 60, 110)]
    run = type("Run", (), {"trace": _trace({0: ops}), "chips": 1, "traced_steps": 2})()
    assert read("attention_ms")(run) == pytest.approx((30 + 50) * 1e-9 / 2 * 1e3)
    # the update kernel's reader does not take a splash call for its own
    assert not re.search(KERNEL, SPLASH.format(n=1))
    assert read("guided_update_ms")(run) is None


def test_attention_ms_reads_nothing_without_splash_kernels():
    run = type("Run", (), {"trace": xplane.Trace(SMALL), "chips": 1, "traced_steps": 3})()
    assert read("attention_ms")(run) is None
