"""attention_ms: device time per train step of the splash attention kernels
(`models.layers._splash_attention`), averaged over the chips: the operations
whose HLO name starts with `%splash_` (each layer's forward, its remat
recompute, dq and dkv). A trace without them (the XLA path, whose attention
is spread over unnamed fusions) reads nothing."""

#: a splash kernel's custom call, by the name Pallas gives it:
#: `%splash_mqa_fwd_residuals.<n> = ... custom-call(...)`
KERNEL = r"^%splash_"


def read(run):
    if run.trace is None or not run.trace.ops or not run.traced_steps:
        return None
    s = run.trace.op_seconds(KERNEL)
    return 1e3 * s / run.traced_steps if s > 0 else None
