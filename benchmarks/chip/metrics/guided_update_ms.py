"""guided_update_ms: device time of the fused guided-update Pallas kernels
per train step, averaged over the chips."""

#: the fused update's kernels among a trace's operations: the Pallas calls
#: (`tpu_custom_call`) whose last operand is the update's scalar pack, f32[2]
#: (sgd), [3] (momentum), [4] (rmsprop) or [9] (adam). The kernels carry no
#: name of their own in the trace.
KERNEL = r'f32\[(2|3|4|9)\]\{0[^}]*\} %[\w.\-]+\), custom_call_target="tpu_custom_call"' 


def seconds_per_step(run):
    if run.trace is None or not run.trace.ops or not run.traced_steps:
        return None
    s = run.trace.op_seconds(KERNEL)
    return s / run.traced_steps if s > 0 else None


def read(run):
    s = seconds_per_step(run)
    return None if s is None else 1e3 * s
