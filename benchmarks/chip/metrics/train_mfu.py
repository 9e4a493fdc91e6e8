"""train_mfu: model FLOPs of the traced window's tokens over the chips'
bf16 peak, in percent. Model FLOPs per token are `counts.flops_per_token`
(6 per matmul weight plus causal attention; remat recompute not counted);
the rate is the traced tokens over the traced window on the device
timeline."""
import counts


def read(run):
    if run.trace is None or not run.trace.ops or not run.traced_tokens:
        return None
    rate = run.traced_tokens / run.trace.window_s()
    flops = counts.flops_per_token(run.cfg, int(run.traffic["seq_len"]))
    return 100.0 * flops * rate / (run.chips * run.peaks["bf16_flops"])
