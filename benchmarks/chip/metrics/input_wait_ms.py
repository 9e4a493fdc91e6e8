"""input_wait_ms: host time per train step in which the fit loop waited for
its next staged block (the program's `fit.input_wait` spans): what the
prefetcher failed to hide."""


def per_step_ms(run, *spans):
    """Summed duration of the host spans named `spans` per traced step, in
    ms; None where the trace holds none of them (a program without them)."""
    if run.trace is None or not run.traced_steps:
        return None
    ns = [end - start for name, start, end in run.trace.host if name in spans]
    return 1e-6 * sum(ns) / run.traced_steps if ns else None


def read(run):
    return per_step_ms(run, "fit.input_wait")
