"""collective_exposed_ms: per train step, the time of all-gather,
all-reduce, reduce-scatter (and other collective) operations during which no
other operation runs on the same chip, averaged over the chips. Nothing to
read on one chip."""


def read(run):
    if run.trace is None or not run.trace.ops or run.chips < 2 or not run.traced_steps:
        return None
    return 1e3 * run.trace.collective_exposed_s() / run.traced_steps
