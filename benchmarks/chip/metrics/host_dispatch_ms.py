"""host_dispatch_ms: host time per train step spent issuing dispatches (the
program's `fit.dispatch` spans: argument handling, donation, enqueue)."""
from metrics.input_wait_ms import per_step_ms


def read(run):
    return per_step_ms(run, "fit.dispatch")
