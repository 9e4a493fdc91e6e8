"""prefetch_busy_ms: the prefetch worker's own host time per train step,
drawing blocks (`prefetch.make`: generation and stacking) and placing them on
the device (`prefetch.put`). It overlaps the dispatches; `input_wait_ms`
says whether it kept ahead of them."""
from metrics.input_wait_ms import per_step_ms


def read(run):
    return per_step_ms(run, "prefetch.make", "prefetch.put")
