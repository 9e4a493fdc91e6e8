from repro.train.steps import (  # noqa: F401
    batch_shardings,
    build_decode_step,
    build_prefill_step,
    state_shardings,
)
