import os

# Keep the default test process single-device (the dry-run sets its own flags
# in a separate process; forcing 512 devices here would slow every test).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import pytest

jax.config.update("jax_enable_x64", False)
# the launchers turn JAX's persistent compilation cache on for their process
# (repro.common.cache); under pytest that would be every later test of the
# worker, compiled into and read back from the checkout's .cache/
jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture(scope="session", autouse=True)
def _repro_tsan():
    """REPRO_TSAN=1 runs the whole session under the runtime race sanitizer
    (repro.analysis.sanitize): every Lock/Condition/Thread the dist, prefetch
    and checkpoint classes create is instrumented, and any lock-order
    inversion or unlocked shared write observed across the run fails the
    session at teardown. Off by default — zero overhead for plain runs."""
    from repro.analysis import sanitize

    if not sanitize.enabled():
        yield
        return
    sanitize.install()
    yield
    reports = sanitize.report()
    sanitize.uninstall()
    if reports:
        pytest.fail("race sanitizer found issues:\n" + "\n".join(reports),
                    pytrace=False)
