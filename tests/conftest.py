"""Test harness: force a virtual 8-device CPU platform BEFORE jax initializes.

This stands in for the reference's 1-process MPI world fixture
(reference Test/unittests/multiverso_env.h:10-29) — the whole PS path runs
in-process, but over a *real* 8-device jax mesh so sharding/collective code
paths are exercised without TPU hardware. The benchmark (``benchmark/run.py``)
and ``chip_smoke.py`` use the real chip instead.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The environment variable is read when jax is imported; if something
# imported jax before this file ran, only the config switch still applies.
# Tests never touch a chip: chip_smoke.py and benchmark/run.py own it.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import faulthandler  # noqa: E402

import pytest  # noqa: E402

#: per-test hang guard (failsafe subsystem): if a single test runs this
#: long, dump EVERY thread's stack to stderr so a deadlock yields a
#: stack report in the tier-1 log instead of a silent `timeout -k`
#: kill. Sits above the slowest legitimate test (2-proc children use
#: inner timeouts up to 280s) and below the tier-1 global 870s budget.
#: exit=False: the dump is a report, not a kill — the harness owns that.
_HANG_DUMP_S = float(os.environ.get("MV_TEST_HANG_DUMP_S", "330"))


@pytest.fixture(autouse=True)
def _hang_guard():
    if _HANG_DUMP_S <= 0:
        yield
        return
    faulthandler.dump_traceback_later(_HANG_DUMP_S, exit=False)
    yield
    faulthandler.cancel_dump_traceback_later()


@pytest.fixture()
def mv_env():
    """MultiversoEnv: MV_Init'd 1-host world, torn down after the test
    (reference Test/unittests/multiverso_env.h:10-21)."""
    import multiverso_tpu as mv
    mv.MV_Init([])
    yield mv
    mv.MV_ShutDown()


@pytest.fixture()
def sync_mv_env():
    """SyncMultiversoEnv: same with -sync=true
    (reference multiverso_env.h:23-29)."""
    import multiverso_tpu as mv
    mv.MV_Init(["-sync=true"])
    yield mv
    mv.MV_ShutDown()
