"""Test configuration.

Tests run JAX on a virtual 8-device CPU mesh so multi-chip sharding is
exercised without TPU hardware; the driver's dryrun_multichip does the same.
``force_cpu_backend`` (tmtpu/tpu/compat.py) must run before any test
triggers jax backend initialization: it asks for the CPU emulation
(``JAX_PLATFORMS=cpu``, which is also what lets an explicit ``tpu``
backend start here), sets the virtual device count and places the
persistent compile cache. Pallas kernels run in interpret mode off a TPU;
the compiled form is covered by tests/test_mosaic_aot.py (slow) and by
chip_smoke.py on the chip.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tmtpu.tpu.compat import force_cpu_backend

force_cpu_backend(8)


import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running integration tests (TPU graph on CPU)"
    )
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection / crash-recovery tests (libs/faultinject)"
    )
    config.addinivalue_line(
        "markers",
        "scenarios: declarative adversarial scenarios (tmtpu/scenario); "
        "tier-1 runs the FAST pair, the full library runs via "
        "tools/scenario_run.py"
    )


@pytest.fixture(autouse=True)
def _fresh_sigcache():
    """The verified-signature cache and flush scheduler are process-wide
    by design; tests must not see each other's verifications (or a
    disabled cache left behind by a cache-off test)."""
    from tmtpu.crypto import batch as crypto_batch
    from tmtpu.crypto import sigcache

    sigcache.DEFAULT.set_enabled(True)
    sigcache.DEFAULT.invalidate_all()
    crypto_batch.SCHEDULER.reset()
    yield
    sigcache.DEFAULT.set_enabled(True)
    sigcache.DEFAULT.invalidate_all()
    crypto_batch.SCHEDULER.reset()
