import os

# Tests run on the single real CPU device; the 512-device production mesh is
# exercised ONLY by launch/dryrun.py (which sets XLA_FLAGS itself).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True)
def _kernel_state_guard():
    """Snapshot/restore the only remaining global kernel-dispatch state —
    the process-default KernelContext — so a test that swaps the default
    (ops.set_default_context) can never leak plan state into another test,
    whatever the ordering."""
    from repro.kernels import ops

    saved = ops.default_context()
    yield
    ops.set_default_context(saved)
