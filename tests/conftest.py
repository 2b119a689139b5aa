"""Shared fixtures. NOTE: no XLA_FLAGS device-count override here — smoke
tests and benches must see the real single device (the 512-device override
belongs to launch/dryrun.py alone). Multi-device collective tests spawn a
subprocess with their own flags (tests/test_collectives.py)."""
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.fixture(scope="session")
def host_mesh():
    from repro.launch.mesh import make_host_mesh

    return make_host_mesh()


@pytest.fixture(scope="session")
def rng0():
    import jax

    return jax.random.PRNGKey(0)
