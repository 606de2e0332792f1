import hashlib
import os
import random
import sys

# Tests run on the CPU and see its real devices; a test that needs more
# sets XLA's host device count in a subprocess of its own.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

# The drivers' main() turns JAX's persistent compilation cache on
# (`launch/compile_cache.py`); tests that call a main() keep it off, so a
# test run writes nothing to the checkout's .jax_cache/.
jax.config.update("jax_enable_compilation_cache", False)

# Hypothesis determinism: explicit profiles with deadlines disabled (the
# chaos/fleet tests share CI machines with compile-heavy neighbours, so
# wall-clock deadlines flake) and derandomized example generation — the
# same examples on every run, every shard, every repeat of the 3x CI
# flake gate.  Select with HYPOTHESIS_PROFILE (default "dev"; CI uses
# "ci").  Optional dependency: absent hypothesis, the property tests
# skip themselves and there is nothing to configure.
try:
    from hypothesis import settings as _hyp_settings

    _hyp_settings.register_profile("dev", deadline=None, derandomize=True)
    _hyp_settings.register_profile("ci", deadline=None, derandomize=True,
                                   max_examples=25, print_blob=True)
    _hyp_settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "dev"))
except ImportError:
    pass


@pytest.fixture(autouse=True)
def _seed_stochastic_sources(request):
    """Determinism sweep: every test starts from a seed derived from its
    own nodeid, so any code reaching for the global ``random`` /
    ``np.random`` state is reproducible per-test and independent of
    execution order, sharding, or the CI repeat count."""
    digest = hashlib.sha256(request.node.nodeid.encode()).digest()
    seed = int.from_bytes(digest[:4], "big")
    random.seed(seed)
    np.random.seed(seed)


@pytest.fixture(scope="session")
def rng():
    return np.random.RandomState(0)


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)


class FakeMesh:
    """Mesh stand-in exposing just what the sharding helpers consume
    (axis_names / shape / size) without touching device state."""

    def __init__(self, **axes):
        self.axis_names = tuple(axes)
        self.shape = dict(axes)
        self.size = int(np.prod(list(axes.values())))


@pytest.fixture
def mesh_16x16():
    return FakeMesh(data=16, model=16)


@pytest.fixture
def mesh_pod():
    return FakeMesh(pod=2, data=16, model=16)
