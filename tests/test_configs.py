"""DIFET's configuration: the paper's settings, and what keys on them."""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs import PAPER_ALGORITHMS, PAPER_CONFIG, DifetConfig
from repro.serve.api import config_digest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_paper_defaults():
    """Section 4 of the paper: the 7681 x 7831 Landsat-8 scene, SURF's
    Hessian threshold of 400, FAST-9; tiles of 512 with a 24-px halo."""
    assert PAPER_CONFIG == DifetConfig()
    assert PAPER_CONFIG.scene_hw == (7681, 7831)
    assert PAPER_CONFIG.tile == 512
    assert PAPER_CONFIG.halo == 24
    assert PAPER_CONFIG.surf_hessian_threshold == 400.0
    assert PAPER_CONFIG.fast_arc == 9


def test_paper_algorithms_are_the_papers_seven():
    assert PAPER_ALGORITHMS == ("harris", "shi_tomasi", "sift", "surf",
                                "fast", "brief", "orb")


def test_config_is_a_jit_static_argument():
    """Frozen and hashable: the engine jits over it as a static argument,
    so equal configs share one trace and a changed one traces anew."""
    assert hash(DifetConfig()) == hash(DifetConfig())
    with pytest.raises(dataclasses.FrozenInstanceError):
        PAPER_CONFIG.tile = 256
    traces = []

    def f(x, cfg):
        traces.append(cfg)
        return x * cfg.tile

    g = jax.jit(f, static_argnums=1)
    x = jnp.ones(3)
    assert float(g(x, DifetConfig())[0]) == 512.0
    assert float(g(x, DifetConfig())[0]) == 512.0
    assert float(g(x, DifetConfig(tile=256))[0]) == 256.0
    assert len(traces) == 2


def test_the_program_imports_only_difet():
    """Importing the job, the engine and the service loads DIFET's own
    packages and nothing else: of the configurations, only the paper's."""
    code = (
        "import sys\n"
        "import repro.core.engine, repro.core.job, repro.serve\n"
        "print('\\n'.join(sorted(m for m in sys.modules "
        "if m.startswith('repro.'))))\n")
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True).stdout.split()
    assert "repro.core.job" in out and "repro.serve.api" in out
    packages = {m.split(".")[1] for m in out}
    assert packages <= {"configs", "core", "data", "distributed", "kernels",
                        "obs", "serve"}, packages
    assert [m for m in out if m.startswith("repro.configs.")] == \
        ["repro.configs.difet_paper"]


@pytest.mark.parametrize("field,value", [
    ("halo", 24),
    ("max_keypoints_per_tile", 64),
    ("fast_threshold", 0.2),
    ("surf_hessian_threshold", 500.0),
    ("sift_contrast_threshold", 0.03),
    ("dtype", "bfloat16"),
])
def test_config_digest_keys_every_output_field(field, value):
    """The service's cache key changes with any field that changes the
    features, so a changed config is always a cache miss."""
    base = DifetConfig(tile=256, halo=16, max_keypoints_per_tile=128)
    changed = dataclasses.replace(base, **{field: value})
    assert getattr(changed, field) != getattr(base, field)
    assert config_digest(changed) != config_digest(base)
    assert config_digest(dataclasses.replace(base)) == config_digest(base)
