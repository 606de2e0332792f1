"""chip_smoke.py's control flow at tiny sizes on the CPU.

The smoke itself runs only on a TPU (its device gate refuses the CPU);
here each phase runs on a 64-px tile geometry, with the Pallas kernels in
interpret mode, so a broken phase shows up in tier-1 before it costs chip
time.
"""
import importlib.util
import json
import os
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs.difet_paper import DifetConfig
from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]
CFG = DifetConfig(tile=64, halo=16, max_keypoints_per_tile=32)
SCENE = (256, 256)                    # 4 x 4 tiles: 4 shards of 4


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiles(smoke):
    return smoke.check_tiles(CFG, SCENE, n_check=4)


def test_main_refuses_cpu_with_device_gate_message(smoke, capsys):
    with pytest.raises(SystemExit) as e:
        smoke.main([])
    assert e.value.code not in (0, None)
    assert "no TPU" in str(e.value.code)
    assert '"ok": true' not in capsys.readouterr().out


def test_extract_phase(smoke, tiles, tmp_path, capsys):
    smoke.phase_extract(tmp_path, *tiles, cfg=CFG, scene_hw=SCENE,
                        algorithms=("harris", "fast", "orb"))
    out = capsys.readouterr().out
    assert "chip-vs-cpu orb" in out and "16 tiles" in out


def test_pallas_phase_interpret(smoke, tiles, capsys):
    smoke.phase_pallas(*tiles, cfg=CFG, compiled=False,
                       algorithms=("shi_tomasi", "sift", "brief"))
    out = capsys.readouterr().out
    assert "pallas-vs-jnp brief" in out
    assert "sift       kernels: none; octave 0 (96 px): kernel" in out


def test_service_phase(smoke, capsys):
    smoke.phase_service(n_requests=8, bucket=32, batch=4, unique=4)
    assert "8 requests, 0 mismatching arrays" in capsys.readouterr().out


def test_matcher_phase(smoke, tmp_path, capsys):
    smoke.phase_matcher(tmp_path, nq=64, nk=512)
    out = capsys.readouterr().out
    assert out.count("idx equal True") == 8
    assert "hamming default dispatch -> jnp_" in out


def test_four_chip_phase_on_one_device(smoke, tmp_path, capsys):
    smoke.phase_four_chips(tmp_path, cfg=CFG, scene_hw=SCENE, n_devices=1,
                           algorithms=("harris", "fast"))
    assert "mesh 1 vs mesh 1: 0 differing arrays" in capsys.readouterr().out


def test_lowered_kernels_finds_no_mosaic_in_interpret_mode(smoke):
    from repro.kernels import ops
    x = np.zeros((1, 40, 40), np.float32)
    assert smoke.lowered_kernels(ops.harris, x) == []


def test_compile_cache_dir_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == []                # JAX reads the variable itself


def test_compile_cache_dir_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    want = str(ROOT / ".jax_cache")
    assert compile_cache.compile_cache_dir() == want
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    assert compile_cache.enable_compile_cache() == want
    assert calls == [("jax_compilation_cache_dir", want)]
