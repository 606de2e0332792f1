"""The service cells' check: a sound run is correct; the bfloat16
control and each fault the service can have, planted in the timed path,
are not."""
import jax
import jax.numpy as jnp

from bench.tests.faults import run_small


def test_sound_run_is_correct(tmp_path):
    r, run = run_small("xyz256.steady", tmp_path)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] == round(60.0 * 1.5)
    assert set(r["metrics"]) == {"tile_p50_ms", "setup_s"}
    assert run["keypoints"] > 50 and run["window_compiles"] == 0


def test_hot_cell_sound_run_is_correct(tmp_path):
    # a hot mix (the driver's cache path): 70% of requests on an eighth
    # of the pool, so cached answers are checked beside computed ones
    r, run = run_small("xyz256.steady", tmp_path, overrides={"traffic": {
        "hot_fraction": 0.125, "hot_weight": 0.7}})
    assert r["correct"], r["checks"]
    assert run["stats"]["cache_hits"] > run["stats"]["cache_misses"]


def test_stamps_mark_when_the_last_tile_resolves():
    import time
    from concurrent.futures import Future
    from types import SimpleNamespace as NS
    from bench.drivers.service import Stamps
    a, b, c = Future(), Future(), Future()
    st = Stamps(4)
    st.refuse(3)                                          # shed by submit
    st.watch(0, NS(_parts=[NS(future=a), NS(future=None), NS(future=b)]),
             end=100.0)
    st.watch(1, NS(_parts=[NS(future=None)]), end=100.0)  # cached whole
    st.watch(2, NS(_parts=[NS(future=c)]), end=100.0)
    a.set_result(1)
    c.set_exception(RuntimeError("step failed"))
    assert st.done().tolist() == [False, True, False, False]
    b.set_result(2)
    st.wait(time.time() + 1)
    assert st.done().tolist() == [True, True, False, False]
    assert st.refused.tolist() == [False, False, False, True]
    assert st.at[0] > 100.0 and st.at[1] == 100.0


def test_control_in_bfloat16_is_not_correct(tmp_path):
    r, run = run_small("xyz256.steady", tmp_path, control=True)
    assert not r["correct"], r["checks"]


def _patch_step(monkeypatch, wrap):
    from repro.serve import buckets
    real = buckets.make_serve_step

    def make(algorithms, cfg, use_pallas=False):
        step = real(algorithms, cfg, use_pallas=use_pallas)
        return jax.jit(lambda t, h: wrap(step, t, h))
    monkeypatch.setattr(buckets, "make_serve_step", make)


def test_altered_answer_is_caught(tmp_path, monkeypatch):
    def altered(step, t, h):          # every keypoint one column off
        return {a: {**r, "top_xs": r["top_xs"] + 1}
                for a, r in step(t, h).items()}
    _patch_step(monkeypatch, altered)
    r, run = run_small("xyz256.steady", tmp_path)
    assert not r["correct"]
    assert r["checks"]["keypoint_miss"]["value"] > 0.5


def test_half_the_batch_left_out_is_caught(tmp_path, monkeypatch):
    def half(step, t, h):             # the later half of the filled rows
        real = (h[:, 5] == 0).sum()   # are never extracted
        drop = jnp.arange(h.shape[0]) >= (real + 1) // 2
        return step(t, h.at[:, 5].set(jnp.where(drop, 1, h[:, 5])))
    _patch_step(monkeypatch, half)
    r, run = run_small("xyz256.steady", tmp_path,
                  overrides={"traffic": {"rate": 400.0}})
    assert not r["correct"]
    assert r["checks"]["count_gap"]["value"] > 0.1
