"""The reduction from a device trace to busy time, idle share, operation
times and labelled idle gaps."""
import pytest

from bench import xtrace
from bench.spec import ROOT

MS = 1e6          # nanoseconds


def _events():
    # window 0-100 ms; device 0 busy 10-40 (two overlapping ops and one
    # nested in them) and 60-70; device 1 busy 0-50
    return {
        "devices": {
            "/device:TPU:0": [(10 * MS, 30 * MS, "fusion.1"),
                              (20 * MS, 40 * MS, "fusion.2"),
                              (12 * MS, 14 * MS, "copy.3"),
                              (60 * MS, 70 * MS, "fusion.1"),
                              (150 * MS, 160 * MS, "outside")],
            "/device:TPU:1": [(0, 50 * MS, "fusion.1")],
        },
        "spans": [(0, 100 * MS, "bench.window"),
                  (40 * MS, 58 * MS, "bench.store.get"),
                  (72 * MS, 99 * MS, "bench.store.put_result"),
                  (80 * MS, 90 * MS, "bench.submit")],
    }


def test_busy_is_the_union_of_overlapping_ops():
    r = xtrace.reduce(_events())
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_by_device"]["/device:TPU:0"] == pytest.approx(0.040)
    assert r["busy_by_device"]["/device:TPU:1"] == pytest.approx(0.050)
    assert r["busy_s"] == pytest.approx(0.045)
    assert r["idle_share"] == pytest.approx(0.55)


def test_op_times_count_nested_ops_once():
    r = xtrace.reduce(_events())
    ops = dict(r["device_ops"])
    # device 0: fusion.1 10-12, 14-20 and 60-70 (copy.3 started later
    # and holds 12-14, fusion.2 holds 20-40); device 1: fusion.1 0-50;
    # averaged over 2 devices; the op outside the window is not read
    assert ops["fusion.1"] == pytest.approx((0.018 + 0.050) / 2)
    assert ops["copy.3"] == pytest.approx(0.002 / 2)
    assert ops["fusion.2"] == pytest.approx(0.020 / 2)
    assert "outside" not in ops
    assert sum(ops.values()) == pytest.approx(r["busy_s"])


def test_gaps_are_labelled_by_the_innermost_span():
    r = xtrace.reduce(_events())
    gaps = r["idle_gaps"]
    # device 1: 50-100 (mid 75: put_result); device 0: 70-100 (mid 85:
    # submit inside put_result), 40-60 (mid 50: store.get), 0-10 (mid 5:
    # the window alone)
    assert gaps[0] == ["bench.store.put_result", pytest.approx(0.050)]
    assert gaps[1] == ["bench.submit", pytest.approx(0.030)]
    assert gaps[2] == ["bench.store.get", pytest.approx(0.020)]
    assert gaps[3] == ["bench.window", pytest.approx(0.010)]
    assert r["spans"]["bench.store.get"] == {"count": 1,
                                             "seconds": pytest.approx(0.018)}


def test_window_defaults_to_the_device_extent_without_a_span():
    ev = _events()
    ev["spans"] = []
    r = xtrace.reduce(ev)
    assert r["window_s"] == pytest.approx(0.160)


def test_no_device_op_is_an_error():
    with pytest.raises(ValueError):
        xtrace.reduce({"devices": {}, "spans": [(0, 1, "bench.window")]})


TRIMMED = ROOT / "bench/testdata/landsat8_x1_trimmed.xspace.txt"


@pytest.fixture(scope="module")
def chip_trace():
    """641 operations and the benchmark's spans around one bundle's
    ``bench.store.get``, trimmed from a ``--trace 1`` run of
    ``landsat8.all7.x1`` on a TPU v5 lite."""
    from jax.profiler import ProfileData
    return xtrace.events(ProfileData.from_text_proto(TRIMMED.read_text()))


def _union_by_counting(iv):
    """Busy length by a +1/-1 sweep over the boundaries."""
    marks = sorted([(s, 1) for s, e in iv] + [(e, -1) for s, e in iv])
    busy, depth, last = 0.0, 0, None
    for t, d in marks:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy * 1e-9


def test_chip_trace_busy_idle_and_ops(chip_trace):
    ops = chip_trace["devices"]["/device:TPU:0"]
    assert len(ops) == 641 and all(" " in n for _, _, n in ops)
    r = xtrace.reduce(chip_trace)
    lo, hi = xtrace.window_of(chip_trace)
    want = _union_by_counting([(max(s, lo), min(e, hi)) for s, e, _ in ops])
    assert r["busy_s"] == pytest.approx(want, rel=1e-9)
    assert r["idle_share"] == pytest.approx(1 - want / ((hi - lo) * 1e-9))
    # nested ops (a while loop's body) count once: per-op times add up
    # to the busy time, and the loop keeps only what its body leaves
    times = xtrace.self_times(ops, lo, hi)
    assert sum(times.values()) == pytest.approx(want, rel=1e-9)
    (loop,) = [o for o in ops if o[2].startswith("while.")]
    body = [(s, e) for s, e, n in ops
            if loop[0] <= s and e <= loop[1] and (s, e, n) != loop]
    assert body
    assert times[loop[2]] == pytest.approx(
        (loop[1] - loop[0]) * 1e-9 - _union_by_counting(body), abs=1e-9)


def test_chip_trace_gap_is_the_store_read(chip_trace):
    r = xtrace.reduce(chip_trace)
    label, secs = r["idle_gaps"][0]
    assert label == "bench.store.get" and secs > 0.5
    assert r["spans"]["bench.store.put_result"]["count"] == 7
