"""The trace reduction, on a small trace recorded on one TPU v5e
(``chipbench/tools/record_trace.py``, kept in ``data/``): five runs of
``jit_chipbench_probe`` inside a ``chipbench/window`` span, each inside a
``train/step`` span and followed by a 2 ms host sleep, then one run of
``jit_chipbench_idle`` after the window."""
import pathlib

import pytest

from chipbench import trace_reduce as tr

SMALL = pathlib.Path(__file__).parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    return tr.load(str(SMALL))


def test_planes_and_spans(trace):
    assert list(trace.ops) == ["/device:TPU:0"]
    names = [n for _, _, n in trace.host]
    assert names.count(tr.WINDOW) == 1
    assert names.count("train/step") == 5


def test_summary(trace):
    s = tr.summarize(trace)
    lo, hi = tr.window_of(trace)
    assert s["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert 0 < s["busy_s"] < s["window_s"]
    assert s["idle_share"] == pytest.approx(1 - s["busy_s"] / s["window_s"])
    # only the probe ran on the device inside the window
    assert all(k.startswith("jit_chipbench_probe/")
               for k, _ in s["device_ops"])
    assert sum(v for _, v in s["device_ops"]) >= s["busy_s"]
    assert len(s["idle_gaps"]) == 10
    gaps = [g for _, g in s["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    # the 2 ms sleeps fall between train/step spans: no program span open
    long_gaps = [label for label, g in s["idle_gaps"] if g > 2e-3]
    assert len(long_gaps) >= 4 and set(long_gaps) == {"none"}
    assert {label for label, _ in s["idle_gaps"]} <= {"none", "train/step"}
    assert len(s["spans"]["train/step"]) == 5


def test_program_device_time(trace):
    probe = tr.program_device_s(trace, "jit_chipbench_probe")
    mods = trace.modules["/device:TPU:0"]
    assert probe == pytest.approx(sum(
        (e - s) * 1e-9 for s, e, n in mods
        if n.startswith("jit_chipbench_probe(")))
    assert probe > 0
    assert tr.program_device_s(trace, "jit_chipbench_idle") > 0
    assert tr.program_device_s(trace, "jit_chipbench") == 0


def test_device_times_move_onto_the_host_clock(trace):
    # every probe run lies inside its train/step span once moved
    steps = [(s, e) for s, e, n in trace.host if n == "train/step"]
    runs = [m for m in trace.modules["/device:TPU:0"]
            if m[2].startswith("jit_chipbench_probe(")]
    assert len(runs) == len(steps) == 5
    for (s, e, _), (hs, he) in zip(runs, steps):
        assert hs < s < e < he


def test_clock_skew_bounds():
    runs = {1: (100, 110), 2: (200, 230)}
    # enqueued at 150 and 240 on the host: at least +50; completed at 175
    # and 290: at most +60
    assert tr.clock_skew(runs, {1: 150, 2: 240}, {1: 175, 2: 290}) == 55
    assert tr.clock_skew(runs, {1: 150}, {}) == 50
    assert tr.clock_skew(runs, {}, {}) == 0.0


def test_union_and_gaps():
    busy = tr.union([(5, 8, "a"), (0, 2, "b"), (1, 3, "c"), (9, 20, "d")],
                    1, 12)
    assert busy == [(1, 3), (5, 8), (9, 12)]
    assert tr.gaps(busy, 0, 14) == [(0, 1), (3, 5), (8, 9), (12, 14)]


def test_gap_labels_take_the_innermost_span():
    host = [(0, 100, "sched/wave"), (10, 50, "serve/wave"),
            (0, 200, tr.WINDOW)]
    assert tr.span_at(host, 20) == "serve/wave"
    assert tr.span_at(host, 70) == "sched/wave"
    assert tr.span_at(host, 150) == "none"


def test_op_labels():
    assert tr.op_label("jit_step(123)", "%fusion.3 = f32[8]{0} fusion(x)") \
        == "jit_step/fusion.3"
    assert tr.op_label("", "copy.1") == "?/copy.1"
