"""The finer reductions (``chipbench/trace_detail.py``): on a synthetic
trace, on the small trace recorded on one TPU v5e (whose existing summary
must read as before), and the scope map of a CPU-compiled training step."""
import pathlib

import pytest

from chipbench import trace_detail as td
from chipbench import trace_reduce as tr

SMALL = pathlib.Path(__file__).parent / "data" / "small.xplane.pb"
DEV = "/device:TPU:0"


def synthetic():
    """A 100 ns window with two iterations of a training loop. Device ops
    of ``jit_step`` run at 20–30 (conv0), 30–35 (conv1), 35–40 (no scope)
    and 70–80 (conv0); a collection runs at 50–60 inside the second
    iteration's ``train/batch``."""
    ops = {DEV: [(20, 30, "%fusion.1 = f32[8] fusion(x)"),
                 (30, 35, "fusion.2"), (35, 40, "copy.3"),
                 (70, 80, "fusion.1")]}
    modules = {DEV: [(20, 40, "jit_step(1)"), (70, 80, "jit_step(1)")]}
    host = sorted([
        (0, 100, tr.WINDOW),
        (5, 45, "train/iter"), (5, 15, "train/batch"), (15, 25, "train/step"),
        (25, 45, "train/sync"),
        (45, 90, "train/iter"), (45, 65, "train/batch"), (50, 60, "host/gc"),
        (65, 68, "train/step"),
        (90, 120, "train/iter"),             # runs past the window
    ])
    return tr.Trace(ops=ops, modules=modules, host=host)


def test_detail_spans_in_the_window():
    d = td.detail_spans(synthetic())
    ns = 1e-9
    assert d == {"train/iter": [pytest.approx(40 * ns), pytest.approx(45 * ns)],
                 "train/batch": [pytest.approx(10 * ns),
                                 pytest.approx(20 * ns)],
                 "train/sync": [pytest.approx(20 * ns)],
                 "host/gc": [pytest.approx(10 * ns)]}


def test_idle_by_span_splits_each_gap_by_the_innermost_span():
    idle = td.idle_by_span(synthetic())
    ns = 1e-9
    # gaps: 0–20, 40–70, 80–100
    assert idle == {
        "none": pytest.approx(5 * ns),          # 0–5
        "train/batch": pytest.approx(20 * ns),  # 5–15, 45–50, 60–65
        "train/step": pytest.approx(8 * ns),    # 15–20, 65–68
        "train/sync": pytest.approx(5 * ns),    # 40–45
        "host/gc": pytest.approx(10 * ns),      # 50–60
        "train/iter": pytest.approx(22 * ns),   # 68–70, 80–90, 90–100
    }
    s = tr.summarize(synthetic())
    assert sum(idle.values()) == pytest.approx(s["window_s"] - s["busy_s"])


def test_scope_device_time():
    scopes = {"fusion.1": "conv0/spmm/dense", "fusion.2": "conv1"}
    got = td.scope_device_s(synthetic(), "jit_step", scopes)
    assert got == {"conv0/spmm/dense": pytest.approx(20e-9),
                   "conv1": pytest.approx(5e-9)}
    assert td.scope_device_s(synthetic(), "jit_other", scopes) == {}


def test_hlo_scopes_parse_op_names():
    text = "\n".join([
        'ENTRY %main {',
        '  %fusion.3 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f, '
        'metadata={op_name="jit(step)/transpose(jvp(conv1))/spmm/dense/mul"'
        ' source_file="x.py"}',
        '  ROOT %add.1 = f32[8]{0} add(%b, %c), metadata={op_name='
        '"jit(step)/jvp(conv0)/bmn,cnf->cbmf"}',
        '  %mul.2 = f32[8]{0} multiply(%b, %c), metadata={op_name='
        '"jit(step)/jvp()/mul"}',
        '  %convert.4 = f32[8]{0} convert(%b), metadata={op_name='
        '"jit(step)/convert_element_type"}',
        '  %copy.5 = f32[8]{0} copy(%b)',
        '}'])
    assert td.hlo_scopes(text) == {"fusion.3": "conv1/spmm/dense",
                                   "add.1": "conv0"}


def test_existing_summary_reads_as_before():
    """``trace_reduce.summarize`` on the recorded trace, pinned: loading
    the detail spans beside it changes none of its keys."""
    for trace in (tr.load(str(SMALL)), td.load(str(SMALL))):
        s = tr.summarize(trace)
        assert list(s) == ["window_s", "busy_s", "idle_share", "device_ops",
                           "idle_gaps", "spans"]
        assert s["window_s"] == pytest.approx(0.016904869, rel=1e-9)
        assert s["busy_s"] == pytest.approx(2.1678e-05, rel=1e-9)
        assert s["idle_share"] == pytest.approx(0.9987176475605933)
        assert s["device_ops"] == [
            ["jit_chipbench_probe/fusion", pytest.approx(1.9675e-05)],
            ["jit_chipbench_probe/copy-done", pytest.approx(1.938e-06)],
            ["jit_chipbench_probe/copy-start", pytest.approx(6.5e-08)]]
        assert [g[0] for g in s["idle_gaps"]] == ["none"] * 5 + [
            "train/step"] * 5
        assert [g[1] for g in s["idle_gaps"][:6]] == pytest.approx(
            [0.00375043, 0.003467163, 0.003177496, 0.003062592,
             0.00296528, 0.000460214])
        assert s["spans"] == {"train/step": pytest.approx(
            [0.00100978, 0.00067124, 0.00060525, 0.00106688, 0.00063628])}


def test_recorded_trace_has_no_detail_spans():
    trace = td.load(str(SMALL))
    s = td.summarize(trace)
    assert s["detail_spans"] == {}
    assert s["idle_by_span"] == {"none": pytest.approx(0.012915439),
                                 "train/step": pytest.approx(0.003967752)}


def test_step_scope_map_of_a_cpu_compiled_training_step(tiny_cell):
    """The tool's scope map of the training driver's own step names
    operations of both conv layers, and the SpMM kernels inside them."""
    import jax

    from chipbench import run as harness
    from chipbench.tools import detail

    cell = tiny_cell("tox21.train")
    jax.config.update("jax_default_matmul_precision",
                      cell.config["precision"]["matmul"])
    driver = harness.load_module(harness.HERE / "drivers" / "train.py")
    run = harness.Run(cell=cell, seed=2 ** 31 + 3, seconds=1.0,
                      trace=False, peak=None)
    state = driver.setup(run)
    scopes = td.hlo_scopes(detail.step_hlo(state))
    layers = {s.split("/")[0] for s in scopes.values()}
    assert layers == {"conv0", "conv1"}
    assert any("/spmm/" in s for s in scopes.values())
    state["ckpt"].cleanup()
