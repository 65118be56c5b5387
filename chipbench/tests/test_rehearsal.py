"""A CPU rehearsal of each traffic kind at a tiny size, with the Pallas
kernels in interpret mode: the run reaches its end and its last line has
the contract's shape. The command itself refuses the CPU, and a directory
that holds only the benchmark."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import run as harness
from chipbench import trace_reduce
from conftest import ROOT

BENCH = harness.load_json(ROOT / "BENCHMARK.json")
KINDS = {"train": "tox21.train", "serve": "tox21.serve.poisson"}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_each_kind_rehearses(kind, run_tiny, capsys):
    name = KINDS[kind]
    result = run_tiny(name, 2 ** 31 + 5)
    capsys.readouterr()
    harness.emit(result)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert isinstance(line["correct"], bool)
    assert line["attempted"] > 0 and line["failed"] == 0
    cell = harness.resolve_cell(BENCH, name)
    assert {k: v["unit"] for k, v in line["metrics"].items()} == {
        m["name"]: m["unit"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert set(line["checks"]) == set(cell.spec["limits"])
    tail = err.strip().splitlines()[-len(line["checks"]):]
    assert [t.split(":")[0] for t in tail] == [f"check {k}"
                                              for k in line["checks"]]


def test_every_cell_reports_what_its_metrics_move():
    for w in BENCH["workloads"]:
        cell = harness.resolve_cell(BENCH, w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        assert all(m["moves"] in e2e for m in cell.per_layer)
        assert (ROOT / "chipbench" / "drivers"
                / f"{cell.spec['kind']}.py").is_file()
    for m in BENCH["per_layer"]:
        assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").is_file()


def test_metric_readers_on_the_recorded_trace():
    """Every per-layer reader gives a number where its input is there and
    ``None`` where it is not; no share passes 100 %."""
    from chipbench.peaks import PEAKS

    tr = trace_reduce.load(str(ROOT / "chipbench" / "tests" / "data"
                               / "small.xplane.pb"))
    summary = trace_reduce.summarize(tr)
    counters = {"mol_per_s": 1000.0, "train_flops_per_mol": 6e6,
                "queue_wait_ms": 2.0, "pad_waste": 0.5, "p99_ms": 30.0,
                "probe": [{"program": "jit_chipbench_probe",
                           "flops": 1e6, "bytes": 1e6}]}
    for m in BENCH["per_layer"]:
        reader = harness.load_module(ROOT / "chipbench" / "metrics"
                                     / f"{m['name']}.py")
        cell = harness.resolve_cell(BENCH, m["workloads"][0])
        full = harness.MetricInputs(
            trace=summary, counters=counters, peak=PEAKS["TPU v5 lite"],
            chips=1, cell=cell, programs={
                "jit_chipbench_probe": trace_reduce.program_device_s(
                    tr, "jit_chipbench_probe")})
        empty = harness.MetricInputs(trace=None, counters={}, peak=None,
                                     chips=1, cell=cell, programs={})
        value = reader.read(full)
        if m["name"] != "wave_ms.serve":    # no serve/wave span recorded
            assert value is not None and value > 0, m["name"]
        if m["unit"] == "%" and value is not None:
            assert value <= 100, m["name"]
        assert reader.read(empty) is None, m["name"]


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "tox21.train",
         "--seed", str(2 ** 31 + 9), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_the_cpu():
    proc = _command(ROOT)
    assert proc.returncode == harness.EXIT_NO_CHIP
    assert not proc.stdout.strip()
    assert "needs 1 accelerator" in proc.stderr


def test_command_refuses_a_directory_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _command(tmp_path)
    assert proc.returncode != 0
    assert not proc.stdout.strip()
