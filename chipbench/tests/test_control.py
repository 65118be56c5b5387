"""Each cell's control: the reference, put in the program's place and
computed at ``high`` (the three-pass bfloat16 split, the nearest precision
below the configuration's float32 at ``highest``), fails the cell's
limits. The chip's readings at full size are in PERF.md; these hold the
comparisons at a size a test run can hold."""
import pytest

from chipbench import compare, reference, traffic
from chipbench import run as harness
from conftest import ROOT

SEEDS = (2 ** 31 + 41, 2 ** 31 + 42, 2 ** 31 + 43)


def _cell(name):
    return harness.resolve_cell(harness.load_json(ROOT / "BENCHMARK.json"),
                                name)


@pytest.mark.parametrize("seed", SEEDS)
def test_serving_control_fails(seed):
    cell = _cell("tox21.serve.poisson")
    config = cell.config
    pool = traffic.molecule_pool(config, dict(cell.spec, molecules=256))
    n_max = config["molecules"]["max_nodes"]
    want, got = (reference.serve_logits(seed, config["gcn"], pool, n_max,
                                        precision=p, block=128)
                 for p in ("highest", "high"))
    limit = cell.spec["limits"]["logit_gap"]
    assert compare.logit_readings(got, want)["logit_gap"] > limit


@pytest.mark.parametrize("name", ["tox21.train"])
def test_training_control_fails(name, run_tiny):
    """A tiny run through the harness, its control judged by the same
    comparison as the program's readings."""
    result = run_tiny(name, SEEDS[0], extra=("control",))
    limits = _cell(name).spec["limits"]
    assert harness.judge(result["_readings"]["program"], limits)[1]
    assert not harness.judge(result["_readings"]["control"], limits)[1]
