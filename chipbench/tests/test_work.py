"""The useful-work counts on a hand-counted molecule and layer, and the
peaks table."""
import dataclasses

import numpy as np
import pytest

from chipbench import peaks, work


@dataclasses.dataclass
class Mol:
    rows: list
    n_nodes: int


# three atoms, two channels: channel 0 holds the 3 self loops, channel 1
# the bond 0-1 in both directions
MOL = Mol(rows=[np.array([0, 1, 2]), np.array([0, 1])], n_nodes=3)
CFG = {"n_features": 2, "conv_widths": [3], "n_tasks": 4}


def test_conv_flops_by_hand():
    # transform: 2 channels x 2*3*2*3 = 72; aggregation: 2*3*3 + 2*2*3 = 30
    assert work.conv_flops(3, [3, 2], 2, 3) == 102


def test_model_flops_by_hand():
    # one conv layer (102) and the head: 2 * 3 * 4 = 24
    assert work.forward_flops(MOL, CFG) == 126
    assert work.train_flops(MOL, CFG) == 3 * 126


def test_conv_train_bytes_by_hand():
    # X 3x2, W 2x(2x3 + 3), Y 3x3, 5 non-zeros: floats 2*6 + 2*18 + 2*9
    # plus 3 words per non-zero
    assert work.conv_train_bytes(3, 5, 2, 2, 3) == 4 * (12 + 36 + 18 + 15)
    assert work.conv_train_flops([(3, [3, 2])], 2, 3) == 3 * 102


def test_least_time_names_its_bound():
    peak = peaks.peak_for("TPU v5 lite")
    t, bound = work.least_time(197e12, 1.0, peak)
    assert (t, bound) == (1.0, "compute")
    t, bound = work.least_time(1.0, 819e9, peak)
    assert (t, bound) == (1.0, "memory")


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak_for("cpu")
