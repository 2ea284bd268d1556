"""The work counts the roofline metrics divide by."""

import json
import os

import pytest

import work
from conftest import BENCH


def test_mvm_counts_at_resnet18_224_shapes():
    # conv1: 112x112 outputs, 7x7x3 patches, 64 channels
    assert work.mvm_ops(12544, 147, 64) == 236_027_904
    assert work.mvm_bytes(12544, 147, 64) == (12544 * 147 + 147 * 64
                                              + 4 * 12544 * 64)
    # layer4's 3x3 conv: 7x7 outputs, 3x3x512 patches, 512 channels
    assert work.mvm_ops(49, 4608, 512) == 231_211_008
    assert work.mvm_bytes(49, 4608, 512) == 2_685_440


def test_mvm_least_time_takes_the_larger_bound():
    peaks = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}
    # conv1 moves 5.06 MB for 0.236 GOP: memory bound
    assert work.mvm_least_s(12544, 147, 64, peaks) == pytest.approx(
        5_064_640 / 819e9)
    # a square 4096 matmul: 137 GOP over 84 MB, compute bound
    assert work.mvm_least_s(4096, 4096, 4096, peaks) == pytest.approx(
        2 * 4096 ** 3 / 393e12)


def test_stage_pass_least_bytes():
    # resnet18@224's first stage: 166,887 decode rows, a 64-machine fleet
    assert work.stage_pass_bytes(166_887, 64) == 8 * 166_887 * (13 + 64 + 11)
    assert work.stage_pass_bytes(166_887, 1) == 8 * 166_887 * 25


def test_peaks_table_has_the_v5e_and_its_source():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
    assert "TPU v5e" in peaks["source"]
