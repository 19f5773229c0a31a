"""The arithmetic the metrics rest on, on hand-worked cases."""

import numpy as np
import pytest

import bench_helpers  # noqa: F401 - puts the repo root on sys.path
from benchmark import check_train, manifest
from benchmark.families import gpt_dense
from benchmark.weights import count

GPT2M = manifest.load_json(
    manifest.ROOT + "/benchmark/configs/gpt2-medium.json"
)


def test_gpt2_medium_parameter_and_operation_counts():
    assert count(gpt_dense.param_spec(GPT2M)) == 406_188_032
    assert gpt_dense.matmul_params(GPT2M) == 24 * 12 * 1024 ** 2 + 1024 * 50257
    ops4 = gpt_dense.train_ops_per_step(GPT2M, {"seq_len": 1024}, 4)
    # 6 x 353.45 M x 4096 tokens = 8.686 T; causal attention 0.618 T
    assert ops4 == pytest.approx(9.30e12, rel=2e-3)
    assert gpt_dense.train_ops_per_step(GPT2M, {"seq_len": 1024}, 8) == 2 * ops4
    # bench.py's 6 * N * tokens with every parameter and the full square
    old = 6 * 406_188_032 * 4096 + 12 * 24 * 4 * 1024 ** 2 * 1024
    assert old / ops4 == pytest.approx(1.21, abs=0.01)


def test_attention_forward_cost_is_compute_bound_on_v5e():
    ops, nbytes = gpt_dense.attn_fwd_cost(GPT2M, {"seq_len": 1024}, 4)
    assert ops == 2 * 24 * 4 * 1024 ** 2 * 1024
    assert nbytes == 4 * 24 * 4 * 1024 * 1024 * 2
    peak = manifest.peak_for("TPU v5 lite")
    assert ops / peak["bf16_flops"] > nbytes / peak["hbm_bytes_per_s"]


def test_worst_leaf_gap_uses_the_larger_of_leaf_and_median():
    want = np.array([10.0, 1.0, 1e-9])
    got = np.array([10.5, 1.0, 2e-3])
    # leaf 0: 0.5 / 10; leaf 2: 2e-3 / median(=1): an all-but-zero gradient
    # is measured against the median leaf, not against itself
    assert check_train.worst_leaf_gap(got, want) == pytest.approx(0.05)


def test_a_cell_compares_the_numbers_its_limits_name():
    numbers = {"a": 0.5, "b": 7.0, "c": 0.0}
    ok, rows = check_train.verdict(numbers, {"a": 1.0, "c": 0})
    assert ok and [r["number"] for r in rows] == ["a", "c"]
    with pytest.raises(KeyError):  # a limit on a number nothing computes
        check_train.verdict(numbers, {"d": 1.0})


def test_verdict_prints_each_number_beside_its_limit():
    ok, rows = check_train.verdict({"a": 0.5, "b": float("nan")},
                                   {"a": 1.0, "b": 1.0})
    assert not ok
    assert rows[0] == {"number": "a", "value": 0.5, "limit": 1.0,
                       "within": True}
    assert rows[1]["within"] is False
