"""Rehearsal 1 of ``lfm2moe-train-1chip``: the cell end to end on the CPU at
its rehearsal sizes, the command as the driver gives it, untraced and traced.
The program's first steps agree with the plain reference within the cell's
rehearsal limits (the committed limits are the chip's, at full size)."""

import pytest

from bench_helpers import CONTRACT_KEYS, rehearse

CELL = "lfm2moe-train-1chip"


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_comes_out_correct(trace):
    line, out = rehearse(CELL, seed=2147483659 + trace, seconds=0.5,
                         trace=trace, timeout=600)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    if trace:
        assert set(line) == CONTRACT_KEYS | {"breakdown"}
        # no TPU plane in a CPU trace: the device readers, the new ones
        # among them, find nothing and are left out
        assert "dispatch_ms.train" in line["metrics"]
        assert not any(name.startswith(("short_conv", "moe_", "attn_"))
                       for name in line["metrics"])
    else:
        assert set(line) == CONTRACT_KEYS
        assert set(line["metrics"]) >= {"train_samples_per_s_per_chip",
                                        "setup_s"}
    assert '"number": "first_grad_norm"' in out
