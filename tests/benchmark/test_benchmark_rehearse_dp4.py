"""Rehearsal 2: the four-chip cell on four virtual CPU devices, by the
command as the driver gives it. The last line holds exactly the contract's
keys and names the CPU as its device, so it can never be filed as a chip
reading."""

from bench_helpers import CONTRACT_KEYS, rehearse


def test_dp4_cell_rehearses_on_four_virtual_devices():
    line, _ = rehearse("gpt2m-train-dp4", seconds=0.5)
    assert set(line) == CONTRACT_KEYS
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 4
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) >= {"train_samples_per_s_per_chip", "setup_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
