"""Rehearsal 1 of the train kind: the one-chip train cell end to end on the
CPU at its rehearsal sizes, traced (a new process, the command as the driver
gives it), and a cell that a later PR adds as data (in this process). The
untraced command in a new process is ``test_benchmark_rehearse_dp4.py``."""

from bench_helpers import (CONTRACT_KEYS, added_benchmark, rehearse,
                           run_in_process)


def test_traced_rehearsal_reports_per_layer_metrics_it_can_read():
    line, _ = rehearse("gpt2m-train-1chip", seconds=0.5, trace=1)
    assert set(line) == CONTRACT_KEYS | {"breakdown"}
    # no TPU plane in a CPU trace: the device readers find nothing and are
    # left out; the span reader still reads its span
    assert "dispatch_ms.train" in line["metrics"]
    assert "mfu.train" not in line["metrics"]
    assert "train_samples_per_s_per_chip" not in line["metrics"]
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1


def test_cell_added_as_data_runs_with_no_edit(tmp_path, monkeypatch, capsys):
    """A later PR's cell: a new configuration file, a new traffic file, a
    new ``cells/`` file and new entries, no file that was there edited. The
    harness finds them by name and the run comes out correct."""
    from benchmark import manifest

    added_benchmark(str(tmp_path))
    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))
    line = run_in_process(capsys, ["--workload", "added-cell", "--seed",
                                   "2147483659", "--seconds", "0.5",
                                   "--trace", "0", "--rehearse-cpu"])
    assert set(line) == CONTRACT_KEYS
    assert line["correct"] is True and line["attempted"] > 0
    assert set(line["metrics"]) >= {"train_samples_per_s_per_chip", "setup_s"}
