"""A run with the timed path broken underneath has to come out as not
correct. These skip the harness's look for a chip (``--rehearse-cpu``) and
drive the rest of a run in this process."""

from bench_helpers import REHEARSAL_LIMITS as TRAIN_LIMITS, run_in_process
from benchmark import manifest

ARGS = ["--seed", "5", "--seconds", "0.5", "--trace", "0", "--rehearse-cpu"]


def _loosen(monkeypatch, limits):
    """The committed limits are the chip's, at full size; a rehearsal at tiny
    sizes gets limits that its own sound run meets (asserted below)."""
    original = manifest.Cell.__init__

    def init(self, *a, **kw):
        original(self, *a, **kw)
        self.options = dict(self.options, limits=limits)

    monkeypatch.setattr(manifest.Cell, "__init__", init)


def test_sound_train_run_is_correct_at_rehearsal_limits(monkeypatch, capsys):
    _loosen(monkeypatch, TRAIN_LIMITS)
    line = run_in_process(capsys, ["--workload", "gpt2m-train-1chip"] + ARGS)
    assert line["correct"] is True


def test_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch,
                                                              capsys):
    from benchmark.families import gpt_dense

    _loosen(monkeypatch, TRAIN_LIMITS)
    build = gpt_dense.build_train

    def broken(cfg, traffic, options, mesh):
        step, tx = build(cfg, traffic, options, mesh)

        def lazy(params, state, batch):
            _, new_state, loss = step(
                *__import__("jax").tree.map(lambda x: x + 0, (params, state)),
                batch,
            )
            return params, new_state, loss  # the update is thrown away

        return lazy, tx

    monkeypatch.setattr(gpt_dense, "build_train", broken)
    line = run_in_process(capsys, ["--workload", "gpt2m-train-1chip"] + ARGS)
    assert line["correct"] is False


def test_part_of_the_batch_left_out_is_not_correct(monkeypatch, capsys):
    from benchmark.families import gpt_dense

    _loosen(monkeypatch, TRAIN_LIMITS)
    build = gpt_dense.build_train

    def broken(cfg, traffic, options, mesh):
        step, tx = build(cfg, traffic, options, mesh)

        def half(params, state, batch):
            tokens, labels = batch
            n = tokens.shape[0] // 2  # the second half of the rows is
            twice = lambda x: __import__("jax").numpy.concatenate(  # dropped
                [x[:n], x[:n]]
            )
            return step(params, state, (twice(tokens), twice(labels)))

        return half, tx

    monkeypatch.setattr(gpt_dense, "build_train", broken)
    line = run_in_process(capsys, ["--workload", "gpt2m-train-1chip"] + ARGS)
    assert line["correct"] is False


def test_every_run_says_each_number_compared_beside_its_limit(monkeypatch,
                                                              capsys):
    """Last in the result's line under a key of its own, and as the last
    lines on standard error: what a refused run leaves in the record."""
    import json

    from benchmark import run

    _loosen(monkeypatch, TRAIN_LIMITS)
    assert run.main(["--workload", "gpt2m-train-1chip"] + ARGS) == 0
    said = capsys.readouterr()
    line = json.loads([l for l in said.out.splitlines() if l.strip()][-1])
    assert list(line)[-1] == "compared"
    assert set(line["compared"]) == set(TRAIN_LIMITS)
    for name, pair in line["compared"].items():
        assert pair["limit"] == TRAIN_LIMITS[name]
        assert pair["value"] <= pair["limit"]
    last = [l for l in said.err.splitlines() if l.strip()][-len(TRAIN_LIMITS):]
    assert [l.split()[1] for l in last] == list(TRAIN_LIMITS)
    assert all(l.startswith("compared ") and l.endswith(" within")
               for l in last)
    assert run._plain(float("nan")) == "nan" and run._plain(0.5) == 0.5
