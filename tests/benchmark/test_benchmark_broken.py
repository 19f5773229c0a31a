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
