"""Device time per step of the head and the loss: ``lm_head`` forward and
backward, and what lies in ``hvd_loss_grad`` under no module (the softmax
cross-entropy). Median over the traced steps, chip 0. Source: device trace,
group ``head_loss`` of ``scope_groups/<family>.json``."""

from benchmark import scope_reduce


def compute(run):
    return scope_reduce.group_ms(run, "head_loss")
