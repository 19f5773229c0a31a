"""Device time per step of the optimizer update: the ``hvd_optimizer`` scope
of the step builders, outside any ``hvd_exchange`` (an exchange a wrapper
makes inside it, where the step could not open the wrapper, is wire, not
update) and any ``hvd_guard``.
Median over the traced steps, chip 0. Source: device trace, group
``optimizer`` of ``scope_groups/<family>.json``."""

from benchmark import scope_reduce


def compute(run):
    return scope_reduce.group_ms(run, "optimizer")
