"""Device time per step of the exchange's copies: ``pack``, ``unpack`` and
every other operation under any ``hvd_exchange`` scope that is not a
collective (those are ``grad_collective_ms``). Median over the traced steps,
chip 0. Source: device trace, group ``exchange_copy`` of
``scope_groups/<family>.json``."""

from benchmark import scope_reduce


def compute(run):
    return scope_reduce.group_ms(run, "exchange_copy")
