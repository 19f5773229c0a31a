"""Device time per step of the expert layer's grouped products over the ragged
assignment: the kernels XLA lowers ``lax.ragged_dot`` to (custom calls named
``ragged-dot-...``, which carry that name and not the program's scope) and what
else lies under the scope ``moe_experts`` (the experts' casts, SiLU and
product), forward, recomputed and backward. Median over the traced steps,
chip 0. Source: device trace, groups ``moe_experts_kernel``,
``moe_experts_fwd`` and ``moe_experts_bwd`` of ``scope_groups/<family>.json``."""

from benchmark import scope_reduce

GROUPS = ("moe_experts_kernel", "moe_experts_fwd", "moe_experts_bwd")


def compute(run):
    result = scope_reduce.of_run(run)
    if result is None or not all(g in result["groups_ms"] for g in GROUPS):
        return None
    return sum(result["groups_ms"][g] for g in GROUPS)
