"""Device time per step of the expert layer's routing: the router's float32
product and softmax, top-k, the sort of the (token, expert) pairs, the gather
of their rows and the weighted scatter-add back, in both directions (scope
``moe_route``). Median over the traced steps, chip 0. Source: device trace,
group ``moe_route`` of ``scope_groups/<family>.json``."""

from benchmark import scope_reduce


def compute(run):
    result = scope_reduce.of_run(run)
    if result is None:
        return None
    return result["groups_ms"].get("moe_route")
