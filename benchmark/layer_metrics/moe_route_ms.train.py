"""Device time per step of the expert layer's routing: the router's float32
product and scores, top-k, the sort of the (token, expert) pairs and its
inverse, the gather of their rows, and the gather-sum that brings a token's
rows back (the Pallas kernel ``moe_combine.<n>`` with the relayout before it;
the kernel's own part is ``moe_route_kernel_ms.train``), in both directions
(scope ``moe_route``). Median over the traced steps, chip 0. Source: device
trace, group ``moe_route`` of ``scope_groups/<family>.json``."""

from benchmark import scope_reduce


def compute(run):
    result = scope_reduce.of_run(run)
    if result is None:
        return None
    return result["groups_ms"].get("moe_route")
