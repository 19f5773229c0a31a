"""Device time per step of the indexer's objective (scope
``sparse_index_loss``: the attention heads' probabilities summed over the
heads on the selected keys, the KL term against the indexer's softmax, and the
products that carry its gradient into qI, kI and w), in every direction.
Median over the traced steps, chip 0. Source: device trace, group
``sparse_index_loss`` of ``scope_groups/<family>.json``."""

from benchmark import scope_reduce


def compute(run):
    result = scope_reduce.of_run(run)
    if result is None:
        return None
    return result["groups_ms"].get("sparse_index_loss")
