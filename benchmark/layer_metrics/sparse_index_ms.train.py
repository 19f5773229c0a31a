"""Device time per step of the sparse selection in front of attention (scope
``sparse_index``: the indexer's three projections, its key's norm and rotary,
the scores over the causal pairs, the search for each row's k-th value and the
selection's mask), first forward pass, recomputation and the projections'
transposes. Median over the traced steps, chip 0. Source: device trace, groups
``sparse_index_fwd`` and ``sparse_index_bwd`` of ``scope_groups/<family>.json``."""

from benchmark import scope_reduce

GROUPS = ("sparse_index_fwd", "sparse_index_bwd")


def compute(run):
    result = scope_reduce.of_run(run)
    if result is None or not all(g in result["groups_ms"] for g in GROUPS):
        return None
    return sum(result["groups_ms"][g] for g in GROUPS)
