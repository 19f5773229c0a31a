"""Device time per step of the latent-attention mixer OUTSIDE the flash
kernels (scope ``latent_attn``, after the kernels' own groups): the four
low-rank products, the two latent norms, rotary, the assembly of k from
``k_nope`` and the one broadcast ``k_rope`` head, and ``W_o``, in both
directions. Median over the traced steps, chip 0. Source: device trace, group
``latent_attn`` of ``scope_groups/<family>.json``."""

from benchmark import scope_reduce


def compute(run):
    result = scope_reduce.of_run(run)
    if result is None:
        return None
    return result["groups_ms"].get("latent_attn")
