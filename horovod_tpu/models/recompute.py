"""What a layer that is recomputed in its backward keeps.

The seven language models wrap their layer in :func:`remat_layer`. A layer's
activations are computed again in its backward, all but the residuals an op
has NAMED because computing them again is a kernel call and keeping them is
little: the forward flash kernel's result and logsumexp (``[B, T, H * d_v]``
in the compute dtype and ``[B * H, T]`` float32 an attention layer, one to
two times the residual stream's bytes) and the indexer objective's gradient
(some 36 MB a layer at 16384 positions). A layer that calls no such op has
nothing under those names, and keeps nothing."""

from __future__ import annotations

import flax.linen as nn
import jax

from .. import trace as _trace
from ..ops.pallas_attention import FLASH_RESIDUALS
from ..ops.sparse_index import KL_RESIDUALS

KEEPS = (*FLASH_RESIDUALS, KL_RESIDUALS)


def remat_layer(module):
    """``nn.remat(module)`` that keeps every named residual of :data:`KEEPS`
    (plan note ``layer_recompute_keeps``)."""
    _trace.note_plan(layer_recompute_keeps=KEEPS)
    return nn.remat(
        module,
        policy=jax.checkpoint_policies.save_only_these_names(*KEEPS))
