"""Flow training losses and metrics.

``sequence_loss`` reproduces the reference's gamma-weighted L1 over all
refinement iterations (train.py:48-73), including its exact masking
semantics: invalid pixels are zeroed but still counted in the mean.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

MAX_FLOW = 400.0


def flow_metrics(flow_pred: jax.Array, flow_gt: jax.Array, valid: jax.Array) -> Dict[str, jax.Array]:
    """End-point-error stats over valid pixels.

    flow_pred/flow_gt: (B, H, W, 2); valid: (B, H, W) boolean.
    Matches train.py:63-71 (masked mean EPE and <1/3/5 px rates).
    """
    epe = jnp.sqrt(jnp.sum((flow_pred - flow_gt) ** 2, axis=-1))
    v = valid.astype(jnp.float32)
    denom = jnp.maximum(v.sum(), 1.0)

    def masked_mean(x):
        return jnp.sum(x * v) / denom

    return {
        "epe": masked_mean(epe),
        "1px": masked_mean((epe < 1.0).astype(jnp.float32)),
        "3px": masked_mean((epe < 3.0).astype(jnp.float32)),
        "5px": masked_mean((epe < 5.0).astype(jnp.float32)),
    }


@jax.named_scope("sequence_loss")
def sequence_loss(
    flow_preds: jax.Array,
    flow_gt: jax.Array,
    valid: jax.Array,
    gamma: float = 0.8,
    max_flow: float = MAX_FLOW,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Exponentially weighted L1 loss over the iteration sequence.

    flow_preds: (iters, B, H, W, 2) — the stacked per-iteration upsampled
    flows (the reference's python list, train.py:51).
    flow_gt: (B, H, W, 2); valid: (B, H, W) float or bool.

    Weight for prediction i of n is gamma**(n-1-i) (train.py:58-61); the
    per-iteration term is mean over *all* pixels with invalid ones zeroed —
    NOT a masked mean — matching train.py:61 exactly.
    """
    n = flow_preds.shape[0]
    mag = jnp.sqrt(jnp.sum(flow_gt**2, axis=-1))
    valid_mask = (valid >= 0.5) & (mag < max_flow)
    vf = valid_mask.astype(jnp.float32)[None, ..., None]  # (1, B, H, W, 1)

    weights = gamma ** jnp.arange(n - 1, -1, -1, dtype=jnp.float32)  # (n,)
    i_loss = jnp.abs(flow_preds - flow_gt[None])
    per_iter = jnp.mean(vf * i_loss, axis=(1, 2, 3, 4))  # (n,)
    flow_loss = jnp.sum(weights * per_iter)

    metrics = flow_metrics(flow_preds[-1], flow_gt, valid_mask)
    return flow_loss, metrics
