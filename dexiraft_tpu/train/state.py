"""Train state: params + batch stats + optimizer state + step + PRNG key.

One pytree that the jitted step consumes and returns. Unlike the reference
(which checkpoints only model weights, train.py:189-190 — optimizer and
schedule restart on resume, SURVEY.md §5), the full state here round-trips
through checkpoints.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import optax

from dexiraft_tpu.config import RAFTConfig, TrainConfig
from dexiraft_tpu.models.raft import RAFT


@flax.struct.dataclass
class TrainState:
    """Dtype contract: `params`, `opt_state`, and `batch_stats` are fp32
    REGARDLESS of TrainConfig.precision — under the bf16 policy the model
    runs its mixed-precision path and flax casts per-op bf16 copies from
    the fp32 masters here, which are what the optimizer updates and
    checkpoints serialize. Checkpoints are therefore precision-portable:
    a run can switch policy on resume."""

    step: jax.Array  # scalar int32
    params: Any
    batch_stats: Any  # BatchNorm running stats ({} when encoders have none)
    opt_state: Any
    rng: jax.Array  # PRNG key threaded through steps (dropout / noise aug)

    @property
    def variables(self):
        return {"params": self.params, "batch_stats": self.batch_stats}


def model_inputs_shape(
    cfg: RAFTConfig, batch: int, image_size: Tuple[int, int]
) -> Tuple[Tuple[int, ...], Optional[Tuple[int, ...]]]:
    """(image shape, edge-image shape or None) for init/dummy batches."""
    h, w = image_size
    img = (batch, h, w, 3)
    edges = (batch, h, w, 3) if (cfg.variant in ("early", "separate") and not cfg.embed_dexined) else None
    return img, edges


def create_state(
    rng: jax.Array,
    cfg: RAFTConfig,
    tc: TrainConfig,
    batch_size: Optional[int] = None,
    image_size: Optional[Tuple[int, int]] = None,
) -> TrainState:
    """Initialize params (Kaiming/Xavier per module) and optimizer state.

    Init runs on small dummy shapes — RAFT is fully convolutional, so
    parameters are shape-independent of the training resolution — and
    as ONE jitted program: eagerly it is a compile per distinct op and
    shape (some 1,200 of them for v5), minutes of set-up on a cold chip.
    """
    model = RAFT(cfg)
    bs = batch_size if batch_size is not None else 1
    init_size = image_size if image_size is not None else (64, 64)
    img_shape, edge_shape = model_inputs_shape(cfg, bs, init_size)
    tx = make_optimizer_from(tc)

    def init(rng: jax.Array) -> TrainState:
        init_rng, state_rng = jax.random.split(rng)
        dummy = jnp.zeros(img_shape, jnp.float32)
        kwargs = {}
        if edge_shape is not None:
            e = jnp.zeros(edge_shape, jnp.float32)
            kwargs = dict(edges1=e, edges2=e)
        variables = model.init(init_rng, dummy, dummy, iters=1, train=False,
                               **kwargs)
        params = variables["params"]
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            batch_stats=variables.get("batch_stats", {}),
            opt_state=tx.init(params),
            rng=state_rng,
        )

    return jax.jit(init)(rng)


def make_optimizer_from(tc: TrainConfig) -> optax.GradientTransformation:
    from dexiraft_tpu.train.optimizer import make_optimizer

    return make_optimizer(tc.lr, tc.num_steps, tc.wdecay, tc.epsilon, tc.clip)


def param_count(params: Any) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))
