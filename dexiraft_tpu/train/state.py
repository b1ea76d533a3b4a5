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

from dexiraft_tpu.config import TrainConfig
from dexiraft_tpu.train.family import family_of


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


def create_state(
    rng: jax.Array,
    cfg: Any,
    tc: TrainConfig,
    batch_size: Optional[int] = None,
    image_size: Optional[Tuple[int, int]] = None,
) -> TrainState:
    """Initialize params (the family's own init, train/family.py) and
    optimizer state, as ONE jitted program: eagerly it is a compile per
    distinct op and shape (some 1,200 of them for v5), minutes of set-up
    on a cold chip. `batch_size` / `image_size` size RAFT's dummy init
    batch; no parameter's shape depends on them.
    """
    family = family_of(cfg, tc)
    sizes = {k: v for k, v in (("batch_size", batch_size),
                               ("image_size", image_size)) if v is not None}
    tx = make_optimizer_from(tc)

    def init(rng: jax.Array) -> TrainState:
        init_rng, state_rng = jax.random.split(rng)
        params, batch_stats = family.init(init_rng, **sizes)
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            batch_stats=batch_stats,
            opt_state=tx.init(params),
            rng=state_rng,
        )

    return jax.jit(init)(rng)


def make_optimizer_from(tc: TrainConfig) -> optax.GradientTransformation:
    from dexiraft_tpu.train.optimizer import make_optimizer

    return make_optimizer(tc.lr, tc.num_steps, tc.wdecay, tc.epsilon, tc.clip)


def param_count(params: Any) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))
