"""The seam between a model family and the train step.

A family is what differs between the networks this trainer runs:

    init(rng)                                -> (params, batch_stats)
    loss_fn(params, batch_stats, batch, rng) -> (loss, (metrics, new_stats))
    augment(batch, rng)                      -> batch   (on device, in the step)
    grad_metrics(grads)                      -> dict    (read before the clip)

Everything after it is shared (train/state.py, train/step.py): the
jitted init with the optimizer state, value-and-grad, microbatch
accumulation, clip + AdamW on the OneCycle schedule, fp32 masters under
the bf16 policy, the `all_finite` verdict, donation and the shardings.
`family_of(cfg, tc)` picks by the config's type: a `RAFTConfig` gives
RAFT v1-v5 (below), a `DecoderConfig` the language model
(models/lm/family.py, imported only then).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from dexiraft_tpu.config import DecoderConfig, RAFTConfig, TrainConfig

Batch = Dict[str, jax.Array]


def family_of(cfg: Any, tc: TrainConfig):
    if isinstance(cfg, DecoderConfig):
        from dexiraft_tpu.models.lm.family import LMFamily

        return LMFamily(cfg, tc)
    return RaftFamily(cfg, tc)


def cast_floating(tree: Any, dtype: Any) -> Any:
    """Cast every floating leaf of a pytree to dtype; leave the rest alone."""
    def cast(x):
        x = jnp.asarray(x)
        return x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x
    return jax.tree.map(cast, tree)


def model_inputs_shape(
    cfg: RAFTConfig, batch: int, image_size: Tuple[int, int]
) -> Tuple[Tuple[int, ...], Optional[Tuple[int, ...]]]:
    """(image shape, edge-image shape or None) for init/dummy batches."""
    h, w = image_size
    img = (batch, h, w, 3)
    edges = (batch, h, w, 3) if (cfg.variant in ("early", "separate") and not cfg.embed_dexined) else None
    return img, edges


def thread_remat(cfg: RAFTConfig, tc: TrainConfig) -> RAFTConfig:
    """The TrainConfig remat axis in the model config: both checkpointing
    modes wrap the scanned iteration; the policy decides what the
    checkpoint saves (config.py remat_policy)."""
    if tc.remat == "none":
        return cfg
    return dataclasses.replace(
        cfg, remat=True,
        remat_policy=("dots_saveable" if tc.remat == "dots_saveable"
                      else "full"))


def _add_noise(rng: jax.Array, stdv: jax.Array, image: jax.Array) -> jax.Array:
    """Gaussian noise at the given stdv, clipped to [0,255] (train.py:170-173);
    the reference draws ONE stdv ~ U(0,5) shared by both frames."""
    noisy = image + stdv * jax.random.normal(rng, image.shape, jnp.float32)
    return jnp.clip(noisy, 0.0, 255.0)


class RaftFamily:
    """RAFT v1-v5: `RAFT(cfg)` under `sequence_loss`, batches of
    image1, image2, flow, valid [, edges1, edges2]."""

    def __init__(self, cfg: RAFTConfig, tc: TrainConfig):
        from dexiraft_tpu.models.raft import RAFT

        cfg = thread_remat(cfg, tc)
        # bf16 training policy: force the MODEL's mixed-precision path —
        # module compute dtype becomes bf16, so flax casts each op's params
        # from the fp32 masters per use (autodiff transposes the casts and
        # the gradients land back fp32), activations are genuinely bf16, and
        # the corr volume stays fp32 by the model's own mixed-precision
        # contract. Everything after the model — loss, metrics, BN running
        # stats, optimizer — stays fp32. No loss scaling: bf16 shares fp32's
        # exponent range (README design note). NOTE a hand-cast of params /
        # inputs here would NOT work: RAFT.__call__ re-casts inputs fp32 and
        # derives its compute dtype from cfg.mixed_precision alone.
        self.bf16 = tc.precision == "bf16"
        if self.bf16 and not cfg.mixed_precision:
            cfg = dataclasses.replace(cfg, mixed_precision=True)
        if tc.edge_sum_fusion and (cfg.variant != "raft" or cfg.embed_dexined):
            raise ValueError(
                "edge_sum_fusion is the v1 (plain 'raft') training fusion — "
                "the model itself consumes edges in the other variants")
        self.cfg, self.tc = cfg, tc
        self.model = RAFT(cfg)

    def init(self, rng: jax.Array, batch_size: int = 1,
             image_size: Tuple[int, int] = (64, 64)) -> Tuple[Any, Any]:
        """Init runs on small dummy shapes — RAFT is fully convolutional,
        so parameters are shape-independent of the training resolution."""
        img_shape, edge_shape = model_inputs_shape(
            self.cfg, batch_size, image_size)
        dummy = jnp.zeros(img_shape, jnp.float32)
        kwargs = {}
        if edge_shape is not None:
            e = jnp.zeros(edge_shape, jnp.float32)
            kwargs = dict(edges1=e, edges2=e)
        variables = self.model.init(rng, dummy, dummy, iters=1, train=False,
                                    **kwargs)
        return variables["params"], variables.get("batch_stats", {})

    def augment(self, batch: Batch, rng: jax.Array) -> Batch:
        if not self.tc.add_noise:
            return batch
        k_stdv, k1, k2 = jax.random.split(rng, 3)
        stdv = jax.random.uniform(k_stdv, (), jnp.float32, 0.0, 5.0)
        batch = dict(batch)
        batch["image1"] = _add_noise(k1, stdv, batch["image1"])
        batch["image2"] = _add_noise(k2, stdv, batch["image2"])
        return batch

    def grad_metrics(self, grads: Any) -> Dict[str, jax.Array]:
        return {}

    def loss_fn(self, params: Any, batch_stats: Any, batch: Batch,
                rng: jax.Array):
        from dexiraft_tpu.ops.losses import sequence_loss

        tc, model = self.tc, self.model

        def fwd(stats, drop_rng, im1, im2, **kw):
            return model.apply(
                {"params": params, "batch_stats": stats},
                im1, im2, iters=tc.iters, train=True,
                freeze_bn=tc.freeze_bn, mutable=["batch_stats"],
                rngs={"dropout": drop_rng}, **kw,
            )

        if tc.edge_sum_fusion:
            if "edges1" not in batch:
                raise ValueError("edge_sum_fusion needs edge-pair data "
                                 "(edge_root)")
            # v1-lineage summed fusion (alt/train_1.py:173-176): same
            # model on the image pair and the edge-image pair, per-iter
            # predictions summed; BN stats update through both passes
            # sequentially, and each pass draws independent dropout masks
            # like the reference's two separate forward calls
            rng_img, rng_edge = jax.random.split(rng)
            img_flow, mut1 = fwd(batch_stats, rng_img,
                                 batch["image1"], batch["image2"])
            edge_flow, mut2 = fwd(mut1.get("batch_stats", batch_stats),
                                  rng_edge,
                                  batch["edges1"], batch["edges2"])
            outputs = img_flow + edge_flow
            mutated = mut2
        else:
            kwargs: Dict[str, Any] = {}
            if "edges1" in batch:
                kwargs = dict(edges1=batch["edges1"], edges2=batch["edges2"])
            outputs, mutated = fwd(batch_stats, rng, batch["image1"],
                                   batch["image2"], **kwargs)
        new_stats = mutated.get("batch_stats", batch_stats)
        if self.bf16:
            # fp32 loss/metrics and fp32 carried state, whatever dtype
            # the bf16 forward emitted
            outputs = outputs.astype(jnp.float32)
            new_stats = cast_floating(new_stats, jnp.float32)
        loss, metrics = sequence_loss(outputs, batch["flow"], batch["valid"],
                                      tc.gamma)
        return loss, (metrics, new_stats)
