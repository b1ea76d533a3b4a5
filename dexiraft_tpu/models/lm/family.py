"""The language model's side of the train seam (train/family.py):
`init(rng)` and `loss_fn(params, batch_stats, batch, rng)` over batches
of `tokens`, `positions`, `segment_ids` (each `[B, S]` int32:
data/tokens.py). Everything after them, from value-and-grad to the
shardings, is the shared step."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import optax

from dexiraft_tpu.config import TrainConfig
from dexiraft_tpu.models.lm.model import LM, next_token_targets


class LMFamily:
    def __init__(self, cfg: Any, tc: TrainConfig):
        """cfg: a config.DecoderConfig."""
        if tc.remat == "dots_saveable":
            raise ValueError(
                "remat='dots_saveable' is a policy of RAFT's refinement "
                "loop; the language model recomputes whole layers "
                "(remat='per_iter', or cfg.remat) or nothing")
        for flag in ("add_noise", "edge_sum_fusion"):
            if getattr(tc, flag):
                raise ValueError(f"{flag} is an image augmentation: "
                                 "not for a language model")
        self.cfg = dataclasses.replace(
            cfg,
            mixed_precision=cfg.mixed_precision or tc.precision == "bf16",
            remat=cfg.remat or tc.remat == "per_iter")
        self.model = LM(self.cfg)

    def init(self, rng: jax.Array) -> Tuple[Any, Any]:
        """(params, batch_stats). No parameter's shape depends on the
        sequence length, so a short dummy row does."""
        dummy = jnp.zeros((1, min(8, self.cfg.seq_len)), jnp.int32)
        variables = self.model.init(rng, dummy, dummy, dummy + 1, logits=True)
        return variables["params"], variables.get("batch_stats", {})

    def augment(self, batch: Dict[str, jax.Array], rng: jax.Array):
        return batch

    def grad_metrics(self, grads: Any) -> Dict[str, jax.Array]:
        """The norm the clip divides by: the one reading of the gradients
        that leaves the step (XLA shares it with the clip's own)."""
        return {"grad_norm": optax.global_norm(grads)}

    def loss_fn(self, params: Any, batch_stats: Any,
                batch: Dict[str, jax.Array], rng: jax.Array):
        tokens, seg = batch["tokens"], batch["segment_ids"]
        targets, weight = next_token_targets(tokens, seg,
                                             self.cfg.num_pred_heads)
        total, counters = self.model.apply(
            {"params": params, "batch_stats": batch_stats},
            tokens, batch["positions"], seg, targets=(targets, weight))
        n_targets = jnp.sum(weight)
        loss = total / jnp.maximum(n_targets, 1.0)
        metrics = dict(counters, tokens_real=jnp.sum(seg > 0),
                       targets=n_targets)
        # `b` is read, never written: the stats go back as they came
        return loss, (metrics, batch_stats)
