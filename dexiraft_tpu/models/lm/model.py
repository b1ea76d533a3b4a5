"""The decoder stack and its loss.

    x0 = E[id];   h = x + Attn(RMSNorm(x));   x' = h + FFN(RMSNorm(h))
    logits = W_head RMSNorm(x_last)           (untied)

FFN is a SwiGLU of `intermediate_size` in the first
`first_k_dense_replace` layers and the expert layer after them. Under
`cfg.remat` every layer is a `jax.checkpoint` that keeps nothing: the
backward holds one layer's activations at a time.

The loss is the mean cross-entropy over next-token targets that lie in
the same document as their input (a packed row holds several; pad has
segment id 0). The head and the loss run a row of the batch at a time
under a `jax.checkpoint`, so `[S, vocab]` fp32 logits exist for one row
only, in the forward and in the backward.
"""

from __future__ import annotations

from typing import Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from dexiraft_tpu.config import LMConfig
from dexiraft_tpu.models.lm.attention import LatentAttention
from dexiraft_tpu.models.lm.layers import SwiGLU, Weights, rms_norm
from dexiraft_tpu.models.lm.moe import MoE
from dexiraft_tpu.ops.lm_attention import block_pair_counts, kernel_blocks

COUNTERS = ("moe_slots_held", "moe_load_max", "moe_load_mean",
            "moe_dropped_slots", "attn_block_pairs_visited",
            "attn_block_pairs_causal")


def _gain(module: nn.Module, name: str, width: int) -> jax.Array:
    return module.param(name, nn.initializers.ones, (width,), jnp.float32)


class DecoderLayer(Weights):
    cfg: LMConfig = None
    dense: bool = False

    @nn.compact
    def __call__(self, x, positions, segment_ids):
        cfg = self.cfg
        kw = dict(dtype=self.dtype, init_std=self.init_std)
        d = x.shape[-1]
        with jax.named_scope("lm/norm"):
            normed = rms_norm(x, _gain(self, "attn_norm", d),
                              cfg.rms_norm_eps)
        h = x + LatentAttention(cfg=cfg, name="attn", **kw)(
            normed, positions, segment_ids)
        with jax.named_scope("lm/norm"):
            normed = rms_norm(h, _gain(self, "ffn_norm", d),
                              cfg.rms_norm_eps)
        if self.dense:
            with jax.named_scope("lm/mlp"):
                out = SwiGLU(width=cfg.intermediate_size, name="mlp",
                             **kw)(normed)
            return h + out, {}
        out, counters = MoE(cfg=cfg, name="moe", **kw)(normed)
        return h + out, counters


class LM(nn.Module):
    """tokens, positions, segment_ids `[B, S]` int32 and `targets`
    (`next_token_targets`' pair) -> (the sum of the targets'
    cross-entropies, the expert layers' counters). `logits=True` gives
    `[B, S, vocab]` logits instead, for tests: the train path never
    holds them."""

    cfg: LMConfig

    @nn.compact
    def __call__(self, tokens, positions, segment_ids, *, targets=None,
                 logits: bool = False):
        cfg = self.cfg
        dtype = jnp.bfloat16 if cfg.mixed_precision else jnp.float32
        kw = dict(dtype=dtype, init_std=cfg.init_std)
        embed = self.param("embed", nn.initializers.normal(cfg.init_std),
                           (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        with jax.named_scope("lm/embed"):
            x = embed.astype(dtype)[tokens]
        layer_cls = (nn.remat(DecoderLayer, prevent_cse=True)
                     if cfg.remat else DecoderLayer)
        per_layer = []
        for i in range(cfg.num_hidden_layers):
            x, counters = layer_cls(
                cfg=cfg, dense=i < cfg.first_k_dense_replace,
                name=f"layers_{i}", **kw)(x, positions, segment_ids)
            if counters:
                per_layer.append(counters)
        with jax.named_scope("lm/norm"):
            x = rms_norm(x, _gain(self, "final_norm", cfg.hidden_size),
                         cfg.rms_norm_eps)
        head = self.param("head", nn.initializers.normal(cfg.init_std),
                          (cfg.hidden_size, cfg.vocab_size),
                          jnp.float32).astype(dtype)
        counters = dict(_reduce_counters(per_layer),
                        **_attention_counters(cfg, segment_ids))
        if logits:
            return jnp.matmul(x, head,
                              preferred_element_type=jnp.float32), counters
        return head_loss(x, head, *targets), counters


def _reduce_counters(per_layer) -> Dict[str, jax.Array]:
    """Over the expert layers: slots and drops summed, the fullest
    expert's load, the mean load."""
    if not per_layer:
        return {}
    stack = {k: jnp.stack([c[k] for c in per_layer]) for k in per_layer[0]}
    return {
        "moe_slots_held": jnp.sum(stack["moe_slots_held"]),
        "moe_load_max": jnp.max(stack["moe_load_max"]),
        "moe_load_mean": jnp.mean(stack["moe_load_mean"]),
        "moe_dropped_slots": jnp.sum(stack["moe_dropped_slots"]),
    }


def _attention_counters(cfg: LMConfig, segment_ids: jax.Array
                        ) -> Dict[str, jax.Array]:
    """Of a layer (every layer sees the same documents): the block pairs
    the attention kernel's grid computes for this batch and those of its
    causal triangle, from the table the kernel is handed. Where the
    kernel does not take the shapes, the one block the XLA path's mask
    covers."""
    seq = segment_ids.shape[1]
    blocks = kernel_blocks(seq, cfg.qk_head_dim, cfg.v_head_dim) or (seq, seq)
    visited, causal = block_pair_counts(segment_ids, *blocks)
    return {"attn_block_pairs_visited": visited,
            "attn_block_pairs_causal": causal}


def next_token_targets(tokens: jax.Array, segment_ids: jax.Array
                       ) -> Tuple[jax.Array, jax.Array]:
    """(targets `[B, S]`, weight `[B, S]` fp32): position t predicts
    token t+1 where both lie in one document; a row's last position,
    a document's last token and pad predict nothing."""
    nxt = jnp.roll(tokens, -1, axis=1)
    same = (jnp.roll(segment_ids, -1, axis=1) == segment_ids) & (segment_ids > 0)
    same = same.at[:, -1].set(False)
    return nxt, same.astype(jnp.float32)


def head_loss(x: jax.Array, head: jax.Array, targets: jax.Array,
              weight: jax.Array) -> jax.Array:
    """Sum over the batch of weight * cross-entropy, and nothing else:
    the caller divides by the number of targets. Logits, log-sum-exp and
    the sum are fp32."""

    @jax.checkpoint
    def row(carry, xs):
        h, tgt, wt = xs
        with jax.named_scope("lm/head_loss"):
            logits = jnp.matmul(h, head, preferred_element_type=jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, tgt[:, None], axis=-1)[:, 0]
            return carry + jnp.sum((lse - picked) * wt), None

    total, _ = jax.lax.scan(row, jnp.zeros((), jnp.float32),
                            (x, targets, weight))
    return total
