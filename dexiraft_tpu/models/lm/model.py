"""The decoder stack and its loss, for both configurations of
`config.LM_CONFIGS`.

    LMConfig:     x0 = E[id]
                  h = x + Attn(N1(x));       x' = h + FFN(N2(h))
    AfmoeConfig:  x0 = E[id] * sqrt(hidden_size)            (mup_enabled)
                  h = x + N2(Attn_l(N1(x))); x' = h + N4(FFN(N3(h)))
    logits = W_head RMSNorm(x_last)           (untied)

`Attn` is the configuration's mixer (models/lm/attention.py `mixer_of`;
an `AfmoeConfig`'s `layer_types` make layer `l`'s a sliding-window or a
full one). FFN is a SwiGLU of `intermediate_size` in the first
`first_k_dense_replace` layers and the expert layer after them. Under
`cfg.remat` every layer is a `jax.checkpoint` that keeps nothing: the
backward holds one layer's activations at a time.

The loss is the mean cross-entropy over next-token targets that lie in
the same document as their input (a packed row holds several; pad has
segment id 0). The head and the loss run a block of `HEAD_BLOCK`
positions at a time under a `jax.checkpoint`, so `[HEAD_BLOCK, vocab]`
fp32 logits exist for one block only, in the forward and in the
backward (a row of 32,768 positions over 25,024 vocabulary rows would be
3.28 GB, twice over in the backward).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from dexiraft_tpu.config import AfmoeConfig
from dexiraft_tpu.models.lm.attention import mixer_of
from dexiraft_tpu.models.lm.layers import SwiGLU, Weights, rms_norm
from dexiraft_tpu.models.lm.moe import MoE
from dexiraft_tpu.ops.lm_attention import block_pair_counts, kernel_blocks

# an `LMConfig`'s layers are of one kind (`attn_block_pairs_visited`, of a
# layer); an `AfmoeConfig`'s are of two, each summed over its layers
COUNTERS = ("moe_slots_held", "moe_load_max", "moe_load_mean",
            "moe_dropped_slots", "attn_block_pairs_visited",
            "attn_block_pairs_visited_window", "attn_block_pairs_visited_full",
            "attn_block_pairs_causal")
# positions a block of `head_loss`; a row that is not whole blocks is one
HEAD_BLOCK = 8192


def _gain(module: nn.Module, name: str, width: int) -> jax.Array:
    return module.param(name, nn.initializers.ones, (width,), jnp.float32)


class DecoderLayer(Weights):
    cfg: Any = None  # one of config.LM_CONFIGS
    index: int = 0   # of the layers held

    @nn.compact
    def __call__(self, x, positions, segment_ids):
        cfg = self.cfg
        kw = dict(dtype=self.dtype, init_std=self.init_std)

        def norm(name, t):
            with jax.named_scope("lm/norm"):
                return rms_norm(t, _gain(self, name, t.shape[-1]),
                                cfg.rms_norm_eps)

        # an AfmoeConfig's layer also norms what each half adds
        sandwich = isinstance(cfg, AfmoeConfig)
        out = mixer_of(cfg, self.index, **kw)(
            norm("attn_norm", x), positions, segment_ids)
        h = x + (norm("attn_post_norm", out) if sandwich else out)
        normed = norm("ffn_norm", h)
        if self.index < cfg.first_k_dense_replace:
            with jax.named_scope("lm/mlp"):
                out = SwiGLU(width=cfg.intermediate_size, name="mlp",
                             **kw)(normed)
            counters = {}
        else:
            out, counters = MoE(cfg=cfg, name="moe", **kw)(normed)
        return h + (norm("ffn_post_norm", out) if sandwich else out), counters


class LM(nn.Module):
    """tokens, positions, segment_ids `[B, S]` int32 and `targets`
    (`next_token_targets`' pair) -> (the sum of the targets'
    cross-entropies, the expert layers' counters). `logits=True` gives
    `[B, S, vocab]` logits instead, for tests: the train path never
    holds them."""

    cfg: Any  # one of config.LM_CONFIGS

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
            if isinstance(cfg, AfmoeConfig) and cfg.mup_enabled:
                x = x * jnp.asarray(cfg.hidden_size ** 0.5, dtype)
        layer_cls = (nn.remat(DecoderLayer, prevent_cse=True)
                     if cfg.remat else DecoderLayer)
        per_layer = []
        for i in range(cfg.num_hidden_layers):
            x, counters = layer_cls(cfg=cfg, index=i, name=f"layers_{i}",
                                    **kw)(x, positions, segment_ids)
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


def _attention_counters(cfg, segment_ids: jax.Array) -> Dict[str, jax.Array]:
    """The block pairs the attention kernel's grid computes for this
    batch and those of a layer's causal triangle, from the table the
    kernel is handed (every layer sees the same documents). An
    `LMConfig`: of a layer. An `AfmoeConfig`: by the layers' kind, each
    summed over the layers of the kind. Where the kernel does not take
    the shapes, the one block the XLA path's mask covers."""
    seq = segment_ids.shape[1]
    blocks = kernel_blocks(seq, cfg.qk_head_dim, cfg.v_head_dim) or (seq, seq)
    visited, causal = block_pair_counts(segment_ids, *blocks)
    if not isinstance(cfg, AfmoeConfig):
        return {"attn_block_pairs_visited": visited,
                "attn_block_pairs_causal": causal}
    sliding = sum(k == "sliding_attention" for k in cfg.layer_types)
    windowed, _ = block_pair_counts(segment_ids, *blocks, cfg.sliding_window)
    return {"attn_block_pairs_visited_window": windowed * sliding,
            "attn_block_pairs_visited_full":
                visited * (len(cfg.layer_types) - sliding),
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
              weight: jax.Array, block: int = HEAD_BLOCK) -> jax.Array:
    """Sum over the batch of weight * cross-entropy, and nothing else:
    the caller divides by the number of targets. Logits, log-sum-exp and
    the sum are fp32. x `[B, S, D]`, walked `block` positions at a time
    (a row that is not whole blocks: a row at a time)."""
    seq = x.shape[1]
    if seq % block == 0 and seq != block:
        x = x.reshape(-1, block, x.shape[-1])
        targets, weight = (t.reshape(-1, block) for t in (targets, weight))

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
