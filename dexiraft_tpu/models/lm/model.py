"""The decoder stack and its loss, for the configurations of
`config.LM_CONFIGS`.

    LMConfig:       x0 = E[id]
                    h = x + Attn(N1(x));       x' = h + FFN(N2(h))
    AfmoeConfig:    x0 = E[id] * sqrt(hidden_size)            (mup_enabled)
                    h = x + N2(Attn_l(N1(x))); x' = h + N4(FFN(N3(h)))
    EvaByteConfig:  x0 = E[byte];  LMConfig's layer with every FFN dense,
                    the norms' gains `1 + g` (norm_add_unit_offset) and
                    the two sums in fp32 (fp32_skip_add)
    Lfm2MoeConfig:  x0 = E[id];  LMConfig's layer, the mixer of layer l
                    a short convolution or an attention (`layer_types`)
    logits = W_head RMSNorm(x_last)           (untied; an EvaByteConfig's
                                               head has `num_pred_heads`
                                               vocabularies of columns;
                                               under `tie_embedding`
                                               W_head is E's transpose and
                                               no parameter of its own)

`Attn` is the configuration's mixer (models/lm/attention.py `mixer_of`;
an `AfmoeConfig`'s `layer_types` make layer `l`'s a sliding-window or a
full one, an `Lfm2MoeConfig`'s a convolution or an attention). FFN is a
SwiGLU of `intermediate_size` in the first `first_k_dense_replace`
layers and the expert layer after them. Under `cfg.remat` every layer is
a `jax.checkpoint` that keeps nothing: the backward holds one layer's
activations at a time.

The loss is the mean cross-entropy over next-token targets that lie in
the same document as their input (a packed row holds several; pad has
segment id 0); an `EvaByteConfig`'s head `j` of `num_pred_heads`
predicts the token `1 + j` ahead, and the mean is over heads and
positions alike. The head and the loss run a block of `HEAD_BLOCK`
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

from dexiraft_tpu.config import AfmoeConfig, EvaByteConfig
from dexiraft_tpu.models.lm.attention import mixer_of
from dexiraft_tpu.models.lm.layers import SwiGLU, Weights, rms_norm
from dexiraft_tpu.models.lm.moe import MoE
from dexiraft_tpu.ops.lm_attention import block_pair_counts, kernel_blocks
from dexiraft_tpu.ops.lm_conv import taps_masked
from dexiraft_tpu.ops.lm_eva import local_ids, pair_counts

# an `LMConfig`'s layers are of one kind (`attn_block_pairs_visited`, of a
# layer); an `AfmoeConfig`'s are of two, each summed over its layers; an
# `EvaByteConfig`'s are summed over its layers too, and carry the pairs
# the batch needs beside the block pairs the kernel visits; an
# `Lfm2MoeConfig`'s attention layers are full ones, and its convolution
# layers count the taps their mask zeroes
COUNTERS = ("moe_slots_held", "moe_load_max", "moe_load_mean",
            "moe_dropped_slots", "attn_block_pairs_visited",
            "attn_block_pairs_visited_window", "attn_block_pairs_visited_full",
            "attn_block_pairs_visited_local", "attn_block_pairs_causal",
            "eva_pairs_local", "eva_pairs_remote", "conv_taps_masked")
# positions a block of `head_loss`; a row that is not whole blocks is one
HEAD_BLOCK = 8192


def _gain(module: nn.Module, cfg, name: str, width: int) -> jax.Array:
    """A norm's gain. Under `norm_add_unit_offset` the parameter is the
    gain's distance from 1 and starts at 0."""
    if getattr(cfg, "norm_add_unit_offset", False):
        return 1.0 + module.param(name, nn.initializers.zeros, (width,),
                                  jnp.float32)
    return module.param(name, nn.initializers.ones, (width,), jnp.float32)


def _skip_add(cfg, x: jax.Array, out: jax.Array) -> jax.Array:
    """x + out; under `fp32_skip_add` summed in fp32 and stored in the
    stream's dtype."""
    if getattr(cfg, "fp32_skip_add", False):
        return (x.astype(jnp.float32) + out.astype(jnp.float32)
                ).astype(x.dtype)
    return x + out


class DecoderLayer(Weights):
    cfg: Any = None  # one of config.LM_CONFIGS
    index: int = 0   # of the layers held

    @nn.compact
    def __call__(self, x, positions, segment_ids):
        cfg = self.cfg
        kw = dict(dtype=self.dtype, init_std=self.init_std)

        def norm(name, t):
            with jax.named_scope("lm/norm"):
                return rms_norm(t, _gain(self, cfg, name, t.shape[-1]),
                                cfg.rms_norm_eps)

        # an AfmoeConfig's layer also norms what each half adds
        sandwich = isinstance(cfg, AfmoeConfig)
        out = mixer_of(cfg, self.index, **kw)(
            norm("attn_norm", x), positions, segment_ids)
        h = _skip_add(cfg, x,
                      norm("attn_post_norm", out) if sandwich else out)
        normed = norm("ffn_norm", h)
        if self.index < cfg.first_k_dense_replace:
            with jax.named_scope("lm/mlp"):
                out = SwiGLU(width=cfg.intermediate_size, name="mlp",
                             **kw)(normed)
            counters = {}
        else:
            out, counters = MoE(cfg=cfg, name="moe", **kw)(normed)
        return _skip_add(cfg, h, norm("ffn_post_norm", out)
                         if sandwich else out), counters


class LM(nn.Module):
    """tokens, positions, segment_ids `[B, S]` int32 and `targets`
    (`next_token_targets`' pair) -> (the sum of the targets'
    cross-entropies, the expert layers' counters). `logits=True` gives
    `[B, S, vocab]` logits instead (`num_pred_heads` vocabularies side
    by side where the configuration has them), for tests: the train
    path never holds them."""

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
            x = rms_norm(x, _gain(self, cfg, "final_norm", cfg.hidden_size),
                         cfg.rms_norm_eps)
        if getattr(cfg, "tie_embedding", False):
            head = embed.astype(dtype).T
        else:
            head = self.param(
                "head", nn.initializers.normal(cfg.init_std),
                (cfg.hidden_size,
                 cfg.vocab_size * getattr(cfg, "num_pred_heads", 1)),
                jnp.float32).astype(dtype)
        counters = dict(_reduce_counters(per_layer),
                        **_attention_counters(cfg, segment_ids))
        if logits:
            return jnp.matmul(x, head,
                              preferred_element_type=jnp.float32), counters
        return head_loss(x, head, *targets), counters


def _reduce_counters(per_layer) -> Dict[str, jax.Array]:
    """Over the expert layers: slots and drops summed, the fullest
    expert's load, the mean load. A stack without one holds no slot and
    drops none."""
    if not per_layer:
        return {"moe_slots_held": jnp.zeros((), jnp.int32),
                "moe_dropped_slots": jnp.zeros((), jnp.int32)}
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
    `LMConfig`: of a layer. A configuration with `layer_types`: by the
    layers' kind, each summed over the layers of the kind (the window
    kind where the configuration has a `sliding_window`; the taps the
    convolution layers' mask zeroes where it has such layers). An
    `EvaByteConfig`: the exact
    part's, on the ids that separate document and window, summed over
    the layers, beside the (query, key) and (query, summary) pairs the
    batch needs, exactly. Where the kernel does not take the shapes, the
    one block the XLA path's mask covers."""
    seq = segment_ids.shape[1]
    blocks = kernel_blocks(seq, cfg.qk_head_dim, cfg.v_head_dim) or (seq, seq)
    if isinstance(cfg, EvaByteConfig):
        layers = cfg.num_hidden_layers
        visited, causal = block_pair_counts(
            local_ids(segment_ids, cfg.window_size), *blocks,
            cfg.window_size)
        local, remote = pair_counts(segment_ids, window=cfg.window_size,
                                    chunk=cfg.chunk_size)
        return {"attn_block_pairs_visited_local": visited * layers,
                "attn_block_pairs_causal": causal * layers,
                "eva_pairs_local": local * layers,
                "eva_pairs_remote": remote * layers}
    visited, causal = block_pair_counts(segment_ids, *blocks)
    kinds = getattr(cfg, "layer_types", None)
    if kinds is None:
        return {"attn_block_pairs_visited": visited,
                "attn_block_pairs_causal": causal}
    out = {}
    if hasattr(cfg, "sliding_window"):
        windowed, _ = block_pair_counts(segment_ids, *blocks,
                                        cfg.sliding_window)
        out["attn_block_pairs_visited_window"] = (
            windowed * kinds.count("sliding_attention"))
    out.update(attn_block_pairs_visited_full=(
        visited * kinds.count("full_attention")),
        attn_block_pairs_causal=causal)
    if "conv" in kinds:
        out["conv_taps_masked"] = (taps_masked(segment_ids, cfg.conv_L_cache)
                                   * kinds.count("conv"))
    return out


def next_token_targets(tokens: jax.Array, segment_ids: jax.Array,
                       ahead: int = 1) -> Tuple[jax.Array, jax.Array]:
    """(targets `[B, S]`, weight `[B, S]` fp32): position t predicts
    token t+1 where both lie in one document; a row's last position,
    a document's last token and pad predict nothing. `ahead > 1`: both
    `[B, S, ahead]`, entry j for the token t+1+j under the same rule
    (documents are contiguous, so one document at both ends is one
    document between)."""
    def one(j):
        nxt = jnp.roll(tokens, -j, axis=1)
        same = ((jnp.roll(segment_ids, -j, axis=1) == segment_ids)
                & (segment_ids > 0))
        for tail in range(1, j + 1):  # the row's last j positions
            same = same.at[:, -tail].set(False)
        return nxt, same.astype(jnp.float32)

    if ahead == 1:
        return one(1)
    pairs = [one(j) for j in range(1, ahead + 1)]
    return (jnp.stack([t for t, _ in pairs], axis=-1),
            jnp.stack([w for _, w in pairs], axis=-1))


def head_loss(x: jax.Array, head: jax.Array, targets: jax.Array,
              weight: jax.Array, block: int = HEAD_BLOCK) -> jax.Array:
    """Sum over the batch of weight * cross-entropy, and nothing else:
    the caller divides by the number of targets. Logits, log-sum-exp and
    the sum are fp32. x `[B, S, D]`, walked `block` positions at a time
    (a row that is not whole blocks: a row at a time). targets and
    weight `[B, S]`, or `[B, S, J]` for a head of `J` vocabularies of
    columns side by side, each with its own softmax."""
    seq = x.shape[1]
    ahead = targets.shape[2:]
    if seq % block == 0 and seq != block:
        x = x.reshape(-1, block, x.shape[-1])
        targets, weight = (t.reshape((-1, block) + ahead)
                           for t in (targets, weight))

    @jax.checkpoint
    def row(carry, xs):
        h, tgt, wt = xs
        with jax.named_scope("lm/head_loss"):
            logits = jnp.matmul(h, head, preferred_element_type=jnp.float32)
            if ahead:
                logits = logits.reshape(h.shape[0], ahead[0], -1)
            lse = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, tgt[..., None],
                                         axis=-1)[..., 0]
            return carry + jnp.sum((lse - picked) * wt), None

    total, _ = jax.lax.scan(row, jnp.zeros((), jnp.float32),
                            (x, targets, weight))
    return total
