"""The four mixers, for the heads this chip holds: `mixer_of` picks by
the configuration's `mixer(layer)`.

**`LatentAttention`** (`LMConfig`): multi-head latent attention without a
query LoRA (`q_lora_rank: null`).

    q            = W_q x            -> per head [q_nope; q_rope]
    [c; k_rope]  = W_kva x          k_rope is shared by every head
    [k_nope; v]  = W_kvb RMSNorm(c) -> per head
    scores       = (q_nope . k_nope + rope(q_rope) . rope(k_rope)) / sqrt(d_qk)
    out          = W_o concat_heads(softmax(scores) v)

`W_q`, `W_kvb` and `W_o` hold the columns (rows) of the held heads only;
the latent projection `W_kva` and its norm are whole on every chip of
the group. What the absent heads would add to `out` is left out: in a
deployment it arrives with the tensor-parallel sum.

**`GatedAttention`** (`AfmoeConfig`): grouped-query attention with a
sigmoid gate on its output and an RMSNorm on every query and key head.

    q = W_q x -> heads x d;  k = W_k x, v = W_v x -> kv heads x d;  g = W_g x
    q = RMSNorm_d(q), k = RMSNorm_d(k)     one gain each, shared by the heads
    sliding layers: q, k = rope(q), rope(k)   (half rotation, over all d)
    full layers: no positional embedding
    scores over the same document, earlier keys, and in sliding layers
    fewer than `sliding_window` back;  out = W_o (softmax(scores) v * sigmoid(g))

`W_q`, `W_g` and `W_o` hold the held query heads' columns (rows), `W_k`
and `W_v` the columns of the key/value heads those read
(`cfg.kv_heads_held`): a key/value head serves several query heads, so
the chips that hold its query heads each hold a copy of it.

An `Lfm2MoeConfig`'s `full_attention` layer is the same module with what
its configuration says: no gate and no `W_g` (`attention_gate`), the
rotary embedding on full layers too (`rope_full_layers`).

**`ShortConv`** (`Lfm2MoeConfig`, `conv` layers): the doubly gated short
causal convolution (ops/lm_conv.py has the equations).

    [B; C; z] = W_in x           in this order
    out = W_out (C * conv_L(B * z))    depthwise, causal, within documents

It has no heads: the channels are the hidden width, and the module is
whole on every chip (in a deployment a tensor-parallel chip would hold
its share of the channels).

**`EvaAttention`** (`EvaByteConfig`): EVA chunked linear attention
(ops/lm_eva.py has the equations).

    q, k = rope(W_q x), rope(W_k x);  v = W_v x     (half rotation, over all d)
    per head: phi, mu in R^d, learned (`adaptive_phi`, `adaptive_mu_k`)
    o = one softmax over the exact keys of the query's own window and
        the phi-pooled, mu-shifted summaries of its document's chunks in
        the windows before;   out = W_o concat_heads(o)

`W_q`, `W_k`, `W_v` hold the held heads' columns, `W_o` their rows, `phi`
and `mu_k` their rows: a head's summaries are its own.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from typing import Any, Optional

from dexiraft_tpu.config import EvaByteConfig, LMConfig
from dexiraft_tpu.models.lm.layers import (Weights, rms_norm, rope_half,
                                           rope_interleaved)
from dexiraft_tpu.ops.lm_attention import document_attention
from dexiraft_tpu.ops.lm_conv import gated_short_conv
from dexiraft_tpu.ops.lm_eva import eva_attention


class LatentAttention(Weights):
    cfg: LMConfig = None

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array,
                 segment_ids: jax.Array) -> jax.Array:
        cfg = self.cfg
        b, s, d = x.shape
        heads = cfg.heads_held[1]
        nope, rope, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
        with jax.named_scope("lm/mla"):
            q = (x @ self.w("wq", (d, heads * (nope + rope)))
                 ).reshape(b, s, heads, nope + rope)
            kva = x @ self.w("wkva", (d, cfg.kv_lora_rank + rope))
            latent = rms_norm(
                kva[..., :cfg.kv_lora_rank],
                self.param("kv_norm", nn.initializers.ones,
                           (cfg.kv_lora_rank,), jnp.float32),
                cfg.rms_norm_eps)
            kv = (latent @ self.w("wkvb", (cfg.kv_lora_rank,
                                           heads * (nope + dv)))
                  ).reshape(b, s, heads, nope + dv)
            q_rope = rope_interleaved(q[..., nope:], positions,
                                      cfg.rope_theta)
            k_rope = rope_interleaved(kva[..., cfg.kv_lora_rank:], positions,
                                      cfg.rope_theta)
            # one product over [nope; rope] is the sum of the two
            q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
            k = jnp.concatenate(
                [kv[..., :nope],
                 jnp.broadcast_to(k_rope[:, :, None], (b, s, heads, rope))],
                axis=-1)
            out = document_attention(
                q, k, kv[..., nope:], segment_ids,
                scale=(nope + rope) ** -0.5, block=cfg.attn_block)
            return out.reshape(b, s, heads * dv) @ self.w(
                "wo", (heads * dv, d))


class GatedAttention(Weights):
    cfg: Any = None  # an AfmoeConfig or an Lfm2MoeConfig
    window: Optional[int] = None  # None: a full layer

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array,
                 segment_ids: jax.Array) -> jax.Array:
        cfg = self.cfg
        b, s, d = x.shape
        heads, kv_heads, hd = (cfg.heads_held[1], cfg.kv_heads_held[1],
                               cfg.head_dim)
        kind = "full" if self.window is None else "window"
        with jax.named_scope("lm/gqa/proj"):
            q = (x @ self.w("wq", (d, heads * hd))).reshape(b, s, heads, hd)
            k = (x @ self.w("wk", (d, kv_heads * hd))
                 ).reshape(b, s, kv_heads, hd)
            v = (x @ self.w("wv", (d, kv_heads * hd))
                 ).reshape(b, s, kv_heads, hd)
            if cfg.attention_gate:
                gate = x @ self.w("wg", (d, heads * hd))
            q, k = (rms_norm(t, self.param(name, nn.initializers.ones, (hd,),
                                           jnp.float32), cfg.rms_norm_eps)
                    for t, name in ((q, "q_norm"), (k, "k_norm")))
            if self.window is not None or cfg.rope_full_layers:
                q = rope_half(q, positions, cfg.rope_theta)
                k = rope_half(k, positions, cfg.rope_theta)
        with jax.named_scope(f"lm/gqa/{kind}/kernel"):
            out = document_attention(q, k, v, segment_ids, scale=hd ** -0.5,
                                     block=cfg.attn_block, window=self.window)
        with jax.named_scope("lm/gqa/proj"):
            out = out.reshape(b, s, heads * hd)
            if cfg.attention_gate:
                out = out * jax.nn.sigmoid(gate)
            return out @ self.w("wo", (heads * hd, d))


class ShortConv(Weights):
    cfg: Any = None  # an Lfm2MoeConfig

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array,
                 segment_ids: jax.Array) -> jax.Array:
        d = x.shape[-1]
        with jax.named_scope("lm/conv/in"):
            b, c, z = jnp.split(x @ self.w("w_in", (d, 3 * d)), 3, axis=-1)
        with jax.named_scope("lm/conv/gate"):
            y = gated_short_conv(
                b, c, z, self.w("taps", (d, self.cfg.conv_L_cache)),
                segment_ids)
        with jax.named_scope("lm/conv/out"):
            return y @ self.w("w_out", (d, d))


class EvaAttention(Weights):
    cfg: EvaByteConfig = None

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array,
                 segment_ids: jax.Array) -> jax.Array:
        cfg = self.cfg
        b, s, d = x.shape
        heads, hd = cfg.heads_held[1], cfg.head_dim
        with jax.named_scope("lm/eva/proj"):
            q, k, v = ((x @ self.w(name, (d, heads * hd))
                        ).reshape(b, s, heads, hd)
                       for name in ("wq", "wk", "wv"))
            q = rope_half(q, positions, cfg.rope_theta)
            k = rope_half(k, positions, cfg.rope_theta)
            phi, mu = self.w("phi", (heads, hd)), self.w("mu_k", (heads, hd))
        out = eva_attention(q, k, v, phi, mu, segment_ids,
                            window=cfg.window_size, chunk=cfg.chunk_size,
                            scale=hd ** -0.5, block=cfg.attn_block)
        with jax.named_scope("lm/eva/proj"):
            return out.reshape(b, s, heads * hd) @ self.w(
                "wo", (heads * hd, d))


def mixer_of(cfg, layer: int, **kw) -> nn.Module:
    """Layer `layer`'s mixer: an attention module (named `attn`) or the
    convolution (named `conv`)."""
    kind = cfg.mixer(layer)
    if kind == "conv":
        return ShortConv(cfg=cfg, name="conv", **kw)
    if kind == "gqa":
        return GatedAttention(cfg=cfg, window=cfg.layer_window(layer),
                              name="attn", **kw)
    if kind == "eva":
        return EvaAttention(cfg=cfg, name="attn", **kw)
    return LatentAttention(cfg=cfg, name="attn", **kw)
