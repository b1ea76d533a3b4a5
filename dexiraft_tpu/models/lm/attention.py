"""The mixers, for the heads this chip holds. `MIXERS` is the one table,
kind -> module; `mixer_of` picks from it by the configuration's
`mixer(layer)`. A mixer reads the configuration's keys it names below
and says which counters a batch gives its layers (`counters`, read by
models/lm/model.py `_attention_counters`): a new one is a module here,
its row in the table and its op.

**`LatentAttention`** (`mla`): multi-head latent attention without a
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

**`GatedAttention`** (`gqa`): grouped-query attention with, under
`qk_norm`, an RMSNorm on every query and key head, under
`attention_gate` a sigmoid gate on its output, and a rotary embedding on
the layers whose `layer_rope` says so. As afmoe runs it:

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

lfm2_moe's `full_attention` layer is the same module with what its
configuration says: no gate and no `W_g` (`attention_gate`), the rotary
embedding on full layers too (`layer_rope`). smallthinker's layers are
it with neither gate nor QK-norm (no `q_norm`, `k_norm` parameters), 7
query heads a key/value head, the window and the rotary embedding by the
published layouts.

**`ShortConv`** (`conv`): the doubly gated short causal convolution
(ops/lm_conv.py has the equations).

    [B; C; z] = W_in x           in this order
    out = W_out (C * conv_L(B * z))    depthwise, causal, within documents

It has no heads: the channels are the hidden width, and the module is
whole on every chip (in a deployment a tensor-parallel chip would hold
its share of the channels).

**`EvaAttention`** (`eva`): EVA chunked linear attention
(ops/lm_eva.py has the equations).

    q, k = rope(W_q x), rope(W_k x);  v = W_v x     (half rotation, over all d)
    per head: phi, mu in R^d, learned (`adaptive_phi`, `adaptive_mu_k`)
    o = one softmax over the exact keys of the query's own window and
        the phi-pooled, mu-shifted summaries of its document's chunks in
        the windows before;   out = W_o concat_heads(o)

`W_q`, `W_k`, `W_v` hold the held heads' columns, `W_o` their rows, `phi`
and `mu_k` their rows: a head's summaries are its own.

**`Mamba2`** (`mamba2`): the selective state-space mixer (ops/lm_ssm.py
has the scan's equations, ops/lm_conv.py the convolution's).

    [z; x; B; C; dt] = W_in u        H P, H P, G N, G N, H columns
    [x; B; C] = silu(conv_L([x; B; C]) + b)   depthwise, causal, within documents
    dt = softplus(dt + dt_bias);  A = -exp(A_log)        fp32, a head each
    y  = scan(x, dt, A, B, C) + D x   the state reset at a document's start
    out = W_out RMSNorm_group(y * silu(z))    the statistics a group's own

`in_proj` holds the held heads' columns of z, x and dt and the held
groups' of B and C, `taps` and `conv_bias` the same channels of
[x; B; C], `dt_bias`, `A_log`, `D` the held heads' entries, `norm` and
`out_proj` their channels' gains and rows. A chip holds whole groups
(`cfg.ssm_heads_held`, `cfg.ssm_groups_held`), so nothing of the norm
crosses chips. `A_log` starts at `log(1 + h mod 16)` of the model's own
head number h, `D` at 1, `dt_bias` at the inverse softplus of a step
log-uniform in [`time_step_min`, `time_step_max`] floored at
`time_step_floor`.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from typing import Any, Optional

from dexiraft_tpu.models.lm.layers import (Weights, rms_norm, rope_half,
                                           rope_interleaved)
from dexiraft_tpu.ops.lm_attention import (block_pair_counts,
                                           document_attention, kernel_blocks)
from dexiraft_tpu.ops.lm_conv import (causal_conv, gated_short_conv,
                                      taps_masked)
from dexiraft_tpu.ops.lm_eva import eva_attention, local_ids, pair_counts
from dexiraft_tpu.ops.lm_ssm import doc_counts, ssm_scan


class Mixer(Weights):
    """A layer's mixer: `(x, positions, segment_ids) -> [B, S, D]`. Beside
    it a mixer has `COUNTERS`, every name its `counters` may give, and

        counters(cfg, segment_ids, layers) -> {name: int32 scalar}

    a static method: what a batch gives the held layers of this kind,
    `layers` their number by window in the order of the stack."""

    cfg: Any = None  # a config.DecoderConfig
    window: Optional[int] = None  # the layer's `cfg.layer_window`
    rope: bool = True  # the layer's `cfg.layer_rope`
    # the layer's sub-tree of parameters
    TREE = "attn"


def _block_pairs(cfg, ids: jax.Array, window: Optional[int] = None):
    """(visited, causal): the block pairs the attention kernel's grid
    computes for this batch and those of a layer's causal triangle, from
    the table the kernel is handed; where the kernel does not take the
    shapes, of the one block the XLA path's mask covers."""
    seq = ids.shape[1]
    blocks = kernel_blocks(seq, cfg.qk_head_dim, cfg.v_head_dim) or (seq, seq)
    return block_pair_counts(ids, *blocks, window)


class LatentAttention(Mixer):
    # `_block_pairs` of one layer: every layer's are the same
    COUNTERS = ("attn_block_pairs_visited", "attn_block_pairs_causal")

    @staticmethod
    def counters(cfg, segment_ids, layers):
        return dict(zip(LatentAttention.COUNTERS,
                        _block_pairs(cfg, segment_ids)))

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


class GatedAttention(Mixer):
    # the visited pairs by the layers' kind (under a window, or full),
    # each summed over the layers of the kind; the triangle of a layer
    COUNTERS = ("attn_block_pairs_visited_window",
                "attn_block_pairs_visited_full", "attn_block_pairs_causal")

    @staticmethod
    def counters(cfg, segment_ids, layers):
        visited, causal = _block_pairs(cfg, segment_ids)
        out = {"attn_block_pairs_causal": causal}
        for window, n in layers.items():
            if window is None:
                out["attn_block_pairs_visited_full"] = visited * n
            else:
                out["attn_block_pairs_visited_window"] = _block_pairs(
                    cfg, segment_ids, window)[0] * n
        return out

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
            if cfg.qk_norm:
                q, k = (rms_norm(t, self.param(name, nn.initializers.ones,
                                               (hd,), jnp.float32),
                                 cfg.rms_norm_eps)
                        for t, name in ((q, "q_norm"), (k, "k_norm")))
            if self.rope:
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


class ShortConv(Mixer):
    TREE = "conv"
    # the taps the mask zeroes, summed over the layers
    COUNTERS = ("conv_taps_masked",)

    @staticmethod
    def counters(cfg, segment_ids, layers):
        return {"conv_taps_masked": (taps_masked(segment_ids,
                                                 cfg.conv_L_cache)
                                     * sum(layers.values()))}

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


class EvaAttention(Mixer):
    # the exact part's block pairs, on the ids that separate document and
    # window, beside the (query, key) and (query, summary) pairs the batch
    # needs, exactly: each summed over the layers
    COUNTERS = ("attn_block_pairs_visited_local", "attn_block_pairs_causal",
                "eva_pairs_local", "eva_pairs_remote")

    @staticmethod
    def counters(cfg, segment_ids, layers):
        counts = _block_pairs(
            cfg, local_ids(segment_ids, cfg.window_size), cfg.window_size
        ) + pair_counts(segment_ids, window=cfg.window_size,
                        chunk=cfg.chunk_size)
        return {name: count * sum(layers.values())
                for name, count in zip(EvaAttention.COUNTERS, counts)}

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


class Mamba2(Mixer):
    TREE = "ssm"
    # the resets the layers apply to real tokens and the chunks that
    # hold one, each summed over the layers
    COUNTERS = ("ssm_doc_starts", "ssm_chunks_reset")

    @staticmethod
    def counters(cfg, segment_ids, layers):
        counts = doc_counts(segment_ids, cfg.chunk_size)
        return {name: count * sum(layers.values())
                for name, count in zip(Mamba2.COUNTERS, counts)}

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array,
                 segment_ids: jax.Array) -> jax.Array:
        cfg = self.cfg
        b, s, d = x.shape
        (first, heads), groups = cfg.ssm_heads_held, cfg.ssm_groups_held[1]
        p, n = cfg.mamba_head_dim, cfg.ssm_state_size
        inner, bc = heads * p, groups * n
        fp32 = lambda name, init: self.param(  # noqa: E731
            name, init, (heads,), jnp.float32)

        def dt_bias_init(key, shape, dtype):
            lo, hi = jnp.log(cfg.time_step_min), jnp.log(cfg.time_step_max)
            step = jnp.maximum(jnp.exp(
                jax.random.uniform(key, shape, dtype) * (hi - lo) + lo),
                cfg.time_step_floor)
            return step + jnp.log(-jnp.expm1(-step))

        with jax.named_scope("lm/ssm/in"):
            z, xbc, dt = jnp.split(
                x @ self.w("in_proj", (d, 2 * inner + 2 * bc + heads)),
                (inner, 2 * inner + 2 * bc), axis=-1)
        with jax.named_scope("lm/ssm/conv"):
            xbc = causal_conv(
                xbc, self.w("taps", (inner + 2 * bc, cfg.conv_kernel)),
                self.param("conv_bias", nn.initializers.zeros,
                           (inner + 2 * bc,), jnp.float32), segment_ids)
        with jax.named_scope("lm/ssm/scan"):
            dt = jax.nn.softplus(dt.astype(jnp.float32)
                                 + fp32("dt_bias", dt_bias_init))
            # an fp32 array of its own type: a weakly typed leaf turns
            # strong in the first step, and the step compiles twice
            a = -jnp.exp(fp32("A_log", lambda *_: jnp.log(
                (1 + (first + jnp.arange(heads)) % 16).astype(jnp.float32))))
            y = ssm_scan(
                xbc[..., :inner].reshape(b, s, heads, p), dt, a,
                xbc[..., inner:inner + bc].reshape(b, s, groups, n),
                xbc[..., inner + bc:].reshape(b, s, groups, n),
                fp32("D", nn.initializers.ones), segment_ids,
                cfg.chunk_size)
        with jax.named_scope("lm/ssm/gate_norm"):
            gated = (y.reshape(b, s, inner).astype(jnp.float32)
                     * jax.nn.silu(z.astype(jnp.float32)))
            gain = self.param("norm", nn.initializers.ones, (inner,),
                              jnp.float32)
            y = rms_norm(gated.reshape(b, s, groups, -1),
                         gain.reshape(groups, -1), cfg.rms_norm_eps
                         ).astype(x.dtype).reshape(b, s, inner)
        with jax.named_scope("lm/ssm/out"):
            return y @ self.w("out_proj", (inner, d))


# a configuration's `mixer(i)` -> the module
MIXERS = {"mla": LatentAttention, "gqa": GatedAttention,
          "eva": EvaAttention, "conv": ShortConv, "mamba2": Mamba2}


def mixer_of(cfg, layer: int, **kw) -> nn.Module:
    """Layer `layer`'s mixer, named as its sub-tree (`attn`, `conv` for
    the convolution, `ssm` for the state-space mixer)."""
    module = MIXERS[cfg.mixer(layer)]
    return module(cfg=cfg, window=cfg.layer_window(layer),
                  rope=cfg.layer_rope(layer), name=module.TREE, **kw)
