"""The decoder stack and its loss, for a `config.DecoderConfig`. What
the configuration answers is in brackets:

    x0 = E[id] * embed_scale
    h  = x + Attn_l(N1(x));       x' = h + FFN(N2(h))
    h  = x + N2(Attn_l(N1(x)));   x' = h + N4(FFN(N3(h)))      [post_norms]
    x' = x + Attn_l(N1(x))   or   x' = x + FFN(N2(x))          [layer_parts]
    logits = W_head RMSNorm(x_last)

A layer holds a mixer and a feed-forward part, each behind its norm and
with its own sum into the stream, or one of the two alone
[`layer_parts(l)`]: the half a layer does not have is not built, and its
norm is not.

The norms' gains are the parameters, or `1 + g` [norm_add_unit_offset];
the two sums are in the stream's dtype, or in fp32 [fp32_skip_add];
`W_head` is a parameter of `num_pred_heads` vocabularies of columns, or
E's transpose and no parameter of its own [tie_embedding].

`Attn_l` is layer `l`'s mixer (models/lm/attention.py `mixer_of`, by
the configuration's `mixer(l)`, `layer_window(l)` and `layer_rope(l)`):
attention of four kinds, a gated short convolution or a state-space
scan. FFN is a SwiGLU of `intermediate_size` in the first
`first_k_dense_replace` layers and the expert layer after them
(models/lm/moe.py), whose router reads what its experts read, `N2(h)`,
or the layer's input `x` itself [router_reads]: then the routing and the
dispatch table are made ahead of the mixer, since nothing they read
waits for it, and the router's gradient flows into `x` and not into
`h`. Under `cfg.remat` every layer is
a `jax.checkpoint` that keeps one thing, the expert layer's routing
(what `moe.plan` puts under the name `moe.ROUTING`: the chosen experts,
their logits, the sorted order and the dispatch table, integers and
floats `[T * top_k]`, five arrays of 4 B a slot a layer: 14 MB at 32,768
tokens by 22): the backward holds one layer's activations at a time and
recomputes them all but the router's fp32 product, the top-k, the sort
and the table, which cost a ninth of the largest cell's step to make
again (98.9 of 873.5 ms; PERF.md, PR 49) and a few MB to keep. A layer
without experts carries no such name and keeps nothing.

The loss is the mean cross-entropy over next-token targets that lie in
the same document as their input (a packed row holds several; pad has
segment id 0); head `j` of `num_pred_heads` predicts the token `1 + j`
ahead, and the mean is over heads and positions alike. The head and the
loss run a block of `HEAD_BLOCK` positions at a time under a
`jax.checkpoint`, so `[HEAD_BLOCK, vocab]` fp32 logits exist for one
block only, in the forward and in the backward (a row of 32,768
positions over 25,024 vocabulary rows would be 3.28 GB, twice over in
the backward).
"""

from __future__ import annotations

import collections
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from dexiraft_tpu.models.lm import moe
from dexiraft_tpu.models.lm.attention import MIXERS, mixer_of
from dexiraft_tpu.models.lm.layers import SwiGLU, Weights, rms_norm

# every counter a step's metrics may carry: the expert layer's and each
# mixer's, as they declare them
COUNTERS = tuple(dict.fromkeys([*moe.COUNTERS, *(
    name for mixer in MIXERS.values() for name in mixer.COUNTERS)]))
# positions a block of `head_loss`; a row that is not whole blocks is one
HEAD_BLOCK = 8192


def _gain(module: nn.Module, cfg, name: str, width: int) -> jax.Array:
    """A norm's gain. Under `norm_add_unit_offset` the parameter is the
    gain's distance from 1 and starts at 0."""
    if cfg.norm_add_unit_offset:
        return 1.0 + module.param(name, nn.initializers.zeros, (width,),
                                  jnp.float32)
    return module.param(name, nn.initializers.ones, (width,), jnp.float32)


def _skip_add(cfg, x: jax.Array, out: jax.Array) -> jax.Array:
    """x + out; under `fp32_skip_add` summed in fp32 and stored in the
    stream's dtype."""
    if cfg.fp32_skip_add:
        return (x.astype(jnp.float32) + out.astype(jnp.float32)
                ).astype(x.dtype)
    return x + out


class DecoderLayer(Weights):
    cfg: Any = None  # a config.DecoderConfig
    index: int = 0   # of the layers held

    @nn.compact
    def __call__(self, x, positions, segment_ids):
        cfg = self.cfg
        kw = dict(dtype=self.dtype, init_std=self.init_std)

        def norm(name, t):
            with jax.named_scope("lm/norm"):
                return rms_norm(t, _gain(self, cfg, name, t.shape[-1]),
                                cfg.rms_norm_eps)

        parts = cfg.layer_parts(self.index)
        experts = plan = None
        if "ffn" in parts and self.index >= cfg.first_k_dense_replace:
            experts = moe.MoE(cfg=cfg, name="moe", **kw)
            if cfg.router_reads == "layer":
                plan = experts.plan(x)
        h = x
        if "mixer" in parts:
            out = mixer_of(cfg, self.index, **kw)(
                norm("attn_norm", x), positions, segment_ids)
            h = _skip_add(cfg, x, norm("attn_post_norm", out)
                          if cfg.post_norms else out)
        if "ffn" not in parts:
            return h, {}
        normed = norm("ffn_norm", h)
        if experts is None:
            with jax.named_scope("lm/mlp"):
                out = SwiGLU(width=cfg.intermediate_size, name="mlp",
                             **kw)(normed)
            counters = {}
        else:
            out, counters = experts(normed, plan)
        return _skip_add(cfg, h, norm("ffn_post_norm", out)
                         if cfg.post_norms else out), counters


class LM(nn.Module):
    """tokens, positions, segment_ids `[B, S]` int32 and `targets`
    (`next_token_targets`' pair) -> (the sum of the targets'
    cross-entropies, the expert layers' counters). `logits=True` gives
    `[B, S, vocab]` logits instead (`num_pred_heads` vocabularies side
    by side where the configuration has them), for tests: the train
    path never holds them."""

    cfg: Any  # a config.DecoderConfig

    @nn.compact
    def __call__(self, tokens, positions, segment_ids, *, targets=None,
                 logits: bool = False):
        cfg = self.cfg
        dtype = jnp.bfloat16 if cfg.mixed_precision else jnp.float32
        kw = dict(dtype=dtype, init_std=cfg.init_std)
        embed = self.param("embed",
                           nn.initializers.normal(cfg.embed_init_std),
                           (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        with jax.named_scope("lm/embed"):
            x = embed.astype(dtype)[tokens]
            if cfg.embed_scale != 1.0:
                x = x * jnp.asarray(cfg.embed_scale, dtype)
        layer_cls = (nn.remat(
            DecoderLayer, prevent_cse=True,
            policy=jax.checkpoint_policies.save_only_these_names(
                moe.ROUTING)) if cfg.remat else DecoderLayer)
        per_layer = []
        for i in range(cfg.num_hidden_layers):
            x, counters = layer_cls(cfg=cfg, index=i, name=f"layers_{i}",
                                    **kw)(x, positions, segment_ids)
            if counters:
                per_layer.append(counters)
        with jax.named_scope("lm/norm"):
            x = rms_norm(x, _gain(self, cfg, "final_norm", cfg.hidden_size),
                         cfg.rms_norm_eps)
        if cfg.tie_embedding:
            head = embed.astype(dtype).T
        else:
            head = self.param(
                "head", nn.initializers.normal(cfg.init_std),
                (cfg.hidden_size, cfg.vocab_size * cfg.num_pred_heads),
                jnp.float32).astype(dtype)
        counters = dict(moe.reduce_counters(per_layer),
                        **_attention_counters(cfg, segment_ids))
        if logits:
            return jnp.matmul(x, head,
                              preferred_element_type=jnp.float32), counters
        return head_loss(x, head, *targets), counters


def _attention_counters(cfg, segment_ids: jax.Array) -> Dict[str, jax.Array]:
    """What this batch gives the mixers of the held layers (every layer
    sees the same documents): each kind of mixer is asked once, in the
    table's order, with the number of its layers by window."""
    layers = collections.defaultdict(collections.Counter)
    for i in range(cfg.num_hidden_layers):
        if "mixer" in cfg.layer_parts(i):
            layers[cfg.mixer(i)][cfg.layer_window(i)] += 1
    out: Dict[str, jax.Array] = {}
    for kind, mixer in MIXERS.items():
        if kind in layers:
            counts = mixer.counters(cfg, segment_ids, layers[kind])
            assert not out.keys() & counts.keys(), (kind, counts.keys())
            out.update(counts)
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
