"""The plain reference of the language model: forward, loss and gradients
in `jax.numpy`, float32, under `jax.default_matmul_precision("highest")`.

Written from the published `config.json` of
kanana-2-30b-a3b-instruct-2601 (`model_type: deepseek_v3`; docs/lm.md has
the equations), independent of models/lm: no Flax module, no kernel, no
sorting, no blocks of attention, no recomputation. Every held expert is
applied to every token and weighted by `w_i` where the router chose it
and by 0 elsewhere; attention makes the full `[heads, S, S]` score
matrix of one sequence at a time. It reads the same parameter tree as
the system (a nested dict of arrays, named as models/lm names them), so
both run on the same seeded weights.

Departures from the published model, each also in docs/lm.md:
  * `e_score_correction_bias` b = 0: the config gives no update rule for
    it, so it is held at its initial value (`bias`, if given, is added
    to the scores for the choice only, as the model does).
  * the share: `experts_held` and `heads_held` name the experts and heads
    this chip of a tensor- and expert-parallel group holds. The router
    still scores and chooses over all experts; what the absent experts
    and heads would add is left out, here as in the system. The
    vocabulary slice is a smaller vocabulary: the embedding and the head
    have the rows that are held and the loss is over them.

`blocked_loss_and_grads` is the same mathematics walked a sequence and
a layer at a time (`jax.vjp` of one layer, inputs kept, layers revisited
in reverse), for sizes at which `jax.grad` of the whole loss does not
fit the chip. tests/test_zz_lm_reference.py holds it to `jax.grad` of
`loss`.

`dtype=jnp.bfloat16` computes everything in bf16 (weights, router,
softmax, norm statistics, loss) at the default matmul precision: the
precision below the configuration's, which the benchmark's check has to
tell from it (PERF.md).
"""

from __future__ import annotations

import contextlib
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Share = Optional[Tuple[int, int]]


def _precision(dtype):
    return (jax.default_matmul_precision("highest")
            if dtype == jnp.float32 else contextlib.nullcontext())


def _rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _rope(x, positions, theta):
    """Pairs (2i, 2i+1) of the last axis rotated by
    position * theta^(-2i/d); x [S, d] or [S, heads, d]. The angles are
    float32 in every mode: a position does not fit bf16."""
    d = x.shape[-1]
    i = jnp.arange(d // 2, dtype=jnp.float32)
    ang = positions.astype(jnp.float32)[:, None] * theta ** (-2.0 * i / d)
    if x.ndim == 3:
        ang = ang[:, None, :]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _swiglu(x, p):
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def attention(p, x, positions, segment_ids, cfg, heads: int):
    """One sequence: x [S, D]. `p` holds `heads` heads' columns."""
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    s = x.shape[0]
    q = (x @ p["wq"]).reshape(s, heads, nope + rope)
    kva = x @ p["wkva"]
    latent = _rms_norm(kva[:, :cfg.kv_lora_rank], p["kv_norm"],
                       cfg.rms_norm_eps)
    kv = (latent @ p["wkvb"]).reshape(s, heads, nope + dv)
    q_rope = _rope(q[..., nope:], positions, cfg.rope_theta)
    k_rope = _rope(kva[:, cfg.kv_lora_rank:], positions, cfg.rope_theta)
    scores = (jnp.einsum("qhd,khd->hqk", q[..., :nope], kv[..., :nope])
              + jnp.einsum("qhd,kd->hqk", q_rope, k_rope)
              ) / jnp.sqrt(jnp.asarray(nope + rope, x.dtype))
    t = jnp.arange(s)
    allowed = ((t[:, None] >= t[None, :])
               & (segment_ids[:, None] == segment_ids[None, :]))
    scores = jnp.where(allowed[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1),
                     kv[..., nope:])
    return out.reshape(s, heads * dv) @ p["wo"]


def routing(p, x, cfg, bias=None):
    """(chosen expert ids [S, k], their weights [S, k]) over ALL experts."""
    scores = jax.nn.sigmoid(x @ p["router"])
    for_choice = scores if bias is None else scores + bias
    _, chosen = jax.lax.top_k(for_choice, cfg.num_experts_per_tok)
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * cfg.routed_scaling_factor


def moe(p, x, cfg, experts_held: Tuple[int, int], bias=None):
    """One sequence: the held experts' part of the routed sum, plus the
    shared experts. Expert `first + j` has row j of `p["experts"]`."""
    first, count = experts_held
    chosen, w = routing(p["experts"], x, cfg, bias)
    out = _swiglu(x, p["shared"])
    for j in range(count):
        w_j = jnp.sum(jnp.where(chosen == first + j, w, 0.0), axis=-1)
        expert = {k: p["experts"][k][j] for k in ("w_gate", "w_up", "w_down")}
        out = out + w_j[:, None] * _swiglu(x, expert)
    return out


def layer(p, x, positions, segment_ids, cfg, dense: bool,
          heads_held: Share = None, experts_held: Share = None, bias=None):
    """h = x + Attn(RMSNorm(x)); x' = h + FFN(RMSNorm(h)); one sequence."""
    heads = (heads_held or cfg.heads_held)[1]
    h = x + attention(p["attn"],
                      _rms_norm(x, p["attn_norm"], cfg.rms_norm_eps),
                      positions, segment_ids, cfg, heads)
    normed = _rms_norm(h, p["ffn_norm"], cfg.rms_norm_eps)
    if dense:
        return h + _swiglu(normed, p["mlp"])
    return h + moe(p["moe"], normed, cfg, experts_held or cfg.experts_held,
                   bias)


def _targets(tokens, segment_ids):
    """Position t predicts token t+1 where both are in one document."""
    valid = (segment_ids[:-1] == segment_ids[1:]) & (segment_ids[:-1] > 0)
    return tokens[1:], valid


def head_loss_sum(p, x, tokens, segment_ids, cfg):
    """Sum of the cross-entropies of one sequence's targets."""
    logits = _rms_norm(x, p["final_norm"], cfg.rms_norm_eps) @ p["head"]
    targets, valid = _targets(tokens, segment_ids)
    logp = jax.nn.log_softmax(logits[:-1], axis=-1)
    picked = jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
    return -jnp.sum(jnp.where(valid, picked, 0.0))


def _cast(tree, dtype):
    return jax.tree.map(lambda a: jnp.asarray(a, dtype), tree)


def _is_dense(cfg, i):
    return i < cfg.first_k_dense_replace


def hidden_states(params, tokens, positions, segment_ids, cfg, **share):
    """One sequence through the stack: [S, D] before the final norm."""
    x = params["embed"][tokens]
    for i in range(cfg.num_hidden_layers):
        x = layer(params[f"layers_{i}"], x, positions, segment_ids, cfg,
                  _is_dense(cfg, i), **share)
    return x


def logits(params, batch: Dict[str, Any], cfg, dtype=jnp.float32, **share):
    """[B, S, vocab] logits of a batch of tokens / positions / segment_ids."""
    params = _cast(params, dtype)
    with _precision(dtype):
        rows = []
        for b in range(batch["tokens"].shape[0]):
            x = hidden_states(params, batch["tokens"][b],
                              batch["positions"][b], batch["segment_ids"][b],
                              cfg, **share)
            rows.append(_rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
                        @ params["head"])
        return jnp.stack(rows)


def n_targets(batch) -> jax.Array:
    seg = batch["segment_ids"]
    return jnp.sum((seg[:, :-1] == seg[:, 1:]) & (seg[:, :-1] > 0))


def loss(params, batch: Dict[str, Any], cfg, dtype=jnp.float32, **share):
    """Mean cross-entropy over the batch's next-token targets."""
    params = _cast(params, dtype)
    with _precision(dtype):
        total = 0.0
        for b in range(batch["tokens"].shape[0]):
            tok, pos, seg = (batch[k][b] for k in
                             ("tokens", "positions", "segment_ids"))
            x = hidden_states(params, tok, pos, seg, cfg, **share)
            total = total + head_loss_sum(params, x, tok, seg, cfg)
        return (total / jnp.maximum(n_targets(batch), 1)).astype(jnp.float32)


def loss_and_grads(params, batch, cfg, dtype=jnp.float32, **share):
    return jax.value_and_grad(loss)(params, batch, cfg, dtype, **share)


def blocked_loss_and_grads(params, batch, cfg, dtype=jnp.float32, **share):
    """`loss_and_grads`, a sequence and a layer at a time: the forward
    keeps each layer's input, the backward takes `jax.vjp` of one layer
    at a time from the last to the first and adds the sequences'
    gradients up. Each piece is jitted once and reused."""
    params = _cast(params, dtype)
    n_layers = cfg.num_hidden_layers
    denom = jnp.maximum(n_targets(batch), 1).astype(dtype)

    def run_layer(dense):
        return lambda p, x, pos, seg: layer(p, x, pos, seg, cfg, dense,
                                            **share)

    def vjp_layer(dense):
        def f(p, x, pos, seg, dy):
            _, pull = jax.vjp(lambda p, x: run_layer(dense)(p, x, pos, seg),
                              p, x)
            return pull(dy)
        return jax.jit(f)

    fwd = {d: jax.jit(run_layer(d)) for d in (True, False)}
    bwd = {d: vjp_layer(d) for d in (True, False)}
    top = {k: params[k] for k in ("final_norm", "head")}
    head = jax.jit(jax.value_and_grad(
        lambda p, x, tok, seg: head_loss_sum(p, x, tok, seg, cfg) / denom,
        argnums=(0, 1)))
    embed_grad = jax.jit(lambda tok, dx: jnp.zeros_like(
        params["embed"]).at[tok].add(dx))

    add = lambda acc, g: g if acc is None else jax.tree.map(jnp.add, acc, g)
    total = jnp.zeros((), jnp.float32)
    grads: Dict[str, Any] = {k: None for k in params}
    with _precision(dtype):
        for b in range(batch["tokens"].shape[0]):
            tok, pos, seg = (batch[k][b] for k in
                             ("tokens", "positions", "segment_ids"))
            inputs = [params["embed"][tok]]
            for i in range(n_layers):
                inputs.append(fwd[_is_dense(cfg, i)](
                    params[f"layers_{i}"], inputs[-1], pos, seg))
            value, (g_top, dx) = head(top, inputs.pop(), tok, seg)
            total = total + value.astype(jnp.float32)
            for k in top:
                grads[k] = add(grads[k], g_top[k])
            for i in reversed(range(n_layers)):
                g, dx = bwd[_is_dense(cfg, i)](
                    params[f"layers_{i}"], inputs.pop(), pos, seg, dx)
                grads[f"layers_{i}"] = add(grads[f"layers_{i}"], g)
            grads["embed"] = add(grads["embed"], embed_grad(tok, dx))
    return total, grads


def take_share(params, cfg, heads_held: Tuple[int, int],
               experts_held: Tuple[int, int]):
    """From the parameters of a model that holds everything, the tree of
    the chip that holds `heads_held` and `experts_held`: the held heads'
    columns of `wq` and `wkvb`, their rows of `wo`, the held experts'
    matrices. The router, the latent projection, the norms, the shared
    experts, the embedding and the head are whole on every chip."""
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    h0, hn = heads_held
    e0, en = experts_held

    def heads(mat, per_head, axis):
        lo, hi = h0 * per_head, (h0 + hn) * per_head
        return mat[:, lo:hi] if axis == 1 else mat[lo:hi]

    out = dict(params)
    for i in range(cfg.num_hidden_layers):
        lp = dict(params[f"layers_{i}"])
        attn = dict(lp["attn"])
        attn["wq"] = heads(attn["wq"], nope + rope, 1)
        attn["wkvb"] = heads(attn["wkvb"], nope + dv, 1)
        attn["wo"] = heads(attn["wo"], dv, 0)
        lp["attn"] = attn
        if "moe" in lp:
            experts = dict(lp["moe"]["experts"])
            for k in ("w_gate", "w_up", "w_down"):
                experts[k] = experts[k][e0:e0 + en]
            lp["moe"] = dict(lp["moe"], experts=experts)
        out[f"layers_{i}"] = lp
    return out
