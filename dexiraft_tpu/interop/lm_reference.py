"""The plain reference of the language models: forward, loss and gradients
in `jax.numpy`, float32, under `jax.default_matmul_precision("highest")`.

Six architectures, picked by the configuration's published `model_type`
from `_ARCHS`, the table further down. `deepseek_v3`: written from the
published `config.json` of kanana-2-30b-a3b-instruct-2601. `afmoe`: from
Trinity-Mini's and, for what the config does not carry (the four norms
and where they sit, the gate, the QK-norm, rotary embedding on sliding
layers only, the embedding's scale), from the model's published
modelling code (`transformers` `models/afmoe`; docs/lm.md has both sets
of equations and what is `assumed`). `evabyte`: from EvaByte's and the
layer as docs/lm.md writes it down (`eva_attention` below: one head at a
time, the pooling as a dense `[chunks, positions]` matrix, one dense row
of scores over every key and every summary with both masks written out).
`lfm2_moe`: from LFM2-8B-A1B's and the layer as docs/lm.md writes it
down (`short_conv` below: the taps as explicit shifted sums, each source
position looked up with its document id beside it; `lfm2_layer`: the
mixer of a layer a convolution or `gated_attention` without its gate
and with the rotary embedding; the head is the embedding's transpose).
`smallthinker`: from SmallThinker-21BA3B-Instruct's and the layer as
docs/lm.md writes it down (`smallthinker_layer`: the router's logits
from the layer's input ahead of attention, a softmax over all experts,
the top few and their sum; `gated_attention` without gate or QK-norm,
window and rotary embedding by the two published layouts; ReLU-gated
experts, none shared).
`nemotron_h`: from NVIDIA-Nemotron-3-Super-120B-A12B's and the layers as
docs/lm.md writes them down (`nemotron_layer`: one norm and one block a
layer; `mamba2`: the state-space layer as the recurrence itself, token
by token, a `lax.scan` over the positions nested and checkpointed in
blocks, which shares nothing with the chunked form of ops/lm_ssm.py, the
convolution's taps as looked-up shifted sums; `gated_attention` without
gate, QK-norm or rotary embedding; `nemotron_moe`: the router on the
hidden width, two-matrix experts with a squared ReLU in the latent
space, the shared expert on the hidden width). Its configuration carries
the fields a control of the check changes (`state_carry`,
`document_reset`, `gate_before_norm`, `attention_rope`,
`router_reads_latent`, `mlp_hidden_act`, `gated_experts`, `d_skip`,
`without_layer`): this file alone reads them.
A row says what its architecture does by itself, from the published
keys: it reads none of the answers `config.DecoderConfig` derives for
models/lm (`post_norms`, `embed_scale`, `tie_embedding`, ...), so a wrong
one there still fails the comparison.
Independent of models/lm: no Flax module, no kernel, no
table, no sorting, no recomputation. Every held expert is applied to
every token and weighted by `w_i` where the router chose it and by 0
elsewhere; attention makes the dense `[heads, query rows, all keys]`
score matrix of one sequence with its mask written out, keys and values
repeated to the query heads. It reads the same parameter tree as the
system (a nested dict of arrays, named as models/lm names them), so both
run on the same seeded weights.

Departures from the published model, each also in docs/lm.md:
  * `e_score_correction_bias` b = 0: the config gives no update rule for
    it, so it is held at its initial value (`bias`, if given, is added
    to the scores for the choice only, as the model does).
  * the share: `experts_held`, `heads_held` and (afmoe) `kv_heads_held`
    name the experts and heads this chip of a tensor- and
    expert-parallel group holds. The router still scores and chooses
    over all experts; what the absent experts and heads would add is
    left out, here as in the system. The vocabulary slice is a smaller
    vocabulary: the embedding and the head have the rows that are held
    and the loss is over them.
  * afmoe's and lfm2_moe's `expert_bias` is the same buffer under
    another name, held at 0 alike; afmoe's `load_balance_coeff` belongs
    to the update rule that is not run.

`blocked_loss_and_grads` is the same mathematics walked a sequence and
a layer at a time (`jax.vjp` of one layer, inputs kept, layers revisited
in reverse), for sizes at which `jax.grad` of the whole loss does not
fit the chip. With `block`, an afmoe layer and the head also take a
block of rows at a time (`_by_blocks`: a sequential map whose backward
recomputes the block): 4 heads x 32,768 x 32,768 fp32 scores are 17 GB,
2,048 query rows of them 1 GB. tests/test_zz_lm_reference.py holds both
to `jax.grad` of `loss`.

`dtype=jnp.bfloat16` computes everything in bf16 (weights, router,
softmax, norm statistics, loss) at the default matmul precision: the
precision below the configuration's, which the benchmark's check has to
tell from it (PERF.md).
"""

from __future__ import annotations

import contextlib
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

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


def _rope_half(x, positions, theta):
    """Pairs (i, i + d/2) of the last axis rotated by
    position * theta^(-2i/d): `x * cos + rotate_half(x) * sin`.
    x [S, heads, d]; float32 angles."""
    d = x.shape[-1]
    i = jnp.arange(d // 2, dtype=jnp.float32)
    ang = (positions.astype(jnp.float32)[:, None]
           * theta ** (-2.0 * i / d))[:, None, :]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    lo, hi = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def _swiglu(x, p, act=jax.nn.silu):
    """W_down(act(W_gate x) * W_up x): a SwiGLU, or with `jax.nn.relu`
    smallthinker's ReGLU."""
    return (act(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def _by_blocks(f, block: Optional[int], *rows):
    """`f(*rows)` of arrays with one leading axis of rows, all at once
    or, given `block`, `block` rows at a time one after another, each
    recomputed in the backward. `f`'s result has the same leading axis."""
    n = rows[0].shape[0]
    if block is None or block >= n:
        return f(*rows)
    if n % block:
        raise ValueError(f"{n} rows are not whole blocks of {block}")
    cut = tuple(r.reshape((n // block, block) + r.shape[1:]) for r in rows)
    out = jax.lax.map(jax.checkpoint(lambda xs: f(*xs)), cut)
    return out.reshape((n,) + out.shape[2:])


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


def gated_attention(p, x, positions, segment_ids, cfg, heads: int,
                    kv_heads: int, window: Optional[int],
                    block: Optional[int] = None, gate: bool = True,
                    rope: Optional[bool] = None, qk_norm: bool = True):
    """One sequence of afmoe's mixer: x [S, D]. `p` holds `heads` query
    heads' columns and the `kv_heads` key/value heads they read, each
    serving `heads // kv_heads` of them in order. `window` None: a full
    layer, without a positional embedding unless `rope` says otherwise
    (lfm2_moe's attention layers: the rotary embedding, and no `gate`;
    smallthinker's: neither `gate` nor `qk_norm`)."""
    hd, eps = cfg.head_dim, cfg.rms_norm_eps
    s = x.shape[0]
    q = (x @ p["wq"]).reshape(s, heads, hd)
    if qk_norm:
        q = _rms_norm(q, p["q_norm"], eps)
    k = (x @ p["wk"]).reshape(s, kv_heads, hd)
    if qk_norm:
        k = _rms_norm(k, p["k_norm"], eps)
    v = (x @ p["wv"]).reshape(s, kv_heads, hd)
    if (window is not None) if rope is None else rope:
        q = _rope_half(q, positions, cfg.rope_theta)
        k = _rope_half(k, positions, cfg.rope_theta)
    k, v = (jnp.repeat(t, heads // kv_heads, axis=1) for t in (k, v))
    t = jnp.arange(s)

    def rows(q_rows, at, seg_rows):
        scores = (jnp.einsum("qhd,khd->hqk", q_rows, k)
                  / jnp.sqrt(jnp.asarray(hd, x.dtype)))
        back = at[:, None] - t[None, :]
        visible = (back >= 0) & (seg_rows[:, None] == segment_ids[None, :])
        if window is not None:
            visible &= back < window
        scores = jnp.where(visible[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)

    out = _by_blocks(rows, block, q, t, segment_ids).reshape(s, heads * hd)
    if gate:
        out = out * jax.nn.sigmoid(x @ p["wg"])
    return out @ p["wo"]


def _looked_up_taps(a, taps, segment_ids, within_documents: bool = True):
    """sum_j taps[:, j] a[m] [m >= 0 and d(m) = d(n)], m = n - (L-1) + j,
    for a [S, H] and taps [H, L]: each tap looks its source position up
    with its document id beside it (`within_documents` False: the row's
    start alone cuts a tap)."""
    n = jnp.arange(a.shape[0])
    length = taps.shape[1]
    total = jnp.zeros_like(a)
    for j in range(length):
        m = n - (length - 1) + j
        inside = m >= 0
        if within_documents:
            inside &= segment_ids[jnp.maximum(m, 0)] == segment_ids
        total = total + taps[:, j] * jnp.where(
            inside[:, None], a[jnp.maximum(m, 0)], 0.0)
    return total


def short_conv(p, x, segment_ids, cfg):
    """One sequence of lfm2_moe's convolution mixer: x [S, D].

        [B; C; z] = W_in x;   a_n = B_n * z_n
        c_n = sum_j k[:, j] a_m * [m >= 0 and d(m) = d(n)],  m = n-(L-1)+j
        out = W_out (C * c)

    Each tap looks its source position up (`a[m]`, `d[m]`), so a tap
    that would cross a document's first token reads zero as one before
    position 0 does: the document's outputs are what it gives alone."""
    b, c, z = jnp.split(x @ p["w_in"], 3, axis=-1)
    total = _looked_up_taps(b * z, p["taps"], segment_ids)
    return (c * total) @ p["w_out"]


def eva_attention(p, x, positions, segment_ids, cfg, heads: int,
                  block: Optional[int] = None):
    """One sequence of EvaByte's mixer: x [S, D]; `p` holds `heads`
    heads' columns, `phi` and `mu_k` their rows. Windows and chunks are
    cut by the row's positions t. Per head:

        chunk c:  D(c) = the document of the chunk's last non-pad position
                  a = softmax over the chunk's positions of document D(c)
                      of (k . phi) / sqrt(d);  kk_c = a k + mu, vv_c = a v
        query n:  one softmax over the keys m <= n of n's document in n's
                  window, and the summaries of the chunks c of n's
                  document (D(c) = d(n)) that lie in earlier windows

    which is what the document gives alone at the same row offset with
    every other position pad (tests/test_zz_lm_eva.py). A head at a
    time, one after another, each recomputed in the backward: a head's
    `[block rows, S + chunks]` scores are all that is held."""
    hd, s = cfg.head_dim, x.shape[0]
    chunk, window = cfg.chunk_size, cfg.window_size
    q = _rope_half((x @ p["wq"]).reshape(s, heads, hd), positions,
                   cfg.rope_theta)
    k = _rope_half((x @ p["wk"]).reshape(s, heads, hd), positions,
                   cfg.rope_theta)
    v = (x @ p["wv"]).reshape(s, heads, hd)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, x.dtype))
    t = jnp.arange(s)
    c = jnp.arange(-(-s // chunk))
    in_chunk = t[None, :] // chunk == c[:, None]              # [chunks, S]
    last = jnp.max(jnp.where(in_chunk & (segment_ids > 0)[None], t[None],
                             -1), axis=1)
    chunk_doc = jnp.where(last >= 0, segment_ids[jnp.maximum(last, 0)], 0)
    pooled = in_chunk & (segment_ids[None, :] == chunk_doc[:, None])
    chunk_window = c * chunk // window

    def head(args):
        q_h, k_h, v_h, phi, mu = args
        a = jax.nn.softmax(jnp.where(pooled, (k_h @ phi * scale)[None, :],
                                     -jnp.inf), axis=1)
        kk, vv = a @ k_h + mu, a @ v_h

        def rows(q_rows, at, seg_rows):
            exact = ((seg_rows[:, None] == segment_ids[None, :])
                     & (at[:, None] // window == t[None, :] // window)
                     & (t[None, :] <= at[:, None]))
            summary = ((chunk_doc[None, :] == seg_rows[:, None])
                       & (chunk_window[None, :] < at[:, None] // window))
            scores = jnp.concatenate(
                [jnp.where(exact, q_rows @ k_h.T * scale, -jnp.inf),
                 jnp.where(summary, q_rows @ kk.T * scale, -jnp.inf)],
                axis=1)
            return jax.nn.softmax(scores, axis=1) @ jnp.concatenate(
                [v_h, vv], axis=0)

        return _by_blocks(rows, block, q_h, t, segment_ids)

    per_head = (jnp.swapaxes(q, 0, 1), jnp.swapaxes(k, 0, 1),
                jnp.swapaxes(v, 0, 1), p["phi"], p["mu_k"])
    out = jax.lax.map(jax.checkpoint(head), per_head)         # [heads, S, d]
    return jnp.swapaxes(out, 0, 1).reshape(s, heads * hd) @ p["wo"]


def eva_layer(p, x, positions, segment_ids, cfg, heads_held: Share = None,
              block: Optional[int] = None):
    """h = x + Attn(N1(x)); x' = h + SwiGLU(N2(h)); one sequence. The
    norms' gains are `1 + g` under `norm_add_unit_offset`."""
    eps = cfg.rms_norm_eps
    h = x + eva_attention(
        p["attn"], _rms_norm(x, _gain(p["attn_norm"], cfg), eps), positions,
        segment_ids, cfg, (heads_held or cfg.heads_held)[1], block)
    normed = _rms_norm(h, _gain(p["ffn_norm"], cfg), eps)
    return h + _by_blocks(lambda rows: _swiglu(rows, p["mlp"]), block, normed)


def routing(p, x, cfg, bias=None):
    """(chosen expert ids [S, k], their weights [S, k]) over ALL experts."""
    scores = jax.nn.sigmoid(x @ p["router"])
    for_choice = scores if bias is None else scores + bias
    _, chosen = jax.lax.top_k(for_choice, cfg.num_experts_per_tok)
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + cfg.route_eps)
    return chosen, w * cfg.routed_scaling_factor


def moe(p, x, cfg, experts_held: Tuple[int, int], bias=None):
    """One sequence: the held experts' part of the routed sum, plus the
    shared experts where the model has them. Expert `first + j` has row j
    of `p["experts"]`."""
    chosen, w = routing(p["experts"], x, cfg, bias)
    out = (_swiglu(x, p["shared"]) if cfg.n_shared_experts
           else jnp.zeros_like(x))
    return _add_held_experts(out, p["experts"], x, chosen, w, experts_held)


def _add_held_experts(out, p, x, chosen, w, experts_held: Tuple[int, int],
                      act=jax.nn.silu):
    """`out` + every held expert applied to every token of `x`, weighted
    by `w` where the router chose it and by 0 elsewhere. Expert
    `first + j` has row j of `p`."""
    first, count = experts_held
    for j in range(count):
        w_j = jnp.sum(jnp.where(chosen == first + j, w, 0.0), axis=-1)
        expert = {k: p[k][j] for k in ("w_gate", "w_up", "w_down")}
        out = out + w_j[:, None] * _swiglu(x, expert, act)
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


def afmoe_layer(p, x, positions, segment_ids, cfg, index: int,
                heads_held: Share = None, kv_heads_held: Share = None,
                experts_held: Share = None, bias=None,
                block: Optional[int] = None):
    """h = x + N2(Attn(N1(x))); x' = h + N4(FFN(N3(h))); one sequence,
    layer `index` of the layers held."""
    eps = cfg.rms_norm_eps
    a = gated_attention(
        p["attn"], _rms_norm(x, p["attn_norm"], eps), positions, segment_ids,
        cfg, (heads_held or cfg.heads_held)[1],
        (kv_heads_held or cfg.kv_heads_held)[1], cfg.layer_window(index),
        block)
    h = x + _rms_norm(a, p["attn_post_norm"], eps)
    normed = _rms_norm(h, p["ffn_norm"], eps)
    if index < cfg.num_dense_layers:
        f = _by_blocks(lambda rows: _swiglu(rows, p["mlp"]), block, normed)
    else:
        f = _by_blocks(lambda rows: moe(p["moe"], rows, cfg,
                                        experts_held or cfg.experts_held,
                                        bias), block, normed)
    return h + _rms_norm(f, p["ffn_post_norm"], eps)


def lfm2_layer(p, x, positions, segment_ids, cfg, index: int,
               heads_held: Share = None, kv_heads_held: Share = None,
               experts_held: Share = None, bias=None,
               block: Optional[int] = None):
    """h = x + Mixer_l(N1(x)); x' = h + FFN(N2(h)); one sequence, layer
    `index` of the layers held: a convolution (whole whatever the share)
    or an attention over the heads held."""
    eps = cfg.norm_eps
    u = _rms_norm(x, p["attn_norm"], eps)
    if cfg.layer_types[index] == "conv":
        h = x + short_conv(p["conv"], u, segment_ids, cfg)
    else:
        h = x + gated_attention(
            p["attn"], u, positions, segment_ids, cfg,
            (heads_held or cfg.heads_held)[1],
            (kv_heads_held or cfg.kv_heads_held)[1], None, block,
            gate=False, rope=True)
    normed = _rms_norm(h, p["ffn_norm"], eps)
    if index < cfg.num_dense_layers:
        return h + _by_blocks(lambda rows: _swiglu(rows, p["mlp"]), block,
                              normed)
    return h + _by_blocks(lambda rows: moe(p["moe"], rows, cfg,
                                           experts_held or cfg.experts_held,
                                           bias), block, normed)


def smallthinker_moe(p, x, u, cfg, experts_held: Tuple[int, int]):
    """One sequence of smallthinker's expert layer: the router reads `x`
    (assumed A1: the layer's input, `router_before_attention`; else `u`),
    the experts read `u`.

        r = W_r x;   s = softmax(r) over all experts (or sigmoid(r))
        chosen = the top few of s;   w = s[chosen] / sum s[chosen]
        out = sum_chosen w_e W_down,e (relu(W_gate,e u) * W_up,e u)

    A softmax's chosen over their sum is the softmax over the chosen
    logits, which `norm_topk_prob` leaves as it is; sigmoid scores are
    divided by their sum only under it. Every held expert is applied to
    every token."""
    r = (x if cfg.router_before_attention else u) @ p["experts"]["router"]
    soft = cfg.moe_primary_router_apply_softmax
    scores = jax.nn.softmax(r, axis=-1) if soft else jax.nn.sigmoid(r)
    w, chosen = jax.lax.top_k(scores, cfg.moe_num_active_primary_experts)
    if soft or cfg.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return _add_held_experts(
        jnp.zeros_like(u), p["experts"], u, chosen, w, experts_held,
        {"relu": jax.nn.relu, "silu": jax.nn.silu}[cfg.hidden_act])


def smallthinker_layer(p, x, positions, segment_ids, cfg, index: int,
                       heads_held: Share = None, kv_heads_held: Share = None,
                       experts_held: Share = None,
                       block: Optional[int] = None):
    """h = x + Attn(N1(x)); x' = h + Experts(N2(h); routed on x); one
    sequence, layer `index` of the layers held: its window and its rotary
    embedding by the two published layouts."""
    eps = cfg.rms_norm_eps
    h = x + gated_attention(
        p["attn"], _rms_norm(x, p["attn_norm"], eps), positions, segment_ids,
        cfg, (heads_held or cfg.heads_held)[1],
        (kv_heads_held or cfg.kv_heads_held)[1],
        cfg.sliding_window_size if cfg.sliding_window_layout[index] else None,
        block, gate=False, rope=bool(cfg.rope_layout[index]), qk_norm=False)
    return h + _by_blocks(
        lambda x_rows, u_rows: smallthinker_moe(
            p["moe"], x_rows, u_rows, cfg, experts_held or cfg.experts_held),
        block, x, _rms_norm(h, p["ffn_norm"], eps))


# positions of one checkpointed block of `mamba2`'s recurrence
_SCAN_BLOCK = 256


def mamba2(p, u, segment_ids, cfg, heads: int, groups: int):
    """One sequence of nemotron_h's Mamba-2 mixer: u [S, D]; `p` holds
    `heads` heads' columns and the `groups` B/C groups they read, head h
    reading group `h // (heads // groups)`.

        [z; xBC; dt] = W_in u;  xBC = silu(conv(xBC) + b)  (taps looked up)
        dt = softplus(dt + dt_bias);  a = exp(dt A),  A = -exp(A_log)
        h_t = r_t a_t h_{t-1} + dt_t x_t (x) B_t;   y_t = h_t C_t + D x_t
        out = W_out RMSNorm_group(y * silu(z))

    with r_t = 0 at a document's first token. The recurrence is run as
    written, a position at a time; blocks of `_SCAN_BLOCK` positions are
    recomputed in the backward, so a row of 32,768 keeps 128 states and
    not every one."""
    s = u.shape[0]
    hp, n = cfg.mamba_head_dim, cfg.ssm_state_size
    inner, bc = heads * hp, groups * n
    proj = u @ p["in_proj"]
    z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * bc],
                  proj[:, 2 * inner + 2 * bc:])
    at = jnp.arange(s)
    total = _looked_up_taps(xbc, p["taps"], segment_ids, cfg.document_reset)
    xbc = jax.nn.silu(total + p["conv_bias"])
    x = xbc[:, :inner].reshape(s, heads, hp)
    b = jnp.repeat(xbc[:, inner:inner + bc].reshape(s, groups, n),
                   heads // groups, axis=1)
    c = jnp.repeat(xbc[:, inner + bc:].reshape(s, groups, n),
                   heads // groups, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    decay = jnp.exp(dt * -jnp.exp(p["A_log"]))                  # [S, heads]
    carried = jnp.ones((s,), bool).at[0].set(False)
    if cfg.document_reset:
        carried &= segment_ids == jnp.roll(segment_ids, 1)
    if not cfg.state_carry:
        carried &= at % cfg.chunk_size != 0
    decay = decay * carried[:, None].astype(decay.dtype)

    def token(h, xs):
        x_t, b_t, c_t, dt_t, a_t = xs
        h = (a_t[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return h, jnp.einsum("hpn,hn->hp", h, c_t)

    def rows(h, xs):
        return jax.lax.scan(token, h, xs)

    xs = (x, b, c, dt, decay)
    h0 = jnp.zeros((heads, hp, n), u.dtype)
    if s % _SCAN_BLOCK == 0 and s > _SCAN_BLOCK:
        cut = tuple(v.reshape((-1, _SCAN_BLOCK) + v.shape[1:]) for v in xs)
        _, y = jax.lax.scan(jax.checkpoint(rows), h0, cut)
        y = y.reshape(s, heads, hp)
    else:
        _, y = rows(h0, xs)
    if cfg.d_skip:
        y = y + p["D"][:, None] * x
    y, gate = y.reshape(s, groups, -1), jax.nn.silu(z).reshape(s, groups, -1)
    gain = p["norm"].reshape(groups, -1)
    eps = cfg.layer_norm_epsilon
    y = (_rms_norm(y * gate, gain, eps) if cfg.gate_before_norm
         else _rms_norm(y, gain, eps) * gate)
    return y.reshape(s, inner) @ p["out_proj"]


def nemotron_moe(p, u, cfg, experts_held: Tuple[int, int], bias=None):
    """One sequence of nemotron_h's expert layer: u [S, D], the layer's
    normed input.

        s = sigmoid(W_r u) over all experts (assumed A4: u, not z);
        the top few of s + b, their s over their sum, times the scale
        z = W_lat_down u;   Expert_i(z) = W_down,i relu(W_up,i z)^2
        out = W_lat_up sum_chosen w_i Expert_i(z) + W_sd relu(W_su u)^2

    Every held expert is applied to every token."""
    act = {"relu2": lambda v: jnp.square(jax.nn.relu(v)),
           "relu": jax.nn.relu}[cfg.mlp_hidden_act]
    z = u @ p["latent_down"]
    router, read = p["experts"]["router"], u
    if cfg.router_reads_latent:
        router, read = router[:z.shape[1]], z
    chosen, w = routing({"router": router}, read, cfg, bias)
    first, count = experts_held
    routed = jnp.zeros_like(z)
    for j in range(count):
        w_j = jnp.sum(jnp.where(chosen == first + j, w, 0.0), axis=-1)
        up = z @ p["experts"]["w_up"][j]
        mid = jax.nn.silu(up) * up if cfg.gated_experts else act(up)
        routed = routed + w_j[:, None] * (mid @ p["experts"]["w_down"][j])
    return (routed @ p["latent_up"]
            + act(u @ p["shared"]["w_up"]) @ p["shared"]["w_down"])


def nemotron_layer(p, x, positions, segment_ids, cfg, index: int,
                   ssm_heads_held: Share = None, heads_held: Share = None,
                   kv_heads_held: Share = None, experts_held: Share = None,
                   bias=None, block: Optional[int] = None):
    """x' = x + Block(N(x)); one sequence, layer `index` of the layers
    held: the block its letter of `hybrid_override_pattern` names."""
    kind = cfg.hybrid_override_pattern[index]
    eps = cfg.layer_norm_epsilon
    if index == cfg.without_layer:
        return x
    if kind == "E":
        return x + _by_blocks(
            lambda rows: nemotron_moe(p["moe"], rows, cfg,
                                      experts_held or cfg.experts_held, bias),
            block, _rms_norm(x, p["ffn_norm"], eps))
    u = _rms_norm(x, p["attn_norm"], eps)
    if kind == "M":
        heads = (ssm_heads_held or cfg.ssm_heads_held)[1]
        return x + mamba2(
            p["ssm"], u, segment_ids, cfg, heads,
            heads // (cfg.mamba_num_heads // cfg.n_groups))
    return x + gated_attention(
        p["attn"], u, positions, segment_ids, cfg,
        (heads_held or cfg.heads_held)[1],
        (kv_heads_held or cfg.kv_heads_held)[1], None, block, gate=False,
        rope=cfg.attention_rope, qk_norm=False)



def _gqa_cut(cfg, kv_heads_held: Share):
    hd = cfg.head_dim
    return {"wq": (hd, 1), "wg": (hd, 1), "wo": (hd, 0),
            "wk": (hd, 1, kv_heads_held), "wv": (hd, 1, kv_heads_held)}


def _typed(cfg, i: int):
    """A layer's program where `layer_types` says what its mixer is."""
    return i < cfg.num_dense_layers, cfg.layer_types[i]


class _Arch(NamedTuple):
    """What a `model_type` is."""
    # (p, x, positions, segment_ids, cfg, i, block=, **share): layer `i`
    layer: Callable
    # (cfg, kv_heads_held): a mixer's matrices that `take_share` cuts, as
    # (width a head, axis[, the heads held if not the query heads])
    cut: Callable
    # (cfg, i): what tells layer `i`'s program from another layer's
    kind: Callable = lambda cfg, i: None
    embed_scale: Callable = lambda cfg: 1.0
    # tokens a position predicts at once: the head's vocabularies
    pred_heads: Callable = lambda cfg: 1
    # a norm's gain is 1 + its parameter
    unit_offset: Callable = lambda cfg: False
    # the head is the embedding's own rows
    tied: bool = False


_ARCHS = {
    "deepseek_v3": _Arch(
        lambda p, x, pos, seg, cfg, i, block=None, **share: layer(
            p, x, pos, seg, cfg, i < cfg.first_k_dense_replace, **share),
        lambda cfg, kv: {
            "wq": (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, 1),
            "wkvb": (cfg.qk_nope_head_dim + cfg.v_head_dim, 1),
            "wo": (cfg.v_head_dim, 0)},
        lambda cfg, i: i < cfg.first_k_dense_replace),
    # `mup_enabled`: the embedding times sqrt(hidden_size)
    "afmoe": _Arch(afmoe_layer, _gqa_cut, _typed, embed_scale=lambda cfg: (
        cfg.hidden_size ** 0.5 if cfg.mup_enabled else 1.0)),
    "evabyte": _Arch(
        lambda p, x, pos, seg, cfg, i, **share: eva_layer(
            p, x, pos, seg, cfg, **share),
        lambda cfg, kv: dict(
            {k: (cfg.head_dim, 1) for k in ("wq", "wk", "wv")},
            wo=(cfg.head_dim, 0), phi=(1, 0), mu_k=(1, 0)),
        pred_heads=lambda cfg: cfg.num_pred_heads,
        unit_offset=lambda cfg: cfg.norm_add_unit_offset),
    "lfm2_moe": _Arch(lfm2_layer, _gqa_cut, _typed, tied=True),
    "smallthinker": _Arch(
        smallthinker_layer, _gqa_cut,
        lambda cfg, i: (cfg.sliding_window_layout[i], cfg.rope_layout[i])),
    "nemotron_h": _Arch(nemotron_layer, _gqa_cut,
                        lambda cfg, i: (cfg.hybrid_override_pattern[i],
                                        i == cfg.without_layer)),
}


def _layer_of(cfg, i: int, block: Optional[int] = None, **share):
    """Layer `i` as `f(p, x, positions, segment_ids)`."""
    run = _ARCHS[cfg.model_type].layer
    return lambda p, x, pos, seg: run(p, x, pos, seg, cfg, i, block=block,
                                      **share)


def _embed_scale(cfg, dtype):
    return jnp.asarray(_ARCHS[cfg.model_type].embed_scale(cfg), dtype)


def _pred_heads(cfg) -> int:
    return _ARCHS[cfg.model_type].pred_heads(cfg)


def _head(p, cfg):
    """The head's matrix [D, vocab]."""
    return p["embed"].T if _ARCHS[cfg.model_type].tied else p["head"]


def _gain(g, cfg):
    """A norm's gain from its parameter."""
    return 1.0 + g if _ARCHS[cfg.model_type].unit_offset(cfg) else g


def _targets(tokens, segment_ids, ahead: int = 1):
    """(targets, valid), each [S, ahead]: position t predicts, with its
    head j, token t+1+j where that lies in the row and in t's document;
    pad predicts nothing."""
    s = tokens.shape[0]
    t = jnp.arange(s)[:, None] + 1 + jnp.arange(ahead)[None, :]
    inside = t < s
    t = jnp.minimum(t, s - 1)
    valid = (inside & (segment_ids[t] == segment_ids[:, None])
             & (segment_ids[:, None] > 0))
    return tokens[t], valid


def head_loss_sum(p, x, tokens, segment_ids, cfg,
                  block: Optional[int] = None):
    """Sum of the cross-entropies of one sequence's targets."""
    ahead = _pred_heads(cfg)
    targets, valid = _targets(tokens, segment_ids, ahead)

    def rows(x_rows, targets, valid):
        logits = (_rms_norm(x_rows, _gain(p["final_norm"], cfg), cfg.rms_norm_eps)
                  @ _head(p, cfg)).reshape(x_rows.shape[0], ahead, -1)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, targets[..., None],
                                     axis=-1)[..., 0]
        return jnp.where(valid, picked, 0.0)

    return -jnp.sum(_by_blocks(rows, block, x, targets, valid))


def _cast(tree, dtype):
    return jax.tree.map(lambda a: jnp.asarray(a, dtype), tree)


def hidden_states(params, tokens, positions, segment_ids, cfg, **share):
    """One sequence through the stack: [S, D] before the final norm."""
    x = params["embed"][tokens] * _embed_scale(cfg, params["embed"].dtype)
    for i in range(cfg.num_hidden_layers):
        x = _layer_of(cfg, i, **share)(params[f"layers_{i}"], x, positions,
                                       segment_ids)
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
            rows.append(_rms_norm(x, _gain(params["final_norm"], cfg),
                                  cfg.rms_norm_eps) @ _head(params, cfg))
        return jnp.stack(rows)


def n_targets(batch, ahead: int = 1) -> jax.Array:
    return sum(jnp.sum(_targets(tok, seg, ahead)[1]) for tok, seg in
               zip(batch["tokens"], batch["segment_ids"]))


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
        return (total / jnp.maximum(n_targets(batch, _pred_heads(cfg)), 1)
                ).astype(jnp.float32)


def loss_and_grads(params, batch, cfg, dtype=jnp.float32, **share):
    return jax.value_and_grad(loss)(params, batch, cfg, dtype, **share)


def blocked_loss_and_grads(params, batch, cfg, dtype=jnp.float32,
                           block: Optional[int] = None, **share):
    """`loss_and_grads`, a sequence and a layer at a time: the forward
    keeps each layer's input, the backward takes `jax.vjp` of one layer
    at a time from the last to the first and adds the sequences'
    gradients up. Each kind of layer is jitted once and reused. `block`:
    rows at a time inside an afmoe layer and the head (module
    docstring)."""
    params = _cast(params, dtype)
    n_layers = cfg.num_hidden_layers
    denom = jnp.maximum(n_targets(batch, _pred_heads(cfg)), 1).astype(dtype)
    scale = _embed_scale(cfg, dtype)

    def jit_as(name, f):
        # the name a trace, a compile log and the set-up table
        # (`analysis.guards.setup_report`) give the program
        f.__name__ = f.__qualname__ = name
        return jax.jit(f)

    def vjp_of(run):
        def f(p, x, pos, seg, dy):
            _, pull = jax.vjp(lambda p, x: run(p, x, pos, seg), p, x)
            return pull(dy)
        return jit_as("reference_layer_vjp", f)

    def head_loss(p, x, tok, seg):
        return head_loss_sum(p, x, tok, seg, cfg, block) / denom

    kinds = [_ARCHS[cfg.model_type].kind(cfg, i) for i in range(n_layers)]
    runs = {k: _layer_of(cfg, kinds.index(k), block, **share)
            for k in set(kinds)}
    fwd = {k: jit_as("reference_layer", run) for k, run in runs.items()}
    bwd = {k: vjp_of(run) for k, run in runs.items()}
    # a tied head is the embedding: its gradient from the loss joins the
    # gather's below, under the one name
    tied = _ARCHS[cfg.model_type].tied
    top = {k: params[k] for k in ("final_norm", "embed" if tied else "head")}
    head = jit_as("reference_head_loss_and_grad",
                  jax.value_and_grad(head_loss, argnums=(0, 1)))
    embed_grad = jit_as("reference_embed_grad", lambda tok, dx: jnp.zeros_like(
        params["embed"]).at[tok].add(dx * scale))

    add = lambda acc, g: g if acc is None else jax.tree.map(jnp.add, acc, g)
    total = jnp.zeros((), jnp.float32)
    grads: Dict[str, Any] = {k: None for k in params}
    with _precision(dtype):
        for b in range(batch["tokens"].shape[0]):
            tok, pos, seg = (batch[k][b] for k in
                             ("tokens", "positions", "segment_ids"))
            inputs = [params["embed"][tok] * scale]
            for i in range(n_layers):
                inputs.append(fwd[kinds[i]](
                    params[f"layers_{i}"], inputs[-1], pos, seg))
            value, (g_top, dx) = head(top, inputs.pop(), tok, seg)
            total = total + value.astype(jnp.float32)
            for k in top:
                grads[k] = add(grads[k], g_top[k])
            for i in reversed(range(n_layers)):
                g, dx = bwd[kinds[i]](
                    params[f"layers_{i}"], inputs.pop(), pos, seg, dx)
                # a layer at a time on the device too: a call's results
                # are allocated when it is queued, and five layers'
                # gradients queued beside the kept inputs are 2.4 GB more
                # than the walk needs (my chip run, PR 31)
                dx = jax.block_until_ready(dx)
                grads[f"layers_{i}"] = add(grads[f"layers_{i}"], g)
            grads["embed"] = add(grads["embed"], embed_grad(tok, dx))
    return total, grads


def take_share(params, cfg, heads_held: Tuple[int, int],
               experts_held: Share = None, kv_heads_held: Share = None,
               ssm_heads_held: Share = None,
               shared_columns_held: Share = None):
    """From the parameters of a model that holds everything, the tree of
    the chip that holds `heads_held` and `experts_held`: the held heads'
    columns of `wq` and `wkvb` (afmoe: of `wq` and `wg`, and the held
    key/value heads' columns of `wk` and `wv`; evabyte: of `wq`, `wk`
    and `wv`, and the held heads' rows of `phi` and `mu_k`), their rows
    of `wo`, the held experts' matrices. The router, the latent projection, the
    norms, the shared experts, the embedding and the head are whole on
    every chip. nemotron_h: also the `ssm_heads_held` Mamba heads with
    their B/C groups (`_ssm_share`) and the `shared_columns_held`
    columns of the shared expert."""
    def heads(mat, per_head, axis, held=heads_held):
        lo, hi = held[0] * per_head, (held[0] + held[1]) * per_head
        return mat[:, lo:hi] if axis == 1 else mat[lo:hi]

    cut = _ARCHS[cfg.model_type].cut(cfg, kv_heads_held)

    out = dict(params)
    for i in range(cfg.num_hidden_layers):
        lp = dict(params[f"layers_{i}"])
        if "attn" in lp:  # a convolution mixer is whole on every chip
            lp["attn"] = dict(lp["attn"], **{
                k: heads(lp["attn"][k], *how) for k, how in cut.items()
                if k in lp["attn"]})
        if "ssm" in lp:
            lp["ssm"] = _ssm_share(lp["ssm"], cfg, ssm_heads_held)
        if "moe" in lp:
            e0, en = experts_held
            experts = dict(lp["moe"]["experts"])
            for k in experts.keys() & {"w_gate", "w_up", "w_down"}:
                experts[k] = experts[k][e0:e0 + en]
            lp["moe"] = dict(lp["moe"], experts=experts)
            if shared_columns_held is not None:
                c0, cn = shared_columns_held
                shared = lp["moe"]["shared"]
                lp["moe"]["shared"] = {
                    "w_up": shared["w_up"][:, c0:c0 + cn],
                    "w_down": shared["w_down"][c0:c0 + cn]}
        out[f"layers_{i}"] = lp
    return out


def _ssm_share(p, cfg, heads_held: Tuple[int, int]):
    """The Mamba-2 mixer's tree for the chip that holds `heads_held` of
    `cfg.mamba_num_heads` heads (whole groups): `in_proj`'s columns are
    [z; x; B; C; dt] and the convolution's channels [x; B; C], each cut
    to the held heads or to the groups they read."""
    h0, hn = heads_held
    per = cfg.mamba_num_heads // cfg.n_groups
    hp, n = cfg.mamba_head_dim, cfg.ssm_state_size
    inner, bc = cfg.mamba_num_heads * hp, cfg.n_groups * n
    heads = slice(h0 * hp, (h0 + hn) * hp)
    groups = slice(h0 // per * n, (h0 + hn) // per * n)

    def parts(mat, axis, pieces):
        mat = jnp.moveaxis(mat, axis, 0)
        return jnp.moveaxis(jnp.concatenate(
            [mat[at:at + size][cut] for at, size, cut in pieces]), 0, axis)

    conv = [(0, inner, heads), (inner, bc, groups), (inner + bc, bc, groups)]
    return dict(
        p, in_proj=parts(p["in_proj"], 1, [(0, inner, heads)] + [
            (at + inner, size, cut) for at, size, cut in conv] + [
            (2 * inner + 2 * bc, cfg.mamba_num_heads, slice(h0, h0 + hn))]),
        taps=parts(p["taps"], 0, conv),
        conv_bias=parts(p["conv_bias"], 0, conv),
        norm=p["norm"][heads], out_proj=p["out_proj"][heads],
        **{k: p[k][h0:h0 + hn] for k in ("dt_bias", "A_log", "D")})
