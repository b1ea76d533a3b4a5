"""The routed expert layer, told which experts it holds.

Routing is the whole model's, over all `n_routed_experts`, in fp32, by
the configuration's `route_score`:

  * `"sigmoid"` (`topk_method: noaux_tc`, one group: kanana, Trinity,
    LFM2): `s = sigmoid(W_r x)`, the top `num_experts_per_tok` of
    `s + b`, weights `s` of the chosen (without `b`) normalised to sum 1
    (over their sum and the configuration's `route_eps`) and scaled by
    `routed_scaling_factor`. `b` (`e_score_correction_bias`) is a buffer
    outside the gradient whose update rule the published configs do not
    give; it is carried in the `batch_stats` collection and held at zero
    (docs/lm.md, `assumed`).
  * `"softmax"` (SmallThinker): the top `num_experts_per_tok` of the
    logits `W_r x` and a softmax over the chosen, which sums to 1 by
    itself; no buffer is built.

What the router reads is the configuration's too (`router_reads`): the
tensor its experts read, inside `__call__`, or the layer's input ahead
of the mixer, through `plan` (models/lm/model.py `DecoderLayer`). Either
way `plan(x)` is the router and the dispatch table, everything that
depends on the routing and not on the experts' input, and
`__call__(x, plan)` the experts' part.

The routing is made once a step. `plan` puts what the experts' part and
the router's backward read of it under one name (`ROUTING`,
`checkpoint_name`): the chosen ids and their logits `[T, top_k]`, the
sorted order `[T * top_k]` and the `Plan` (a slot's token and weight,
the loads, the table), and the layer's checkpoint keeps that name and
nothing else (models/lm/model.py): five arrays of 4 B a slot and a few
KB, 14.4 MB a layer at 32,768 tokens by 22 (0.8-5.2 MB in the other
cells), where the fp32 logits `[T, experts]` of ONE recomputed product
are 67 MB. The backward then recomputes neither the `highest` product
`x W_r`, the top-k, the sort, the table nor `weights[order]`; what is
left of the router there is the `[T, top_k]` cotangent through the
score, its scatter into `[T, experts]` and the two products of the
matmul's own transpose. For that the score is taken AFTER the selection
(`_select`, `route`): the derivative of `sigmoid(take(logits))` reads
the kept `[T, top_k]` logits, that of `take(sigmoid(logits))` the whole
`[T, experts]` scores, which would keep the product alive; the values
are the same, a sigmoid being elementwise.

This chip then computes `sum_i w_i Expert_i(x)` over the chosen experts
it holds (`cfg.experts_held`). An expert is three matrices,
`W_down(act(W_gate x) * W_up x)`, or two, `W_down act(W_up x)`, with no
`w_gate` in the tree (`cfg.expert_gate`), `act` the configuration's
`expert_act`; the shared expert has the same form at `cfg.shared_width`
columns (all of `n_shared_experts * moe_intermediate_size`, or the
columns of it this chip holds: its activation is elementwise, so the
chips' column shares add up), and a configuration with
`n_shared_experts == 0` has none and nothing is built for it. Under
`cfg.moe_latent_size` the routed path runs in a latent space: the rows
are projected `hidden -> latent` ahead of the dispatch and the combined
sum back `latent -> hidden` (two matmuls of `MoE`, scope
`lm/moe/latent`, whole on every chip), so the rows that are gathered,
multiplied and added back have the latent width; the router and the
shared expert read the hidden width. What the absent experts and
columns would add is left out: in a deployment it arrives with the
expert-parallel sum. No code stands in for that exchange.

Dropless, in one program shape. A (token, choice) pair is a slot;
`T * top_k` slots exist and any number of them, up to all, may fall on
held experts. Slots are sorted by held expert (the others last), and
the sorted order is cut into chunks of `dispatch_chunk` rows. A chunk
gathers its tokens, runs the grouped products of its experts (three of
a gated MLP, two of an ungated one) with
the chunk's own group sizes, and adds the weighted rows back to their
tokens (below). Chunk 0 always runs; the later chunks are a scan under one
`lax.cond` that is taken only if held slots pass chunk 0, each of them
under a `lax.cond` of its own and a `jax.checkpoint`, so chunks that
seldom run keep nothing for the backward and cost nothing when skipped. At the published balance
(`T * top_k * held / experts` slots) chunk 0 is all there is; with every
token on one held expert every chunk runs. Work follows the slots, the
shape never changes, and no slot is dropped: `moe_dropped_slots` counts
held slots less the rows the chunks took, and stays 0.

The chunk is sized from the shapes (`dispatch_chunk`): a load that
reaches chunk 1 pays for a whole second chunk, so chunk 0 has to clear
the expected load with room; every row of room costs its gather
whether a slot fills it or not. `cfg.moe_chunk` overrides the size (the
tests' several chunks at toy sizes; a smaller chunk to save memory).

The later chunks' branch, around their checkpoint or inside it. A
`lax.cond` around a chunk's checkpoint hands the scan, for each of its
steps, a copy of what the checkpoint keeps, "the rows and the weights,
or zeros": temporaries nobody reads, a GB or so with a handful of later
chunks (kanana's 5 x 285 MB, Trinity's 5 x 335, LFM2's and
SmallThinker's 2 x 300), 6.2 GB with the 43 of 22 slots a token over 8
of 512 experts, where the step does not fit the chip. With the branch
inside the checkpoint the scan keeps a chunk's three arguments only,
and a chunk the slots do not reach adds a `[T, D]` of zeros. The second
form alone would be simpler, and it was measured (PERF.md, PR 48, call
5, parent | change on one seed): Trinity +0.06 %, LFM2 +0.005 %,
SmallThinker inside its noise, but kanana 7.266 -> 7.190 rows/s, -1.05 %
where the same program twice reads -0.02 %, 5.8 ms a step although no
later chunk ran in either: the compiler lays the step out otherwise
around a branch that never runs. So the branch stands around the
checkpoint while the copies that costs stay under `_STACKED_BYTES`, and
inside it past that; chosen from the shapes, and the result is the
same.

How rows come back. The sort is stable, so inside a held expert's run
the tokens ascend: for a block of `rows.block_tokens` tokens and one
expert, the rows that belong to the block are one contiguous range of
the order. `plan` counts each expert's slots by token block (the loads
are that table's column sums, so it costs no further pass), and the
table of where each range starts, cut to a chunk as the group sizes are,
goes with the chunk's rows to `ops/rows.py`: `gather_rows` takes them
out (`x[tokens]`) and `segment_add` adds them back with their weights,
each the other's transpose, the sum a blocked segment sum that writes
every `[T, D]` tile once and reads no row past the held slots. The
counters `moe_rows_covered` / `moe_rows_live` say how much of what that
sum fetches, in whole windows, is rows of a range.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from dexiraft_tpu.models.lm.layers import ActMLP, SwiGLU, Weights
from dexiraft_tpu.ops import rows as row_ops
from dexiraft_tpu.ops.grouped import grouped_matmul


# the name a layer's checkpoint keeps (models/lm/model.py): the routing
ROUTING = "lm_routing"


def _kept(a: jax.Array) -> jax.Array:
    """`a` under the name the layer's checkpoint keeps."""
    return checkpoint_name(a, ROUTING)


def _select(by: jax.Array, logits: jax.Array,
            top_k: int) -> Tuple[jax.Array, jax.Array]:
    """(ids `[T, k]` of the top `top_k` of `by` `[T, E]`, their entries
    of `logits`), both kept. The entries are taken with the kept ids, so
    that their derivative, a scatter into `[T, E]`, reads nothing the
    backward would have to select again."""
    chosen = _kept(jax.lax.top_k(by, top_k)[1])
    return chosen, _kept(jnp.take_along_axis(logits, chosen, axis=-1))


def route(logits: jax.Array, bias: jax.Array, top_k: int, scale: float,
          normalise: bool, eps: float) -> Tuple[jax.Array, jax.Array]:
    """(expert ids `[T, k]`, weights `[T, k]` fp32) from logits `[T, E]`
    fp32: the top `top_k` of `sigmoid(logits) + bias`, weighted by the
    sigmoids of their own logits, taken after the selection (module
    docstring: the same numbers, and a derivative that reads `[T, k]`)."""
    chosen, picked = _select(jax.nn.sigmoid(logits) + bias, logits, top_k)
    weights = jax.nn.sigmoid(picked)
    if normalise:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + eps)
    return chosen, weights * scale


def route_softmax(logits: jax.Array, top_k: int,
                  scale: float) -> Tuple[jax.Array, jax.Array]:
    """(expert ids `[T, k]`, weights `[T, k]` fp32) from logits `[T, E]`
    fp32: the top `top_k` and a softmax over them."""
    chosen, picked = _select(logits, logits, top_k)
    return chosen, jax.nn.softmax(picked, axis=-1) * scale


# a configuration's `expert_act` -> the gate function (of an expert
# without a gate: its activation)
ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu,
        "relu2": lambda x: jnp.square(jax.nn.relu(x))}

# what an expert layer counts of a batch, and how `reduce_counters`
# takes each over a stack's expert layers
COUNTERS = {"moe_slots_held": jnp.sum, "moe_load_max": jnp.max,
            "moe_load_mean": jnp.mean, "moe_dropped_slots": jnp.sum,
            "moe_rows_covered": jnp.sum, "moe_rows_live": jnp.sum}

# a dispatch chunk is a whole number of these rows
_CHUNK_ROWS = 8192
# the later chunks' branch stands around their checkpoint while the
# copies that costs stay under this (an eighth of a v5e's memory: the
# accepted cells' 0.55-1.7 GB), and inside it past it (6.2 GB; module
# docstring, "The later chunks' branch")
_STACKED_BYTES = 2 * 1024 ** 3


def dispatch_chunk(slots: int, held: int, experts: int) -> int:
    """Rows of one dispatch chunk for `slots` (token, choice) pairs when
    `held` of `experts` experts are here: the slots the held experts
    expect at an even balance and a third more, up to the next 8,192
    rows, and at most every slot. kanana2's share at 4 x 8192 tokens
    (24,576 expected): 32,768, what it has run at since PR 26 without
    reaching chunk 1; Trinity-Mini's at 32,768 tokens (32,768 expected,
    and at random weights a document's tokens route alike): 49,152,
    where 40,960 still ran the overflow a few times a window and 65,536
    cost 4.6 % in dead rows (my chip runs, PR 31)."""
    room = -(-4 * slots * held // (3 * experts))
    return min(slots, -(-room // _CHUNK_ROWS) * _CHUNK_ROWS)


class Plan(NamedTuple):
    """A batch's routing as the experts' part needs it: the slots sorted
    by held expert and cut into chunks, `[chunks, chunk]` each (a slot's
    token and its weight), the held experts' loads, and where in the
    order each expert's rows of each block of tokens start."""
    slot_token: jax.Array   # int32
    slot_weight: jax.Array  # fp32
    counts: jax.Array       # [held] int32: slots at each held expert
    starts: jax.Array       # [held]: where each one's run starts
    ends: jax.Array
    n_held: jax.Array       # their sum
    block_lo: jax.Array     # [held, token blocks + 1] int32


class RoutedExperts(Weights):
    cfg: Any = None  # a config.DecoderConfig

    def setup(self):
        cfg = self.cfg
        width = cfg.moe_intermediate_size
        # the width the experts read and write
        d = cfg.moe_latent_size or cfg.hidden_size
        held = cfg.experts_held[1]
        normal = nn.initializers.normal
        self.router = self.param(
            "router", normal(cfg.init_std),
            (cfg.hidden_size, cfg.n_routed_experts), jnp.float32)
        if cfg.route_score == "sigmoid":
            self.bias = self.variable(
                "batch_stats", "e_score_correction_bias",
                lambda: jnp.zeros((cfg.n_routed_experts,), jnp.float32))
        if cfg.expert_gate:
            self.w_gate = self.param("w_gate", normal(self.init_std),
                                     (held, d, width), jnp.float32)
        self.w_up = self.param("w_up", normal(self.init_std),
                               (held, d, width), jnp.float32)
        self.w_down = self.param("w_down", normal(self.init_std),
                                 (held, width, d), jnp.float32)

    def plan(self, x: jax.Array) -> Plan:
        """x `[T, D]`, the tensor the router reads -> the routing, every
        array of it under `ROUTING` (and the ids, their logits and the
        order with them): a checkpoint that keeps the name routes once."""
        cfg = self.cfg
        t = x.shape[0]
        top_k = cfg.num_experts_per_tok
        first, held = cfg.experts_held

        with jax.named_scope("lm/moe/router"):
            logits = jnp.matmul(x.astype(jnp.float32), self.router,
                                precision=jax.lax.Precision.HIGHEST)
            if cfg.route_score == "softmax":
                chosen, weights = route_softmax(logits, top_k,
                                                cfg.routed_scaling_factor)
            else:
                chosen, weights = route(
                    logits, self.bias.value, top_k,
                    cfg.routed_scaling_factor, cfg.norm_topk_prob,
                    cfg.route_eps)

        with jax.named_scope("lm/moe/dispatch"):
            local = chosen.reshape(-1) - first
            key = jnp.where((local >= 0) & (local < held), local, held)
            order = _kept(jnp.argsort(key, stable=True).astype(jnp.int32))
            # each held expert's slots by block of tokens; its load is
            # the sum over the blocks
            blocks = t // row_ops.block_tokens(t)
            by_block = jnp.sum(
                key.reshape(blocks, -1, 1) == jnp.arange(held),
                axis=1, dtype=jnp.int32)                  # [blocks, held]
            counts = jnp.sum(by_block, axis=0)                  # [held]
            n_held = jnp.sum(counts)
            ends = jnp.cumsum(counts)
            starts = ends - counts
            block_lo = jnp.concatenate(
                [starts[:, None], starts[:, None] + jnp.cumsum(by_block, 0).T],
                axis=1)
            chunk = min(cfg.moe_chunk or dispatch_chunk(
                t * top_k, held, cfg.n_routed_experts), t * top_k)
            n_chunks = -(-t * top_k // chunk)
            pad = n_chunks * chunk - t * top_k
            slot_token = jnp.pad(order // top_k, (0, pad)).reshape(
                n_chunks, chunk)
            slot_weight = jnp.pad(weights.reshape(-1)[order], (0, pad)
                                  ).reshape(n_chunks, chunk)
        return Plan(*map(_kept, (slot_token, slot_weight, counts, starts,
                                 ends, n_held, block_lo)))

    def __call__(self, x: jax.Array, plan: Optional[Plan] = None
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """x `[T, D]` at the experts' width, with its routing (None:
        routed on `x` itself) -> (this chip's part of the layer's output
        `[T, D]`, counters)."""
        t, d = x.shape
        slot_token, slot_weight, counts, starts, ends, n_held, block_lo = (
            self.plan(x) if plan is None else plan)
        n_chunks, chunk = slot_token.shape
        act = ACTS[self.cfg.expert_act]

        gated = self.cfg.expert_gate
        if gated:
            w_gate = self.w_gate.astype(self.dtype)
        w_up, w_down = (w.astype(self.dtype) for w in
                        (self.w_up, self.w_down))

        def chunk_lo(c):
            """The table for chunk `c`'s rows: cut to it as `sizes` is."""
            return jnp.clip(block_lo, c * chunk, (c + 1) * chunk) - c * chunk

        def run_chunk(c, tokens, wts):
            """Chunk `c`'s part of the output `[T, D]` fp32, and the
            rows it took."""
            lo = c * chunk
            sizes = (jnp.clip(ends, lo, lo + chunk)
                     - jnp.clip(starts, lo, lo + chunk))
            table = chunk_lo(c)
            # rows past the held slots are in no group, and the grouped
            # product leaves such rows unspecified (on the chip: whatever
            # the buffer held, NaN included). They are zeroed on the way
            # in and after every product, by a select: every value the
            # backward multiplies by is then finite, and a zero cotangent
            # stays zero
            live = (lo + jnp.arange(chunk) < n_held)[:, None]
            keep = lambda a: jnp.where(live, a, jnp.zeros((), a.dtype))
            with jax.named_scope("lm/moe/dispatch"):
                rows = keep(row_ops.gather_rows(x, tokens, table))
            with jax.named_scope("lm/moe/experts"):
                if gated:
                    gate = keep(grouped_matmul(rows, w_gate, sizes))
                    up = keep(grouped_matmul(rows, w_up, sizes))
                    mid = act(gate) * up
                else:
                    mid = act(keep(grouped_matmul(rows, w_up, sizes)))
                out = keep(grouped_matmul(mid, w_down, sizes))
            with jax.named_scope("lm/moe/combine"):
                part = row_ops.segment_add(out, tokens, wts, table, t)
            return part, jnp.sum(sizes)

        y, taken = run_chunk(0, slot_token[0], slot_weight[0])
        if n_chunks > 1:
            # what every later chunk reads alike: the rows and the weights
            shared = sum(a.size * a.dtype.itemsize for a in
                         (x, w_up, w_down) + ((w_gate,) if gated else ()))
            inside = (n_chunks - 1) * shared > _STACKED_BYTES

            def reached(c, tokens, wts):
                return jax.lax.cond(
                    c * chunk < n_held, lambda: run_chunk(c, tokens, wts),
                    lambda: (jnp.zeros((t, d), jnp.float32),
                             jnp.zeros((), jnp.int32)))

            later = jax.checkpoint(reached if inside else run_chunk)

            def body(carry, xs):
                y, taken = carry
                c, tokens, wts = xs
                if inside:
                    part, n = later(c, tokens, wts)
                    return (y + part, taken + n), None

                def add(y):
                    part, n = later(c, tokens, wts)
                    return y + part, n

                y, n = jax.lax.cond(
                    c * chunk < n_held, add,
                    lambda y: (y, jnp.zeros((), jnp.int32)), y)
                return (y, taken + n), None

            @jax.checkpoint
            def overflow(y):
                (y, n), _ = jax.lax.scan(
                    body, (y, jnp.zeros((), jnp.int32)),
                    (jnp.arange(1, n_chunks), slot_token[1:],
                     slot_weight[1:]))
                return y, n

            # one branch around the whole scan: at the usual balance no
            # later chunk runs, and then the scan must not cost its carry
            # copies either (176 ms a step; my chip run, PR 26)
            y, more = jax.lax.cond(
                n_held > chunk, overflow,
                lambda y: (y, jnp.zeros((), jnp.int32)), y)
            taken = taken + more

        # what the segment sums fetch, in whole windows, and the rows of
        # a range among it (every held slot, once)
        every = jax.vmap(chunk_lo)(jnp.arange(n_chunks))
        counters = {
            "moe_slots_held": n_held,
            "moe_load_max": jnp.max(counts),
            "moe_load_mean": jnp.mean(counts.astype(jnp.float32)),
            "moe_dropped_slots": n_held - taken,
            "moe_rows_covered": row_ops.WINDOW * jnp.sum(
                row_ops.windows(every[..., :-1], every[..., 1:])),
            "moe_rows_live": jnp.sum(every[..., 1:] - every[..., :-1]),
        }
        return y.astype(x.dtype), counters


class MoE(Weights):
    """Routed experts held here + the shared experts (one MLP of
    `cfg.shared_width` columns, gated as the routed ones are) and, under
    `cfg.moe_latent_size`, the two projections around the routed path.
    `plan(x)` routes on a tensor of the caller's choosing, `[..., D]`;
    `__call__(x, plan)` takes that routing, or routes on `x` itself."""

    cfg: Any = None  # a config.DecoderConfig

    def setup(self):
        cfg = self.cfg
        kw = dict(dtype=self.dtype, init_std=self.init_std)
        self.experts = RoutedExperts(cfg=cfg, **kw)
        if cfg.n_shared_experts:
            self.shared = (
                SwiGLU(width=cfg.shared_width, **kw) if cfg.expert_gate
                else ActMLP(width=cfg.shared_width,
                            act=ACTS[cfg.expert_act], **kw))
        if cfg.moe_latent_size:
            shape = (cfg.hidden_size, cfg.moe_latent_size)
            normal = nn.initializers.normal(self.init_std)
            self.latent_down = self.param("latent_down", normal, shape,
                                          jnp.float32)
            self.latent_up = self.param("latent_up", normal, shape[::-1],
                                        jnp.float32)

    def plan(self, x: jax.Array) -> Plan:
        return self.experts.plan(x.reshape(-1, x.shape[-1]))

    def __call__(self, x: jax.Array, plan: Optional[Plan] = None
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        flat = x.reshape(-1, x.shape[-1])
        if self.cfg.moe_latent_size:
            # the router reads the hidden width, the experts the latent
            plan = self.experts.plan(flat) if plan is None else plan
            with jax.named_scope("lm/moe/latent"):
                rows = flat @ self.latent_down.astype(self.dtype)
            routed, counters = self.experts(rows, plan)
            with jax.named_scope("lm/moe/latent"):
                routed = routed @ self.latent_up.astype(self.dtype)
        else:
            routed, counters = self.experts(flat, plan)
        if not self.cfg.n_shared_experts:
            return routed.reshape(x.shape), counters
        with jax.named_scope("lm/moe/shared"):
            shared = self.shared(flat)
        return (routed + shared).reshape(x.shape), counters


def reduce_counters(per_layer) -> Dict[str, jax.Array]:
    """Over the expert layers: slots and drops summed, the fullest
    expert's load, the mean load. A stack without one holds no slot and
    drops none."""
    if not per_layer:
        return {"moe_slots_held": jnp.zeros((), jnp.int32),
                "moe_dropped_slots": jnp.zeros((), jnp.int32)}
    stack = {k: jnp.stack([c[k] for c in per_layer]) for k in per_layer[0]}
    return {k: over(stack[k]) for k, over in COUNTERS.items()}
