"""Shared by the tests/test_zz_lm_*.py files: the toy configurations of
the five architectures, a packed batch and seeded weights of O(1)
scale."""

import collections
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np

from dexiraft_tpu.config import (TrainConfig, evabyte_toy, kanana2_toy,
                                 lfm2_8b_a1b_toy, smallthinker_21b_toy,
                                 trinity_mini_toy)
from dexiraft_tpu.interop import lm_reference as ref
from dexiraft_tpu.train.family import family_of

from _models import as_one_program


def packed_batch(cfg, rows=2, seed=0):
    """Rows of `cfg.seq_len`: documents of 50, 40 and 30 tokens, then
    pad; positions restart, segment ids 1, 2, 3, 0."""
    rng = np.random.default_rng(seed)
    s = cfg.seq_len
    docs = (50, 40, 30)
    seg = np.zeros((rows, s), np.int32)
    pos = np.zeros((rows, s), np.int32)
    at = 0
    for i, n in enumerate(docs, start=1):
        seg[:, at:at + n] = i
        pos[:, at:at + n] = np.arange(n)
        at += n
    tokens = rng.integers(0, cfg.vocab_size, (rows, s)).astype(np.int32)
    tokens[seg == 0] = 0
    return {k: jnp.asarray(v) for k, v in
            (("tokens", tokens), ("positions", pos), ("segment_ids", seg))}


@functools.lru_cache(maxsize=None)
def seeded(cfg, precision="fp32", remat="none", seed=1, scale=5.0):
    """(family, params, batch_stats): matrices scaled up from the 0.02
    init so that every path carries signal at toy widths (evabyte's
    0.01275 to the same size). Kept on its arguments: do not write into
    the trees."""
    family = family_of(cfg, TrainConfig(precision=precision, remat=remat))
    params, stats = jax.jit(family.init)(jax.random.PRNGKey(seed))
    scale = scale * 0.02 / cfg.init_std
    params = jax.tree.map(lambda p: p * scale if p.ndim > 1 else p, params)
    return family, params, stats


# (params, batch, cfg, **share): eager it is a few thousand dispatches,
# each with a compile of its own
reference_loss_and_grads = as_one_program(ref.loss_and_grads)


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def routing_ops(text, tokens, experts, top_k):
    """What a lowered step (`.lower(...).as_text()`) holds of a routing
    of `tokens` tokens over `experts` experts, as `main` runs it (a
    function called n times counts n times): the router's products (a
    `dot_general` with `[tokens, experts]` fp32 as its result, the
    forward one, or as an operand, the two of its transpose), the top-k
    selections, the sorts of the `tokens * top_k` slots and the gathers
    of a float a slot (`weights[order]`)."""
    slots = tokens * top_k
    patterns = {
        "products": rf"stablehlo\.dot_general.*tensor<{tokens}x{experts}xf32>",
        "top_k": r"chlo\.top_k",
        "sorts": rf'"stablehlo\.sort"\((?:(?!stablehlo\.sort)[\s\S])*?'
                 rf"\}}\) : \(tensor<{slots}xi32>",
        "weight_gathers": rf"stablehlo\.gather.*\(tensor<{slots}xf32>,"}
    at = [(m.group(1), m.start()) for m in re.finditer(
        r"func\.func (?:public |private )?@([\w.\-]+)\(", text)]
    bodies = {name: text[start:end] for (name, start), (_, end) in
              zip(at, at[1:] + [(None, len(text))])}

    @functools.lru_cache(maxsize=None)
    def held(name):
        found = collections.Counter(
            {k: len(re.findall(p, bodies[name])) for k, p in patterns.items()})
        for callee in re.findall(r"[ .]call @([\w.\-]+)\(", bodies[name]):
            found.update(held(callee))
        return found

    return dict(held("main"))


ARCHS = {"kanana2": kanana2_toy, "trinity": trinity_mini_toy,
         "evabyte": evabyte_toy, "lfm2": lfm2_8b_a1b_toy,
         "smallthinker": smallthinker_21b_toy}
# a share of each toy: experts 2-5; kanana's heads 1-2, trinity's query
# heads 2-3, which read key/value head 1; evabyte's heads 2-3 (it has no
# experts); lfm2's query heads 4-7, the whole group of key/value head 1;
# smallthinker's query heads 7-13, the whole group of 7 of key/value head 1.
# The packed batch's documents start at 50 and 90: inside a chunk of 4
# and inside a window of 32
SHARES = {"kanana2": dict(experts_held=(2, 4), heads_held=(1, 2)),
          "trinity": dict(experts_held=(2, 4), heads_held=(2, 2)),
          "evabyte": dict(heads_held=(2, 2)),
          "lfm2": dict(experts_held=(2, 4), heads_held=(4, 4)),
          "smallthinker": dict(experts_held=(2, 4), heads_held=(7, 7))}


def toy(arch="kanana2", **kw):
    return ARCHS[arch](**kw)


def brute_force_eva_pairs(seg, window, chunk):
    """{"local", "remote"} of one row of segment ids by EVA's definition,
    pair by pair: (query, key) pairs in one document and window with the
    key not after the query; (query, summary) pairs with the chunk's
    document (that of its last non-pad position) the query's and the
    chunk's window an earlier one."""
    seg = [int(d) for d in seg]
    n = len(seg)
    chunk_doc = []
    for c in range(-(-n // chunk)):
        ids = [d for d in seg[c * chunk:(c + 1) * chunk] if d > 0]
        chunk_doc.append(ids[-1] if ids else 0)
    local = remote = 0
    for q in range(n):
        if seg[q] == 0:
            continue
        local += sum(1 for m in range(q + 1)
                     if seg[m] == seg[q] and m // window == q // window)
        remote += sum(1 for c, d in enumerate(chunk_doc)
                      if d == seg[q] and c * chunk // window < q // window)
    return {"local": local, "remote": remote}
