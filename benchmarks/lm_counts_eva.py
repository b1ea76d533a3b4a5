"""FLOPs and bytes of the EvaByte cells (`EvaByteConfig`), counted from
shapes: `lm_counts.py`'s account for the third architecture.

Every layer is dense: four projections of the held heads, the SwiGLU
whole, no router, no slot. The mixer's pairs are of two kinds, both
counted on the host from the rows, exactly, and never from the kernel's
block pairs or the program's static prefixes (which also hold what a
tile or a product computes and masks):

  local   (query, key) pairs with the key in the query's document and
          window, not after it
  remote  (query, summary) pairs: the chunks whose document (that of the
          chunk's last non-pad position) is the query's and whose window
          is an earlier one

A pair of either kind is a score and a weighted value over `head_dim`
for each held head. tests/test_zz_lm_counts.py holds both to a brute-force
count and the dense parts to `flops.py`'s walk of the plain reference.

`attention_roofline_seconds` is the least time the chip could take for
the needed pairs of a step, call by call as `lm_counts_afmoe` counts its
kernels': whatever computes them (a kernel, XLA's batched products).
"""

from __future__ import annotations

from typing import Dict

from benchmarks.lm_counts_afmoe import attention_calls


def layers_by_kind(cfg) -> Dict[str, int]:
    """Every layer has both parts."""
    return {"local": cfg.num_hidden_layers, "remote": cfg.num_hidden_layers}


def per_token_forward(cfg) -> Dict[str, float]:
    """Forward FLOPs a real token, by part, summed over the layers held:
    every product whose size does not depend on the documents. Pooling:
    a logit (k . phi) and the weighted sums of k and v, each over
    `head_dim`, a held head."""
    d, hd, heads = cfg.hidden_size, cfg.head_dim, cfg.heads_held[1]
    layers = cfg.num_hidden_layers
    return {
        "projections": layers * 4 * 2 * d * hd * heads,  # W_q, W_k, W_v, W_o
        "mlp": layers * 3 * 2 * d * cfg.intermediate_size,
        "pooling": layers * heads * 3 * 2 * hd,
        "head": 2 * d * cfg.vocab_size * cfg.num_pred_heads,
    }


def per_pair_forward(cfg) -> float:
    """Forward FLOPs of one (query, key) or (query, summary) pair of one
    layer over the held heads."""
    return cfg.heads_held[1] * 2 * (cfg.head_dim + cfg.head_dim)


def pairs_in_row(segment_ids, window: int, chunk: int) -> Dict[str, int]:
    """`local` and `remote` pairs of ONE row. numpy, a document at a
    time: a document that starts at row position `a` and ends before `b`
    is cut by the windows it crosses; the piece of `n` positions in one
    window has n (n + 1) / 2 exact pairs, and every one of its queries
    meets the document's summaries of earlier windows. Those are the
    chunks from the document's first whole-or-trailing chunk (the first
    chunk whose last non-pad position is the document's) up to the
    window's start."""
    import numpy as np

    seg = np.asarray(segment_ids)
    real = np.flatnonzero(seg > 0)
    end_of_real = int(real[-1]) + 1 if real.size else 0
    edges = np.flatnonzero(np.diff(seg, prepend=0, append=0))
    local = remote = 0
    for a, b in zip(edges[:-1], edges[1:]):
        a, b = int(a), int(b)
        if seg[a] == 0:
            continue
        # the chunk that holds `a` is this document's if the document
        # reaches the chunk's last non-pad position
        first = a // chunk
        chunk_end = min((first + 1) * chunk, end_of_real)
        if b < chunk_end:
            first += 1
        for w in range(a // window, (b - 1) // window + 1):
            lo, hi = max(a, w * window), min(b, (w + 1) * window)
            n = hi - lo
            local += n * (n + 1) // 2
            remote += n * max(0, w * window // chunk - first)
    return {"local": local, "remote": remote}


def pairs_by_kind(cfg, segment_ids) -> Dict[str, float]:
    """The pairs ONE layer needs for rows `[B, S]`, keyed as
    `layers_by_kind` is."""
    import numpy as np

    out = {"local": 0.0, "remote": 0.0}
    for row in np.asarray(segment_ids):
        for kind, n in pairs_in_row(row, cfg.window_size,
                                    cfg.chunk_size).items():
            out[kind] += float(n)
    return out


def step_flops(cfg, tokens_real: float, slots_held: float,
               pairs: Dict[str, float]) -> Dict[str, float]:
    """FLOPs one step needs (forward + backward = 3x forward), by part.
    `pairs` is `pairs_by_kind` of the batch; `slots_held` is 0 (no
    expert layer) and adds nothing."""
    kinds = layers_by_kind(cfg)
    parts = {k: 3 * v * tokens_real for k, v in per_token_forward(cfg).items()}
    parts["attention"] = 3 * per_pair_forward(cfg) * sum(
        kinds[k] * pairs[k] for k in kinds)
    parts["total"] = sum(parts.values())
    return parts


def attention_roofline_seconds(pairs: float, layers: int, tokens: float,
                               heads: int, head_dim: int, remat: bool,
                               peaks: dict) -> Dict[str, float]:
    """The least time the chip could take for a step's mixer over
    `layers` layers that each need `pairs` pairs (local + remote): call
    by call (`lm_counts_afmoe.attention_calls`: 2 products a pair and
    head forward, 2 recomputed, 3 for dq, 4 for dk and dv) the larger of
    FLOPs over the bf16 peak and bytes over the HBM bandwidth, summed. A
    product of a pair and head is 2 x head_dim FLOP; a call moves q, k,
    v, o or their gradients once, bf16, for the `tokens` positions of the
    batch. The summaries (a `chunk`-th of the keys) and the pooling's
    products (under a thousandth of the pairs') are left out, so the
    share errs low."""
    per_call_bytes = tokens * head_dim * 2 * 5 * heads
    flops = bytes_ = seconds = 0.0
    for products in attention_calls(remat).values():
        call_flops = layers * pairs * heads * products * 2 * head_dim
        call_bytes = layers * per_call_bytes
        flops += call_flops
        bytes_ += call_bytes
        seconds += max(call_flops / peaks["bf16_flops_per_s"],
                       call_bytes / peaks["hbm_bytes_per_s"])
    return {"flops": flops, "bytes": bytes_, "seconds": seconds}
