"""Multi-head latent attention without a query LoRA (`q_lora_rank:
null`), for the heads this chip holds.

    q            = W_q x            -> per head [q_nope; q_rope]
    [c; k_rope]  = W_kva x          k_rope is shared by every head
    [k_nope; v]  = W_kvb RMSNorm(c) -> per head
    scores       = (q_nope . k_nope + rope(q_rope) . rope(k_rope)) / sqrt(d_qk)
    out          = W_o concat_heads(softmax(scores) v)

`W_q`, `W_kvb` and `W_o` hold the columns (rows) of the held heads only;
the latent projection `W_kva` and its norm are whole on every chip of
the group. What the absent heads would add to `out` is left out: in a
deployment it arrives with the tensor-parallel sum.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from dexiraft_tpu.config import LMConfig
from dexiraft_tpu.models.lm.layers import Weights, rms_norm, rope_interleaved
from dexiraft_tpu.ops.lm_attention import document_attention


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
