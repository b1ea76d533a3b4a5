"""Configuration tree for the framework.

One resolved, immutable config replaces the reference's three independent
argparse blocks plus the args-namespace mutation inside RAFT.__init__
(core/raft.py:37-53) — configs here are frozen dataclasses, resolved once.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

# storage precisions for the correlation volume / fmap2 pyramid
# (ops/quant.py implements them; lives here jax-free so CLI parser
# construction — including `serve --help` — doesn't pay the jax import).
# int8 is an inference format: its round() kills fmap gradients, so the
# model refuses to train with it (models/raft.py).
CORR_DTYPES = ("fp32", "bf16", "int8")

# correlation implementations: the materialized MXU volume, the XLA
# on-demand path, and the flash-blocked Pallas kernel (fmap2 streamed
# from HBM in row blocks — O(fmaps) memory at any geometry;
# ops/pallas_corr.py). The one list every front end's --corr_impl
# choices are built from. Jax-free for the same CLI-parser reason as
# CORR_DTYPES.
CORR_IMPLS = ("allpairs", "local", "flash")


def resolve_corr_impl(impl: str, platform: str) -> Tuple[str, bool]:
    """Resolve an eval/serve CLI ``--corr_impl`` value to a concrete
    (corr_impl, fused_update) pair.

    "auto" is the production default: on TPU it resolves to the
    flash-blocked fused step (corr_impl="flash", fused_update=True) —
    the O(fmaps)-memory configuration; chip_smoke.py runs it on the chip
    against allpairs and tests/test_chip_compile.py compiles it for
    v5e. Off-TPU it falls back to the materialized volume: Pallas
    kernels only run off-chip in interpreter mode, which is debug-speed,
    not serving-speed. Explicit values pass through with
    fused_update=False (the CLI's --fused_update flag overrides).
    """
    if impl == "auto":
        return ("flash", True) if platform == "tpu" else ("allpairs", False)
    return impl, False


def resolve_corr_impl_args(args, platform: str, label: str) -> Tuple[str, bool]:
    """The eval/serve CLI glue around :func:`resolve_corr_impl`: merge
    the --fused_update flag into the resolution, refuse fused on a
    non-kernel impl with a one-line actionable error, and announce what
    "auto" resolved to. ONE copy so the two CLIs cannot drift."""
    impl, fused_auto = resolve_corr_impl(args.corr_impl, platform)
    fused = args.fused_update or fused_auto
    if fused and impl != "flash":
        raise SystemExit(f"{label}: --fused_update requires --corr_impl "
                         "flash (pass it explicitly — 'auto' resolves to "
                         "allpairs off-TPU)")
    if args.corr_impl == "auto":
        print(f"[{label}] corr_impl auto -> {impl}"
              f"{' + fused_update' if fused else ''}", flush=True)
    return impl, fused


@dataclasses.dataclass(frozen=True)
class RAFTConfig:
    """Architecture config covering the reference's five experiment variants
    (SURVEY.md §2.5):

      v1  variant='raft'                       vanilla RAFT, image stream only
      v2  variant='early'                      6-ch early fusion (image ⊕ edge image from data)
      v3  variant='separate'                   dual stream, edges from data, decoupled
                                               updates + RefineFlow fusion
      v4  variant='early',  embed_dexined=True 10-ch early fusion (image ⊕ 7 DexiNed logit maps)
      v5  variant='dual',   embed_dexined=True dual stream w/ embedded frozen DexiNed,
                                               shared update block, coupled Δf+Δef update
    """

    variant: str = "raft"  # raft | early | separate | dual
    small: bool = False
    embed_dexined: bool = False
    corr_levels: int = 4
    corr_radius: Optional[int] = None  # None -> 4 full / 3 small (core/raft.py:37-47)
    dropout: float = 0.0
    mixed_precision: bool = False  # bf16 compute in encoders/update; corr stays fp32
    # allpairs = materialized MXU volume; local/flash = on-demand
    # paths (flash is the blocked HBM-streaming kernel — the production
    # eval/serve default on TPU via resolve_corr_impl("auto", ...))
    corr_impl: str = "allpairs"
    # STORAGE precision of the correlation pyramid (allpairs: the
    # materialized volume levels; local/flash: the fmap2 pyramid the
    # lookup streams) — "fp32" | "bf16" | "int8" (per-level scale,
    # dequantized inside the consuming matmul/kernel, ops/quant.py).
    # Correlation math stays fp32-accumulated on every path; this knob
    # only changes the HBM bytes each refinement iteration moves. int8
    # is inference-only (gradients to the quantized operand are dead)
    corr_dtype: str = "fp32"
    # fuse each refinement iteration's 4-level window lookup WITH the
    # motion encoder's 1x1 corr conv into ONE Pallas kernel
    # (ops/pallas_corr.flash_fused_step): the
    # (2r+1)^2-per-level corr features never round-trip HBM — only the
    # conv's F-channel output does. Requires corr_impl="flash";
    # parameter tree is IDENTICAL to the unfused path, so checkpoints interchange
    # (models/update.py FusedCorrEncoder)
    fused_update: bool = False
    # rows per chunk for the local path's gather (bounds the transient
    # patch buffer to rows*W*(2r+2)^2*C floats; None = whole frame at once)
    corr_row_chunk: Optional[int] = 8
    # rematerialize each refinement iteration in the backward pass:
    # activations of the scanned step are recomputed instead of stored,
    # trading FLOPs for HBM (jax.checkpoint over the scan body)
    remat: bool = False
    # what the per-iteration checkpoint SAVES when remat=True:
    #   "full"          — save nothing, recompute everything (the
    #                     historical behavior; max HBM savings)
    #   "dots_saveable" — save matmul/conv outputs, recompute the cheap
    #                     elementwise chains (jax.checkpoint_policies.
    #                     dots_saveable): most of the memory win at a
    #                     fraction of the recompute FLOPs — the middle
    #                     point the train_bench HBM columns quantify
    remat_policy: str = "full"
    # rematerialize ONLY the correlation lookup: drops the per-iteration
    # hats and tap rows (until PR 32 their (9, W) trailing dims lane-padded
    # ~10x: the v5e compiler wanted 33.6 GiB at batch 10, 368x496 without
    # remat and 20.2 with this — docs/perf.md; not compiled again since)
    # at a fraction of full remat's recompute cost.
    # Numerically identical; composes with (and is implied by) remat
    remat_lookup: bool = False
    # transposed-conv implementation inside the embedded DexiNed's
    # upsamplers: "transpose" (lax.conv_transpose) or "subpixel" (the
    # numerically identical phase-decomposed form — dense half-res convs
    # instead of an input-dilated full-res conv; see models/dexined.py).
    # "subpixel" is the default (its speed against "transpose" is not
    # measured on today's code — ROADMAP D2).
    dexined_upconv: str = "subpixel"
    # unroll factor for the refinement-loop scan (lax.scan unroll): >1
    # lets XLA software-pipeline consecutive iterations (fuse the next
    # lookup's hat-matrix build with the current GRU) at the cost of
    # code-size/compile time. Numerically identical; eval-latency knob
    scan_unroll: int = 1
    # convergence gate for the ADAPTIVE inference path (models/raft.py
    # adaptive=True): an item freezes once the mean per-pixel L2 norm of
    # its 1/8-res flow delta drops below this. 0.0 disables the gate
    # (the norm is >= 0, so `norm < 0` never fires) — the while_loop
    # then runs exactly `iter_budget` iterations and is bit-exact with
    # the fixed scan at the same count (pinned in tests). The default
    # is the EPE-vs-latency frontier point measured in docs/perf.md:
    # within 0.05 px of fixed-32 at >= 25% fewer mean iterations
    converge_tol: float = 0.02

    def __post_init__(self):
        # config-time refusals (ISSUE 12 satellite): an unknown
        # corr_impl / corr_dtype / fused_update combination fails HERE,
        # at construction, not as a store_corr ValueError deep inside
        # build_local_corr mid-trace. Runtime-dependent checks (int8
        # under train=True) stay in models/raft.py.
        if self.corr_impl not in CORR_IMPLS:
            raise ValueError(
                f"unknown corr_impl {self.corr_impl!r}; expected one of "
                f"{CORR_IMPLS}")
        if self.corr_dtype not in CORR_DTYPES:
            raise ValueError(
                f"unknown corr_dtype {self.corr_dtype!r}; expected one "
                f"of {CORR_DTYPES}")
        if self.fused_update and self.corr_impl != "flash":
            raise ValueError(
                "fused_update=True requires corr_impl='flash' (the "
                "blocked HBM-streaming kernel — the production default); "
                "the allpairs volume cannot be tiled per pixel block")
        if self.remat_policy not in ("full", "dots_saveable"):
            raise ValueError(
                f"unknown remat_policy {self.remat_policy!r}; expected "
                "'full' or 'dots_saveable'")
        if self.converge_tol < 0:
            raise ValueError(
                f"converge_tol must be >= 0 (a flow-delta NORM threshold; "
                f"0 disables the gate), got {self.converge_tol}")

    @property
    def radius(self) -> int:
        return self.corr_radius if self.corr_radius is not None else (3 if self.small else 4)

    @property
    def hidden_dim(self) -> int:
        return 96 if self.small else 128

    @property
    def context_dim(self) -> int:
        return 64 if self.small else 128

    @property
    def fnet_dim(self) -> int:
        return 128 if self.small else 256

    @property
    def corr_planes(self) -> int:
        return self.corr_levels * (2 * self.radius + 1) ** 2

    @property
    def image_channels(self) -> int:
        if self.variant == "early":
            return 10 if self.embed_dexined else 6
        return 3

    @property
    def has_edge_stream(self) -> bool:
        return self.variant in ("separate", "dual")


def raft_v1(**kw) -> RAFTConfig:
    return RAFTConfig(variant="raft", **kw)


def raft_v2(**kw) -> RAFTConfig:
    return RAFTConfig(variant="early", embed_dexined=False, **kw)


def raft_v3(**kw) -> RAFTConfig:
    return RAFTConfig(variant="separate", **kw)


def raft_v4(**kw) -> RAFTConfig:
    return RAFTConfig(variant="early", embed_dexined=True, **kw)


def raft_v5(**kw) -> RAFTConfig:
    return RAFTConfig(variant="dual", embed_dexined=True, **kw)


# experiment-variant name -> constructor: the --variant surface shared by
# the train/eval/serve CLIs. Lives here (jax-free) so parser construction
# — including `serve --help` and the --workers pool parent, which never
# run the model — doesn't pay the jax import.
VARIANTS = {
    "v1": raft_v1, "raft": raft_v1,
    "v2": raft_v2, "early": raft_v2,
    "v3": raft_v3, "separate": raft_v3,
    "v4": raft_v4,
    "v5": raft_v5, "dual": raft_v5,
}


# what a router's normalisation adds to the chosen scores' sum (a
# configuration's `route_eps`) where the published code adds nothing
# that fp32 sees
ROUTE_EPS = 1e-20


class DecoderConfig:
    """What models/lm and `train` read of a language model's
    configuration, each name with the answer of a decoder that has
    nothing special: every layer grouped-query attention over the whole
    document with a rotary embedding and a dense SwiGLU, two pre-norms a
    layer, an untied head of one vocabulary.

    An architecture is a frozen dataclass that derives from this, holds
    its published keys, its share and the row's length, validates them,
    and overrides the answers that differ (docs/lm.md, "Adding an
    architecture", lists the fields). The shared modules read these
    names and never a configuration's class. A stack whose layers hold a
    mixer or a feed-forward part alone says so layer by layer
    (`layer_parts`); experts of two matrices, a latent space around the
    routed path and a share of the shared expert's columns are
    `expert_gate`, `moe_latent_size` and `shared_width`.
    """

    # the published `model_type`: the row of interop/lm_reference.py's
    # table, which reads none of the answers below
    model_type = ""
    # what `layer_types` (and `train --layer_types`) may hold
    layer_kinds = ()
    # RMSNorms on what each half of a layer adds, beside the two in front
    post_norms = False
    # what the embedding is multiplied by
    embed_scale = 1.0
    # the deviation the embedding's rows are drawn at
    embed_init_std = property(lambda self: self.init_std)
    # logits = E . norm(x): no `head` parameter
    tie_embedding = False
    # tokens a position predicts at once: the head's vocabularies
    num_pred_heads = 1
    # a norm's parameter is its gain's distance from 1
    norm_add_unit_offset = False
    # the two sums of a layer in fp32
    fp32_skip_add = False
    # what `GatedAttention` does beside the grouped-query attention: a
    # sigmoid gate on its output; an RMSNorm on every query and key head
    attention_gate = False
    qk_norm = True
    # the expert layer: none, every layer dense
    n_routed_experts = 0
    n_shared_experts = 0
    experts_held = (0, 0)
    moe_intermediate_size = 0
    routed_scaling_factor = 1.0
    norm_topk_prob = True
    route_eps = ROUTE_EPS
    first_k_dense_replace = property(lambda self: self.num_hidden_layers)
    # what the router reads: "ffn", the normed tensor its experts read,
    # or "layer", the layer's input ahead of the mixer
    router_reads = "ffn"
    # how the logits become weights: "sigmoid" of all, a bias buffer in
    # the choice, the chosen scores normalised; or "softmax" over the
    # chosen logits, no buffer
    route_score = "sigmoid"
    # an expert's gate function: a key of models/lm/moe.py `ACTS`
    expert_act = "silu"
    # an expert is `W_down(act(W_gate u) * W_up u)`, three matrices; or,
    # False, `W_down act(W_up u)`, two and no `w_gate`; the shared expert
    # alike
    expert_gate = True
    # the width the routed experts read and write: 0, the hidden width;
    # else a latent space, `hidden -> moe_latent_size` ahead of the
    # dispatch and back after the combine (the router and the shared
    # expert stay on the hidden width)
    moe_latent_size = 0
    # the shared expert's columns held here
    shared_width = property(
        lambda self: self.n_shared_experts * self.moe_intermediate_size)
    # a head's width, and the attention kernel's
    head_dim = property(
        lambda self: self.hidden_size // self.num_attention_heads)
    qk_head_dim = property(lambda self: self.head_dim)
    v_head_dim = property(lambda self: self.head_dim)

    def layer_parts(self, i: int) -> Tuple[str, ...]:
        """What layer `i` holds, each part with one norm in front and
        its own sum into the stream: "mixer", "ffn", or both in this
        order."""
        return ("mixer", "ffn")

    def mixer(self, i: int) -> str:
        """Layer `i`'s mixer: a key of models/lm/attention.py `MIXERS`
        (asked of a layer that has one)."""
        return "gqa"

    def layer_window(self, i: int) -> Optional[int]:
        """Layer `i`'s window, None where it sees the whole document."""
        return None

    def layer_rope(self, i: int) -> bool:
        """Whether layer `i`'s `GatedAttention` rotates its queries and
        keys; False: no positional embedding."""
        return True


@dataclasses.dataclass(frozen=True)
class LMConfig(DecoderConfig):
    """A DeepSeek-V3-style decoder (`model_type: deepseek_v3`): latent
    attention without a query LoRA, one or more leading dense layers,
    then sigmoid-routed expert layers with shared experts
    (models/lm/, docs/lm.md). Keys and defaults are
    kanana-2-30b-a3b-instruct-2601's published `config.json`.

    The share: a chip of a tensor- and expert-parallel group holds
    `heads_held` of the `num_attention_heads` heads, `experts_held` of
    the `n_routed_experts` experts (each a `(first, count)` range) and a
    `vocab_size`-row slice of the vocabulary. The router keeps its
    published width and chooses over all experts; what the absent heads
    and experts would have added is left out. `None` holds everything.
    """

    vocab_size: int = 128_256
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    first_k_dense_replace: int = 1
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    n_routed_experts: int = 128
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    routed_scaling_factor: float = 2.448
    norm_topk_prob: bool = True
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    # assumed (the catalog row does not give it): DeepSeek-V3's
    # `initializer_range`
    init_std: float = 0.02
    # rows of the packed dataset and of every batch
    seq_len: int = 8192
    heads_held: Optional[Tuple[int, int]] = None
    experts_held: Optional[Tuple[int, int]] = None
    # bf16 module compute from the fp32 masters; the train step's bf16
    # policy forces it, as it forces RAFT's
    mixed_precision: bool = False
    # full recomputation of every decoder layer in the backward
    remat: bool = False
    # query rows of one attention block (ops/lm_attention.py) and rows
    # of one dispatch chunk of the expert layer (None: the layer sizes it
    # from the slots its held experts expect, models/lm/moe.py
    # `dispatch_chunk`); the results do not depend on either
    attn_block: int = 1024
    moe_chunk: Optional[int] = None

    def __post_init__(self):
        _hold(self, "heads_held", self.num_attention_heads)
        _hold(self, "experts_held", self.n_routed_experts)
        _whole_attention_blocks(self)

    model_type = "deepseek_v3"

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def mixer(self, i: int) -> str:
        return "mla"


def kanana2(**kw) -> LMConfig:
    """kanana-2-30b-a3b-instruct-2601 as published; `heads_held`,
    `experts_held`, `vocab_size` and `num_hidden_layers` cut it to a
    chip's share (benchmarks/configs/kanana-2-30b-a3b-share8.json)."""
    return LMConfig(**kw)


def kanana2_toy(**kw) -> LMConfig:
    """The CPU tests' size: every mechanism, toy widths (8 shares hold 2
    experts and 1 head each)."""
    base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=3,
                intermediate_size=96, moe_intermediate_size=32,
                n_routed_experts=16, num_experts_per_tok=2,
                num_attention_heads=8, kv_lora_rank=16, qk_nope_head_dim=8,
                qk_rope_head_dim=4, v_head_dim=8, seq_len=128,
                attn_block=32, moe_chunk=64)
    return LMConfig(**{**base, **kw})


@dataclasses.dataclass(frozen=True)
class AfmoeConfig(DecoderConfig):
    """An AFMoE decoder (`model_type: afmoe`): gated grouped-query
    attention with QK-norm, sliding-window layers (rotary embedding) and
    full layers (none) mixed by `layer_types`, four RMSNorms a layer,
    leading dense layers, then sigmoid-routed expert layers with a shared
    expert (models/lm/, docs/lm.md). Keys and defaults are Trinity-Mini's
    published `config.json`.

    The share is `LMConfig`'s, and the key/value heads held beside it: a
    key/value head serves `num_attention_heads // num_key_value_heads`
    query heads, so the chips that hold its query heads each hold a copy
    of it. `kv_heads_held` defaults to the heads that `heads_held` reads.
    """

    vocab_size: int = 200_192
    hidden_size: int = 2048
    num_hidden_layers: int = 32
    num_dense_layers: int = 2
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_experts: int = 128
    num_shared_experts: int = 1
    num_experts_per_tok: int = 8
    route_scale: float = 2.826
    route_norm: bool = True
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 2048
    # one of `layer_kinds` a layer; None: the published pattern, three
    # sliding layers and a full one (`global_attn_every_n_layers: 4`)
    layer_types: Optional[Tuple[str, ...]] = None
    rope_theta: float = 1e4
    rms_norm_eps: float = 1e-5
    mup_enabled: bool = True
    # assumed (the catalog row does not give it): afmoe's default
    # `initializer_range`
    init_std: float = 0.02
    seq_len: int = 32_768
    heads_held: Optional[Tuple[int, int]] = None
    kv_heads_held: Optional[Tuple[int, int]] = None
    experts_held: Optional[Tuple[int, int]] = None
    mixed_precision: bool = False
    remat: bool = False
    attn_block: int = 1024
    moe_chunk: Optional[int] = None

    def __post_init__(self):
        _hold_grouped_heads(self)
        _hold(self, "experts_held", self.num_experts)
        _hold_layer_types(self, tuple(
            self.layer_kinds[(i + 1) % 4 == 0]
            for i in range(self.num_hidden_layers)))
        _whole_attention_blocks(self)

    model_type = "afmoe"
    layer_kinds = ("sliding_attention", "full_attention")
    post_norms = True
    attention_gate = True
    embed_scale = property(lambda self: self.hidden_size ** 0.5
                           if self.mup_enabled else 1.0)
    # this architecture's published names for the expert layer's keys
    n_routed_experts = property(lambda self: self.num_experts)
    n_shared_experts = property(lambda self: self.num_shared_experts)
    routed_scaling_factor = property(lambda self: self.route_scale)
    norm_topk_prob = property(lambda self: self.route_norm)
    first_k_dense_replace = property(lambda self: self.num_dense_layers)

    def layer_window(self, i: int) -> Optional[int]:
        return (self.sliding_window
                if self.layer_types[i] == "sliding_attention" else None)

    def layer_rope(self, i: int) -> bool:
        return self.layer_types[i] == "sliding_attention"


@dataclasses.dataclass(frozen=True)
class EvaByteConfig(DecoderConfig):
    """An EvaByte decoder (`model_type: evabyte`): a dense byte-level
    model whose mixer is EVA chunked linear attention (one softmax over
    the exact keys of the query's own `window_size` window and the
    `chunk_size` summaries of every chunk before it), with
    `num_pred_heads` heads that predict the next bytes at once, norms
    with a unit offset and every layer a SwiGLU (models/lm/, docs/lm.md).
    Keys and defaults are EvaByte's published `config.json`.

    The share is the heads held (`heads_held`, of keys and values too:
    `num_key_value_heads` equals `num_attention_heads`); the SwiGLU, the
    norms, the embedding and the 320-row vocabulary are whole on every
    chip. The model has no experts: none held of none, every layer dense.
    """

    vocab_size: int = 320
    hidden_size: int = 4096
    num_hidden_layers: int = 32
    intermediate_size: int = 11_008
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    window_size: int = 2048
    chunk_size: int = 16
    num_pred_heads: int = 8
    rope_theta: float = 1e5
    rms_norm_eps: float = 1e-5
    norm_add_unit_offset: bool = True
    fp32_skip_add: bool = True
    init_std: float = 0.01275
    seq_len: int = 32_768
    heads_held: Optional[Tuple[int, int]] = None
    mixed_precision: bool = False
    remat: bool = False
    attn_block: int = 1024

    def __post_init__(self):
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError(
                f"{self.num_key_value_heads} key/value heads for "
                f"{self.num_attention_heads} query heads: EVA's summaries "
                "are a head's own")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError(f"hidden_size {self.hidden_size} does not "
                             f"divide over {self.num_attention_heads} heads")
        if self.chunk_size < 1 or self.window_size % self.chunk_size:
            raise ValueError(f"a window of {self.window_size} positions is "
                             f"not whole chunks of {self.chunk_size}")
        if self.num_pred_heads < 1:
            raise ValueError("num_pred_heads counts from 1")
        _hold(self, "heads_held", self.num_attention_heads)
        _whole_attention_blocks(self)

    model_type = "evabyte"

    def mixer(self, i: int) -> str:
        return "eva"


# `layer_types` of LFM2-8B-A1B as published: 18 convolution layers and 6
# attention layers
_LFM2_LAYER_TYPES = tuple(
    ("conv", "full_attention")[i in (2, 6, 10, 14, 18, 21)]
    for i in range(24))


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig(DecoderConfig):
    """An LFM2 mixture-of-experts decoder (`model_type: lfm2_moe`): a
    layer's mixer is a doubly gated short causal convolution (`conv`,
    ops/lm_conv.py) or grouped-query attention with QK-norm and a rotary
    embedding (`full_attention`), picked by `layer_types`; two pre-norms
    a layer; leading dense layers, then sigmoid-routed expert layers
    without a shared expert; the head is the embedding (models/lm/,
    docs/lm.md). Keys and defaults are LFM2-8B-A1B's published
    `config.json`.

    The share is `AfmoeConfig`'s (query heads, the key/value heads they
    read, routed experts, vocabulary rows). The convolution mixer has no
    heads: its channels are the hidden width, and it is whole on every
    chip.
    """

    vocab_size: int = 65_536
    hidden_size: int = 2048
    num_hidden_layers: int = 24
    num_dense_layers: int = 2
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_experts: int = 32
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3
    # one of `layer_kinds` a layer; None: the published 24
    layer_types: Optional[Tuple[str, ...]] = None
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    # assumed (the catalog row does not give it): `initializer_range`
    init_std: float = 0.02
    seq_len: int = 32_768
    heads_held: Optional[Tuple[int, int]] = None
    kv_heads_held: Optional[Tuple[int, int]] = None
    experts_held: Optional[Tuple[int, int]] = None
    mixed_precision: bool = False
    remat: bool = False
    attn_block: int = 1024
    moe_chunk: Optional[int] = None

    def __post_init__(self):
        if self.hidden_size % self.num_attention_heads:
            raise ValueError(f"hidden_size {self.hidden_size} does not "
                             f"divide over {self.num_attention_heads} heads")
        if self.conv_L_cache < 1:
            raise ValueError("conv_L_cache counts the taps from 1")
        _hold_grouped_heads(self)
        _hold(self, "experts_held", self.num_experts)
        # the published pattern has no period: another depth names its own
        _hold_layer_types(self, _LFM2_LAYER_TYPES)
        _whole_attention_blocks(self)

    model_type = "lfm2_moe"
    layer_kinds = ("conv", "full_attention")
    tie_embedding = True
    route_eps = 1e-6
    # this architecture's published names for the shared modules' keys
    n_routed_experts = property(lambda self: self.num_experts)
    first_k_dense_replace = property(lambda self: self.num_dense_layers)
    rms_norm_eps = property(lambda self: self.norm_eps)

    def mixer(self, i: int) -> str:
        return "conv" if self.layer_types[i] == "conv" else "gqa"


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig(DecoderConfig):
    """A SmallThinker decoder (`model_type: smallthinker`): grouped-query
    attention without QK-norm or gate, 7 query heads a key/value head,
    sliding-window layers with a rotary embedding and full layers without
    a positional embedding, published layer by layer
    (`sliding_window_layout`, `rope_layout`); every layer an expert layer
    whose router reads the layer's input ahead of attention, takes the
    top `moe_num_active_primary_experts` logits and a softmax over them,
    and whose experts are ReLU-gated, with no shared expert
    (models/lm/, docs/lm.md). Keys and defaults are
    SmallThinker-21BA3B-Instruct's published `config.json`.

    The share is `AfmoeConfig`'s, in whole groups: a chip holds all 7
    query heads of each key/value head it holds, so nothing is copied.
    """

    vocab_size: int = 151_936
    hidden_size: int = 2560
    num_hidden_layers: int = 52
    moe_ffn_hidden_size: int = 768
    moe_num_primary_experts: int = 64
    moe_num_active_primary_experts: int = 6
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window_size: int = 4096
    # 0 or 1 a held layer; None: the published period, a full layer
    # without a rotary embedding and three sliding layers with one
    sliding_window_layout: Optional[Tuple[int, ...]] = None
    rope_layout: Optional[Tuple[int, ...]] = None
    rope_theta: float = 1.5e6
    rms_norm_eps: float = 1e-6
    # assumed (the catalog row has no key for them; docs/lm.md): the
    # router ahead of attention on the layer's input (`described_as`),
    # the experts' ReLU gate ("sparse ReGLU"). The reference alone reads
    # these two and `moe_primary_router_apply_softmax`, so that a control
    # of the check can hand it another mechanism; the program builds the
    # published one whatever they say (`router_reads`, `route_score`,
    # `expert_act` below)
    router_before_attention: bool = True
    hidden_act: str = "relu"
    # assumed: `initializer_range`
    init_std: float = 0.02
    seq_len: int = 16_384
    heads_held: Optional[Tuple[int, int]] = None
    kv_heads_held: Optional[Tuple[int, int]] = None
    experts_held: Optional[Tuple[int, int]] = None
    mixed_precision: bool = False
    remat: bool = False
    attn_block: int = 1024
    moe_chunk: Optional[int] = None

    def __post_init__(self):
        for name in ("sliding_window_layout", "rope_layout"):
            given = getattr(self, name)
            layout = tuple(int(i % 4 != 0)
                           for i in range(self.num_hidden_layers)
                           ) if given is None else tuple(given)
            if (len(layout) != self.num_hidden_layers
                    or any(v not in (0, 1) for v in layout)):
                raise ValueError(f"{name}={layout!r}: 0 or 1 for each of "
                                 f"{self.num_hidden_layers} layers")
            object.__setattr__(self, name, layout)
        _hold_grouped_heads(self)
        group = self.num_attention_heads // self.num_key_value_heads
        if self.heads_held[0] % group or self.heads_held[1] % group:
            raise ValueError(
                f"heads_held={self.heads_held} splits a group of {group} "
                "query heads: a chip holds a key/value head's query heads "
                "whole")
        _hold(self, "experts_held", self.moe_num_primary_experts)
        _whole_attention_blocks(self)

    model_type = "smallthinker"
    qk_norm = False
    first_k_dense_replace = 0
    router_reads = "layer"
    route_score = "softmax"
    expert_act = "relu"
    # the stand-in for trained weights: a token's own row outweighs what
    # a layer adds to the stream. At `init_std` attention's output, a
    # running mean over the document, outweighs it 30 times, the router
    # (which reads the stream un-normed) sends a document's tokens to the
    # same experts and a chip's load is a lottery of the seed (PERF.md,
    # PR 45: load max / mean 5.6-6.4 against 1.2)
    embed_init_std = 1.0
    # this architecture's published names for the expert layer's keys
    n_routed_experts = property(lambda self: self.moe_num_primary_experts)
    num_experts_per_tok = property(
        lambda self: self.moe_num_active_primary_experts)
    moe_intermediate_size = property(lambda self: self.moe_ffn_hidden_size)

    def layer_window(self, i: int) -> Optional[int]:
        return (self.sliding_window_size if self.sliding_window_layout[i]
                else None)

    def layer_rope(self, i: int) -> bool:
        return bool(self.rope_layout[i])


# `hybrid_override_pattern` of NVIDIA-Nemotron-3-Super-120B-A12B as
# published: 40 Mamba-2 layers (M), 40 expert layers (E), 8 attention
# layers (*)
_NEMOTRON_PATTERN = (
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


@dataclasses.dataclass(frozen=True)
class NemotronHConfig(DecoderConfig):
    """A Nemotron-H hybrid decoder (`model_type: nemotron_h`): every
    layer is ONE block behind one norm, `x + Block(N(x))`, by the
    letters of `hybrid_override_pattern`: `M` a Mamba-2 mixer (a
    selective state-space scan in chunks of `chunk_size`, the state
    carried from chunk to chunk and reset at a document's first token,
    behind a causal depthwise convolution of `conv_kernel` taps with a
    bias and a SiLU), `*` grouped-query attention without positional
    embedding, QK-norm or gate, `E` sigmoid-routed experts of two
    matrices with a squared ReLU in a latent space of `moe_latent_size`,
    beside a shared expert on the hidden width (models/lm/, docs/lm.md).
    Keys and defaults are NVIDIA-Nemotron-3-Super-120B-A12B's published
    `config.json`.

    The share: `ssm_heads_held` of the `mamba_num_heads` heads in whole
    B/C groups (`ssm_groups_held`, derived: head h reads group
    `h // (mamba_num_heads // n_groups)`, and the gated norm's
    statistics are a group's own), `heads_held` query heads with the
    `kv_heads_held` they read, `experts_held`, `shared_columns_held` of
    the shared expert's `moe_shared_expert_intermediate_size` columns,
    and a `vocab_size`-row slice. The router, the latent projections
    and the norms are whole. `None` holds everything.
    """

    vocab_size: int = 131_072
    hidden_size: int = 4096
    num_hidden_layers: int = 88
    # one letter of `layer_kinds` a held layer; None: the published 88
    hybrid_override_pattern: Optional[str] = None
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    rope_theta: float = 1e4
    n_routed_experts: int = 512
    n_shared_experts: int = 1
    num_experts_per_tok: int = 22
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    routed_scaling_factor: float = 5.0
    norm_topk_prob: bool = True
    layer_norm_epsilon: float = 1e-5
    # assumed (docs/lm.md, A1-A6, and the cell's controls): the plain
    # reference alone reads these, so that a control of the check can
    # hand it another mechanism; the program builds the published one
    # whatever they say
    state_carry: bool = True        # False: the state zeroed at every chunk
    document_reset: bool = True     # A1. False: state and taps cross documents
    gate_before_norm: bool = True   # A2. False: norm(y) * silu(z)
    attention_rope: bool = False    # A3. True: a rotary embedding
    router_reads_latent: bool = False   # A4. True: the router's rows of z
    mlp_hidden_act: str = "relu2"   # "relu": not its square
    gated_experts: bool = False     # A6. True: silu(W_up z) * W_up z
    d_skip: bool = True             # False: no D * x
    without_layer: Optional[int] = None  # this held layer adds nothing
    # assumed: `initializer_range`
    init_std: float = 0.02
    seq_len: int = 32_768
    ssm_heads_held: Optional[Tuple[int, int]] = None
    ssm_groups_held: Optional[Tuple[int, int]] = None
    heads_held: Optional[Tuple[int, int]] = None
    kv_heads_held: Optional[Tuple[int, int]] = None
    experts_held: Optional[Tuple[int, int]] = None
    shared_columns_held: Optional[Tuple[int, int]] = None
    mixed_precision: bool = False
    remat: bool = False
    attn_block: int = 1024
    moe_chunk: Optional[int] = None

    def __post_init__(self):
        pattern = self.hybrid_override_pattern
        if pattern is None and self.num_hidden_layers == len(
                _NEMOTRON_PATTERN):
            pattern = _NEMOTRON_PATTERN
        if (pattern is None or len(pattern) != self.num_hidden_layers
                or set(pattern) - set(self.layer_kinds)):
            raise ValueError(
                f"hybrid_override_pattern={pattern!r}: one letter of "
                f"{self.layer_kinds} for each of {self.num_hidden_layers} "
                "layers (only the published depth has a default)")
        object.__setattr__(self, "hybrid_override_pattern", pattern)
        heads, groups = self.mamba_num_heads, self.n_groups
        if heads % groups or self.chunk_size < 1 or self.conv_kernel < 1:
            raise ValueError(
                f"{heads} Mamba heads over {groups} groups, chunks of "
                f"{self.chunk_size}, {self.conv_kernel} taps")
        if self.seq_len % self.chunk_size:
            raise ValueError(f"seq_len {self.seq_len} is not whole chunks "
                             f"of {self.chunk_size}")
        _hold(self, "ssm_heads_held", heads)
        per = heads // groups
        first, count = self.ssm_heads_held
        if first % per or count % per:
            raise ValueError(
                f"ssm_heads_held={self.ssm_heads_held} splits a group of "
                f"{per} heads: a chip holds a B/C group's heads whole (the "
                "gated norm's statistics are the group's)")
        if self.ssm_groups_held is None:
            object.__setattr__(self, "ssm_groups_held",
                               (first // per, count // per))
        if tuple(self.ssm_groups_held) != (first // per, count // per):
            raise ValueError(
                f"ssm_groups_held={self.ssm_groups_held} are not the groups "
                f"of ssm_heads_held={self.ssm_heads_held}")
        _hold(self, "ssm_groups_held", groups)
        _hold_grouped_heads(self)
        _hold(self, "experts_held", self.n_routed_experts)
        _hold(self, "shared_columns_held",
              self.moe_shared_expert_intermediate_size)
        _whole_attention_blocks(self)

    model_type = "nemotron_h"
    layer_kinds = ("M", "*", "E")
    qk_norm = False
    first_k_dense_replace = 0
    expert_act = "relu2"
    expert_gate = False
    # the stand-in for a trained stream, as SmallThinker's: a token's own
    # row outweighs what the layers add to it. A squared ReLU has a mean,
    # so every expert layer adds the same vector to every token (the
    # shared expert's alone has a deviation of 0.4 an entry), and the
    # router of the next one, which reads the normed stream, then prefers
    # the same experts for every token: with the rows at deviation 1 the
    # fullest held expert of the deepest layer took 4.1 times the mean
    # and the overflow chunks ran (PERF.md, PR 48, call 1); at 16 the
    # deviation of the 512 loads over their mean is 0.10-0.14 by layer
    # where 0.09 is the draw's own noise (CPU, real widths, 4,096
    # positions: 0.56-0.94 at 1, 0.23-0.43 at 4, 0.14-0.23 at 8). What
    # 16 stands for, measured there too: the eleven layers are published
    # layers 27-37, which meet a stream 27 blocks built, so a block adds
    # between 1/27 and 1/sqrt(27) of it, 4-19 %; here a block adds 4-6.5
    # % of the stream it meets at 16 (8-13 % at 8, 14-26 % at 4, 24-88 %
    # at 1) and the vector all tokens share is 3-6 % of a token's own
    # part at an expert layer's input (27-39 % at 1). A trained router
    # is balanced by its bias buffer, whose update rule the config does
    # not give
    embed_init_std = 16.0
    rms_norm_eps = property(lambda self: self.layer_norm_epsilon)
    shared_width = property(lambda self: self.shared_columns_held[1])

    def layer_parts(self, i: int) -> Tuple[str, ...]:
        return ("ffn",) if self.hybrid_override_pattern[i] == "E" else (
            "mixer",)

    def mixer(self, i: int) -> str:
        return "mamba2" if self.hybrid_override_pattern[i] == "M" else "gqa"

    def layer_rope(self, i: int) -> bool:
        return False


def _hold(cfg, name: str, whole: int) -> None:
    """A `(first, count)` share of `whole`, or None for all of it."""
    held = getattr(cfg, name)
    if held is None:
        object.__setattr__(cfg, name, (0, whole))
        return
    first, count = (int(v) for v in held)
    if first < 0 or count < 1 or first + count > whole:
        raise ValueError(f"{name}={held!r} is not a range of "
                         f"the {whole} the model has")
    object.__setattr__(cfg, name, (first, count))


def _hold_layer_types(cfg, published) -> None:
    """`layer_types` as a tuple of one of `cfg.layer_kinds` a held layer;
    None: `published`."""
    kinds = tuple(published if cfg.layer_types is None else cfg.layer_types)
    if (len(kinds) != cfg.num_hidden_layers
            or any(k not in cfg.layer_kinds for k in kinds)):
        raise ValueError(f"layer_types={kinds!r}: one of {cfg.layer_kinds} "
                         f"for each of {cfg.num_hidden_layers} layers")
    object.__setattr__(cfg, "layer_types", kinds)


def _hold_grouped_heads(cfg) -> None:
    """`heads_held` of `num_attention_heads` query heads and the
    `kv_heads_held` of `num_key_value_heads` they read (default: exactly
    those)."""
    heads, kv = cfg.num_attention_heads, cfg.num_key_value_heads
    if heads % kv:
        raise ValueError(f"{heads} query heads do not divide over "
                         f"{kv} key/value heads")
    group = heads // kv
    _hold(cfg, "heads_held", heads)
    first, count = cfg.heads_held
    if cfg.kv_heads_held is None:
        lo, hi = first // group, (first + count - 1) // group
        object.__setattr__(cfg, "kv_heads_held", (lo, hi - lo + 1))
    _hold(cfg, "kv_heads_held", kv)
    kv_first, kv_count = cfg.kv_heads_held
    # the op's rule, in local numbers: held query head i reads held
    # key/value head i // (count // kv_count). True of query heads
    # inside one key/value head, and of whole groups from a group's
    # first head
    per = max(count // kv_count, 1)
    if count % kv_count or any(
            (first + i) // group - kv_first != i // per
            for i in range(count)):
        raise ValueError(
            f"heads_held={cfg.heads_held} with kv_heads_held="
            f"{cfg.kv_heads_held}: the query heads a chip holds "
            f"divide evenly, in order, over the key/value heads it "
            f"holds ({group} query heads read one key/value head)")


def _whole_attention_blocks(cfg) -> None:
    if cfg.seq_len % cfg.attn_block and cfg.seq_len > cfg.attn_block:
        raise ValueError(f"seq_len {cfg.seq_len} is not a multiple of "
                         f"attn_block {cfg.attn_block}")


def trinity_mini(**kw) -> AfmoeConfig:
    """Trinity-Mini as published; `heads_held`, `kv_heads_held`,
    `experts_held`, `vocab_size`, `num_hidden_layers`, `num_dense_layers`
    and `layer_types` cut it to a chip's share
    (benchmarks/configs/trinity-mini-share8.json)."""
    return AfmoeConfig(**kw)


def trinity_mini_toy(**kw) -> AfmoeConfig:
    """The CPU tests' size: every mechanism, toy widths. 8 query heads
    over 4 key/value heads, so 8 shares hold 1 query head and 2 experts
    each and a pair of shares holds copies of one key/value head; a dense
    layer, then one period of expert layers; a window shorter than the
    row."""
    base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=5,
                num_dense_layers=1, intermediate_size=96,
                moe_intermediate_size=32, num_experts=16,
                num_experts_per_tok=2, num_attention_heads=8,
                num_key_value_heads=4, head_dim=8, sliding_window=24,
                layer_types=("sliding_attention",) * 4 + ("full_attention",),
                seq_len=128, attn_block=32, moe_chunk=64)
    return AfmoeConfig(**{**base, **kw})


def evabyte(**kw) -> EvaByteConfig:
    """EvaByte 6.5B as published; `heads_held` and `num_hidden_layers`
    cut it to a chip's share
    (benchmarks/configs/evabyte-6.5b-share4.json)."""
    return EvaByteConfig(**kw)


def evabyte_toy(**kw) -> EvaByteConfig:
    """The CPU tests' size: every mechanism, toy widths. 8 heads of 8, a
    row of four windows of 32 positions, chunks of 4 (so the tests'
    documents start inside a chunk and inside a window), 3 bytes
    predicted at once."""
    base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=4,
                intermediate_size=96, num_attention_heads=8,
                num_key_value_heads=8, window_size=32, chunk_size=4,
                num_pred_heads=3, seq_len=128, attn_block=32)
    return EvaByteConfig(**{**base, **kw})


def lfm2_8b_a1b(**kw) -> Lfm2MoeConfig:
    """LFM2-8B-A1B as published; `heads_held`, `kv_heads_held`,
    `experts_held`, `vocab_size`, `num_hidden_layers`, `num_dense_layers`
    and `layer_types` cut it to a chip's share
    (benchmarks/configs/lfm2-8b-a1b-share4.json)."""
    return Lfm2MoeConfig(**kw)


def lfm2_8b_a1b_toy(**kw) -> Lfm2MoeConfig:
    """The CPU tests' size: every mechanism, toy widths. 8 query heads
    of 8 over 2 key/value heads (4 query heads read one), 16 experts; a
    dense convolution layer, then one period of expert layers, the
    attention layer first."""
    base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=5,
                num_dense_layers=1, intermediate_size=96,
                moe_intermediate_size=32, num_experts=16,
                num_experts_per_tok=2, num_attention_heads=8,
                num_key_value_heads=2,
                layer_types=("conv", "full_attention") + ("conv",) * 3,
                seq_len=128, attn_block=32, moe_chunk=64)
    return Lfm2MoeConfig(**{**base, **kw})


def smallthinker_21b(**kw) -> SmallThinkerConfig:
    """SmallThinker-21BA3B-Instruct as published; `heads_held`,
    `kv_heads_held`, `experts_held`, `vocab_size`, `num_hidden_layers`
    and the two layouts cut it to a chip's share
    (benchmarks/configs/smallthinker-21b-a3b-share4.json)."""
    return SmallThinkerConfig(**kw)


def smallthinker_21b_toy(**kw) -> SmallThinkerConfig:
    """The CPU tests' size: every mechanism, toy widths. 14 query heads
    of 8 over 2 key/value heads (a group is 7, as published), 8 experts
    top 2, one period f s s s with a window shorter than the row."""
    base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=4,
                moe_ffn_hidden_size=32, moe_num_primary_experts=8,
                moe_num_active_primary_experts=2, num_attention_heads=14,
                num_key_value_heads=2, head_dim=8, sliding_window_size=32,
                seq_len=128, attn_block=32, moe_chunk=64)
    return SmallThinkerConfig(**{**base, **kw})


def nemotron_h(**kw) -> NemotronHConfig:
    """NVIDIA-Nemotron-3-Super-120B-A12B as published; the shares
    (`ssm_heads_held`, `heads_held`, `kv_heads_held`, `experts_held`,
    `shared_columns_held`), `vocab_size`, `num_hidden_layers` and
    `hybrid_override_pattern` cut it to a chip's share
    (benchmarks/configs/nemotron-3-super-120b-a12b-share64.json)."""
    return NemotronHConfig(**kw)


def nemotron_h_toy(**kw) -> NemotronHConfig:
    """The CPU tests' size: every mechanism, toy widths. 8 Mamba heads
    of 8 with a state of 16 over 4 groups (2 heads a group), chunks of
    16 (the tests' documents start inside a chunk); 8 query heads of 8
    on 2 key/value heads; 16 experts top 3 of width 24 in a latent space
    of 32, a shared expert of 48 columns; M E M * E."""
    base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=5,
                hybrid_override_pattern="MEM*E", mamba_num_heads=8,
                mamba_head_dim=8, ssm_state_size=16, n_groups=4,
                chunk_size=16, num_attention_heads=8,
                num_key_value_heads=2, head_dim=8, n_routed_experts=16,
                num_experts_per_tok=3, moe_intermediate_size=24,
                moe_latent_size=32, moe_shared_expert_intermediate_size=48,
                seq_len=128, attn_block=32, moe_chunk=64)
    return NemotronHConfig(**{**base, **kw})


# language models `train --variant` takes beside VARIANTS. Not in
# VARIANTS: eval, serve and video have no path for them (ROADMAP.md).
LM_VARIANTS = {"kanana2": kanana2, "kanana2-toy": kanana2_toy,
               "trinity-mini": trinity_mini,
               "trinity-mini-toy": trinity_mini_toy,
               "evabyte": evabyte, "evabyte-toy": evabyte_toy,
               "lfm2-8b-a1b": lfm2_8b_a1b,
               "lfm2-8b-a1b-toy": lfm2_8b_a1b_toy,
               "smallthinker-21b": smallthinker_21b,
               "smallthinker-21b-toy": smallthinker_21b_toy,
               "nemotron-h": nemotron_h, "nemotron-h-toy": nemotron_h_toy}


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """One training stage. Presets mirror train_standard.sh / train_mixed.sh."""

    name: str = "raft"
    stage: str = "chairs"  # chairs | things | sintel | kitti
    lr: float = 4e-4
    num_steps: int = 100_000
    batch_size: int = 10
    image_size: Tuple[int, int] = (368, 496)
    wdecay: float = 1e-4
    epsilon: float = 1e-8
    clip: float = 1.0
    gamma: float = 0.8
    iters: int = 12
    add_noise: bool = False
    # training precision policy: "fp32", or "bf16" — the step forces the
    # model's mixed-precision path (bf16 module compute; flax casts each
    # op's params from the fp32 MASTER weights, so gradients land fp32)
    # while loss, metrics, BN running stats, and optimizer math stay
    # fp32. The model's own mixed-precision contract keeps the corr
    # volume fp32. No loss scaling needed: bf16 keeps fp32's exponent
    # range
    precision: str = "fp32"
    # gradient accumulation: the step's batch leading dim is
    # (accum_steps * microbatch) and a lax.scan inside the ONE jitted
    # step runs the microbatches sequentially, averaging gradients —
    # large effective batches on one chip, compiled once. 1 = off
    accum_steps: int = 1
    # device-side prefetch depth: batches device_put ahead of the step
    # consuming them (data.prefetch.DevicePrefetcher); 2 = classic
    # double buffering. 0 disables the prefetcher entirely
    prefetch_depth: int = 2
    # v1-lineage fusion (alt/train_1.py:173-176): run the SAME model on
    # (image1, image2) and on the edge-image pair, and sum the per-iter
    # flow predictions before the sequence loss; requires edge-pair data
    edge_sum_fusion: bool = False
    # rematerialization policy axis for the TRAIN step (the bench's
    # --remat knob): "none" stores every refinement iteration's
    # activations; "per_iter" checkpoints each scanned iteration and
    # recomputes everything in the backward (cfg.remat with
    # remat_policy="full"); "dots_saveable" checkpoints each iteration
    # but SAVES matmul/conv outputs (jax.checkpoint_policies
    # .dots_saveable) — most of per_iter's HBM win at a fraction of its
    # recompute FLOPs. Numerically identical on all three settings
    remat: str = "none"
    freeze_bn: bool = False  # true for all post-chairs stages (train.py:149-150)
    val_freq: int = 5000
    sum_freq: int = 100
    seed: int = 1234
    validation: Tuple[str, ...] = ()


# The 4-stage curriculum, standard recipe (train_standard.sh:3-6).
STANDARD_STAGES = (
    TrainConfig(name="raft-chairs", stage="chairs", validation=("chairs",), num_steps=100_000,
                batch_size=10, lr=4e-4, image_size=(368, 496), wdecay=1e-4),
    TrainConfig(name="raft-things", stage="things", validation=("sintel",), num_steps=100_000,
                batch_size=6, lr=1.25e-4, image_size=(400, 720), wdecay=1e-4, freeze_bn=True),
    TrainConfig(name="raft-sintel", stage="sintel", validation=("sintel",), num_steps=100_000,
                batch_size=6, lr=1.25e-4, image_size=(368, 768), wdecay=1e-5, gamma=0.85,
                freeze_bn=True),
    TrainConfig(name="raft-kitti", stage="kitti", validation=("kitti",), num_steps=50_000,
                batch_size=6, lr=1e-4, image_size=(288, 960), wdecay=1e-5, gamma=0.85,
                freeze_bn=True),
)

# Mixed-precision single-chip recipe (train_mixed.sh:3-6).
MIXED_STAGES = (
    TrainConfig(name="raft-chairs", stage="chairs", validation=("chairs",), num_steps=120_000,
                batch_size=8, lr=2.5e-4, image_size=(368, 496), wdecay=1e-4),
    TrainConfig(name="raft-things", stage="things", validation=("sintel",), num_steps=120_000,
                batch_size=5, lr=1e-4, image_size=(400, 720), wdecay=1e-4, freeze_bn=True),
    TrainConfig(name="raft-sintel", stage="sintel", validation=("sintel",), num_steps=120_000,
                batch_size=5, lr=1e-4, image_size=(368, 768), wdecay=1e-5, gamma=0.85,
                freeze_bn=True),
    TrainConfig(name="raft-kitti", stage="kitti", validation=("kitti",), num_steps=50_000,
                batch_size=5, lr=1e-4, image_size=(288, 960), wdecay=1e-5, gamma=0.85,
                freeze_bn=True),
)
