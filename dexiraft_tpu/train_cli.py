"""Training CLI — one resolved config tree instead of argparse x3 + .sh files.

Reference surface: train.py:220-250 (flags, seeds, checkpoint dir) and the
curriculum scripts train_standard.sh / train_mixed.sh. One invocation runs
one stage; presets supply the per-stage hyperparameters:

  python -m dexiraft_tpu train --stage chairs --name raft-chairs \
      --variant v1 --validation chairs
  python -m dexiraft_tpu train --preset standard --stage things \
      --restore_ckpt checkpoints/raft-chairs
  python -m dexiraft_tpu train --variant kanana2 --tokens docs.npz \
      --layers 6 --heads_held 0 4 --experts_held 0 16 --vocab_size 16032 \
      --batch_size 4 --precision bf16 --remat
          (a language model; docs/lm.md has a line for each of them)

The loop is the reference's (train.py:163-215) re-shaped for TPU: one
jitted sharded step (forward + loss + backward + optimizer), batches
sharded over the data mesh axis, VAL_FREQ checkpoint+validate, final save.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from typing import Any, Dict, Optional

import jax
import numpy as np

from dexiraft_tpu import config as cfglib
from dexiraft_tpu.config import (
    CORR_IMPLS,
    LM_VARIANTS,
    VARIANTS,
    DecoderConfig,
    RAFTConfig,
    TrainConfig,
)

# reference in-training validation iteration counts (evaluate.py:81-210)
_VAL_ITERS = {"chairs": 24, "sintel": 32, "kitti": 24, "hd1k": 24}


def fsdp_arg(value: str):
    """argparse type= for --fsdp: 'auto' or a positive integer, refused
    at parse time with usage text (not a raw int() traceback after the
    dataset/import setup has already run). Shared by train_bench."""
    if value == "auto":
        return value
    try:
        n = int(value)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"expected 'auto' or an integer, got {value!r}")
    if n < 1:
        raise argparse.ArgumentTypeError(f"expected >= 1, got {n}")
    return n


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser("dexiraft-train")
    p.add_argument("--name", default=None,
                   help="experiment name (default: preset's per-stage name, "
                        "else 'raft')")
    p.add_argument("--stage", default=None,
                   choices=["chairs", "things", "sintel", "kitti"],
                   help="curriculum stage (required for the RAFT variants)")
    p.add_argument("--preset", choices=["standard", "mixed", "none"],
                   default="none", help="stage hyperparameter preset")
    p.add_argument("--variant", default="v1",
                   choices=sorted(VARIANTS) + sorted(LM_VARIANTS),
                   help=f"v1..v5: RAFT; {', '.join(sorted(LM_VARIANTS))}: "
                        "the language models of models/lm (docs/lm.md), "
                        "trained on --tokens")
    # the language model's own flags (refused for the RAFT variants)
    p.add_argument("--tokens", default=None,
                   help="language models: token file (.npz of `tokens` and "
                        "`lengths`, data/tokens.py), packed first-fit "
                        "into rows of --seq_len")
    p.add_argument("--seq_len", type=int, default=None,
                   help="language models: positions a row (default: "
                        "the configuration's)")
    p.add_argument("--layers", type=int, default=None,
                   help="language models: decoder layers held (default: "
                        "all)")
    p.add_argument("--dense_layers", type=int, default=None,
                   help="language models: of them, the leading dense "
                        "layers (default: as published)")
    p.add_argument("--layer_types", nargs="+", default=None,
                   choices=list(dict.fromkeys(
                       kind for make in LM_VARIANTS.values()
                       for kind in make().layer_kinds)),
                   help="language models whose layers differ: each held "
                        "layer's kind, of those the configuration takes "
                        "(default: the published pattern)")
    p.add_argument("--vocab_size", type=int, default=None,
                   help="language models: rows of the vocabulary held")
    p.add_argument("--heads_held", type=int, nargs=2, default=None,
                   metavar=("FIRST", "COUNT"),
                   help="language models: the attention (query) heads this "
                        "chip holds of a tensor-parallel group (default: "
                        "all)")
    p.add_argument("--kv_heads_held", type=int, nargs=2, default=None,
                   metavar=("FIRST", "COUNT"),
                   help="language models with grouped key/value heads: "
                        "those this chip holds "
                        "(default: those its query heads read)")
    p.add_argument("--experts_held", type=int, nargs=2, default=None,
                   metavar=("FIRST", "COUNT"),
                   help="language models with an expert layer: the routed "
                        "experts this chip holds of an expert-parallel "
                        "group (default: all)")
    p.add_argument("--ssm_heads_held", type=int, nargs=2, default=None,
                   metavar=("FIRST", "COUNT"),
                   help="language models with a state-space mixer: the "
                        "Mamba-2 heads this chip holds, in whole B/C groups "
                        "(default: all)")
    p.add_argument("--shared_columns_held", type=int, nargs=2, default=None,
                   metavar=("FIRST", "COUNT"),
                   help="language models whose shared expert is divided: "
                        "the columns of it this chip holds (default: all)")
    p.add_argument("--small", action="store_true")
    p.add_argument("--mixed_precision", action="store_true")
    p.add_argument("--corr_impl", default="allpairs",
                   choices=CORR_IMPLS)
    p.add_argument("--corr_dtype", default="fp32", choices=["fp32", "bf16"],
                   help="storage precision of the correlation pyramid "
                        "(halves HBM traffic of the refinement loop at "
                        "bf16; int8 is inference-only — eval/serve)")
    p.add_argument("--fused_update", action="store_true",
                   help="fuse each iteration's 4-level lookup with the "
                        "motion encoder's corr conv into one Pallas "
                        "kernel (requires --corr_impl flash; "
                        "identical param tree, checkpoints interchange)")
    p.add_argument("--remat", action="store_true",
                   help="rematerialize refinement iterations in backward "
                        "(HBM savings at ~1 extra forward of FLOPs)")
    p.add_argument("--remat_lookup", action="store_true",
                   help="rematerialize only the correlation lookup — "
                        "drops the per-iteration hat matrices (the "
                        "dominant training-memory term) far cheaper than "
                        "full --remat")
    p.add_argument("--dexined_upconv", default="subpixel",
                   choices=["transpose", "subpixel"],
                   help="embedded-DexiNed upsampler implementation "
                        "(numerically identical; see docs/perf.md)")
    p.add_argument("--dropout", type=float, default=0.0)
    p.add_argument("--lr", type=float, default=None)
    p.add_argument("--num_steps", type=int, default=None)
    p.add_argument("--batch_size", type=int, default=None)
    p.add_argument("--image_size", type=int, nargs=2, default=None)
    p.add_argument("--wdecay", type=float, default=None)
    p.add_argument("--gamma", type=float, default=None)
    p.add_argument("--clip", type=float, default=1.0)
    p.add_argument("--iters", type=int, default=12)
    p.add_argument("--add_noise", action="store_true")
    p.add_argument("--precision", choices=["fp32", "bf16"], default="fp32",
                   help="training precision policy: bf16 = bf16 "
                        "compute/activations with fp32 master weights "
                        "and fp32 loss/optimizer math")
    p.add_argument("--accum_steps", type=int, default=1,
                   help="gradient accumulation: batch_size = accum * "
                        "microbatch; the microbatches run as a lax.scan "
                        "inside the ONE jitted step")
    p.add_argument("--fsdp", default=None, type=fsdp_arg,
                   help="shard params + optimizer state over the mesh's "
                        "fsdp axis: 'auto' grows the axis over every "
                        "device left after data-parallelism takes the "
                        "largest batch divisor (host-count-aware), an "
                        "integer forces that many ways; default/1 keeps "
                        "the replicated layout. Storage-only sharding "
                        "(docs/perf.md): per-device state HBM drops "
                        "~fsdp-fold, checkpoints flush per shard, the "
                        "step gathers at entry so the math is the "
                        "replicated step's")
    p.add_argument("--prefetch_depth", type=int, default=2,
                   help="device-side prefetch depth (batches device_put "
                        "ahead with the step's input shardings while the "
                        "current step runs; 0 disables)")
    p.add_argument("--compile_cache", action="store_true",
                   help="persistent XLA compilation cache — repeat "
                        "launches skip the multi-minute compile (placed "
                        "by JAX_COMPILATION_CACHE_DIR, else a fixed path "
                        "in the checkout; profiling.enable_persistent_"
                        "cache)")
    p.add_argument("--validation", nargs="*", default=None,
                   choices=sorted(_VAL_ITERS),
                   help="default: the preset's per-stage validation sets")
    p.add_argument("--records_dir", default=None,
                   help="train from a packed-record directory "
                        "(scripts/pack_records.py) instead of decoding "
                        "raw dataset files: same sample sequence, O(1) "
                        "resume seeks, per-host shard reads "
                        "(docs/data_plane.md); the raw-file loader "
                        "remains the default")
    p.add_argument("--edge_root", default=None,
                   help="parallel tree of precomputed edge-map PNGs for the "
                        "v2/v3 data-edge contract (core/datasets_seperate.py)")
    p.add_argument("--edge_sum_fusion", action="store_true",
                   help="v1-lineage fusion (alt/train_1.py:173-176): run the "
                        "model on the image pair AND the edge pair, sum the "
                        "per-iter flows; needs --edge_root")
    p.add_argument("--restore_ckpt", default=None,
                   help="orbax dir for partial (strict=False-style) restore")
    p.add_argument("--resume", action="store_true",
                   help="restore FULL state (incl. optimizer/schedule) from "
                        "--output/<name> and continue")
    p.add_argument("--output", default="checkpoints")
    p.add_argument("--seed", type=int, default=1234)
    p.add_argument("--val_freq", type=int, default=5000)
    p.add_argument("--sum_freq", type=int, default=100)
    p.add_argument("--num_workers", type=int, default=4)
    p.add_argument("--worker_mode", choices=["thread", "process"],
                   default="thread",
                   help="decode pool kind; 'process' sidesteps the GIL "
                   "on many-core hosts (spawned, not forked: the CLI "
                   "initializes jax before the loader exists)")
    p.add_argument("--log_dir", default="runs")
    p.add_argument("--profile_steps", type=int, nargs=2, default=None,
                   metavar=("START", "STOP"),
                   help="capture a jax.profiler trace for steps "
                        "[START, STOP) into <log_dir>/<name>/profile")
    # failure detection / elastic recovery — absent in the reference
    # (SURVEY.md §5): its v3 run diverged from EPE 8.4 to 347 and kept
    # logging (logs/raft_3_train_chairs_log*.out), and outages killed
    # runs that were restarted by hand. Here a non-finite or exploding
    # loss rolls the full state back to the last checkpoint and training
    # continues on the data stream's current position (the divergent
    # batch window is naturally skipped, not replayed).
    p.add_argument("--no_guard", action="store_true",
                   help="disable the divergence guard")
    p.add_argument("--guard_every", type=int, default=100,
                   help="check the loss every N steps (a host sync; the "
                        "logger already syncs at --sum_freq, so matching "
                        "it costs nothing extra)")
    p.add_argument("--guard_threshold", type=float, default=1e4,
                   help="loss above this (or non-finite) triggers a "
                        "rollback to the last checkpoint")
    p.add_argument("--max_rollbacks", type=int, default=3,
                   help="abort after this many rollbacks (persistent "
                        "divergence needs a human: lower the lr)")
    # fault-tolerant runtime (docs/resilience.md): preemption becomes one
    # guard-checked emergency save; --resume continues the EXACT sample
    # sequence via the stream-position sidecar saved with every
    # checkpoint; restores verify integrity and fall back a step instead
    # of crashing on (or silently loading) a truncated checkpoint
    p.add_argument("--keep", type=int, default=0,
                   help="retention: keep only the newest N checkpoints "
                        "(0 = keep all); the current rollback target is "
                        "never deleted")
    p.add_argument("--keep_best", action="store_true",
                   help="retention also keeps the checkpoint with the "
                        "best validation EPE even once it ages out of "
                        "the --keep window")
    p.add_argument("--on_preempt", choices=["save", "abort"],
                   default="save",
                   help="SIGTERM/SIGINT response: 'save' finishes the "
                        "current step and writes one emergency "
                        "checkpoint + data-stream position (a second "
                        "signal aborts immediately); 'abort' stops "
                        "without saving (the reference behavior)")
    p.add_argument("--chaos", default=None, metavar="SPEC",
                   help="fault injection for tests/scripts/chaos_smoke "
                        "(resilience.chaos.parse_spec), e.g. "
                        "'sigterm@30': real SIGTERM after step 30, "
                        "'kill_mid_flush@30': hard-kill during the next "
                        "async checkpoint flush")
    # pod-grade failure handling (docs/resilience.md "Multi-host"):
    # checkpoint flushes are async (the loop only pays the host
    # snapshot; wait_pending barriers sit before the next save /
    # validation / rollback / GC / exit), failure verdicts are
    # host-collective, and a hang is bounded by a watchdog
    p.add_argument("--stall_timeout", type=float, default=0.0,
                   help="hang watchdog: a step/collective region making "
                        "no progress for this many seconds dumps the "
                        "step index + live stack traces and exits "
                        "nonzero instead of hanging the pod "
                        "(0 = disabled; sanctioned slow windows — "
                        "checkpoint, validation, restore — get 10x "
                        "this bound)")
    p.add_argument("--straggler_factor", type=float, default=10.0,
                   help="warn when a step runs this many times the "
                        "step-time EWMA (same watchdog timer; needs "
                        "--stall_timeout > 0)")
    p.add_argument("--coord_every", type=int, default=10,
                   help="multi-host: poll the coordinated preemption "
                        "flag every N steps (one tiny allgather; "
                        "divergence verdicts coordinate on "
                        "--guard_every; single-process runs never "
                        "issue a collective)")
    p.add_argument("--coord_timeout_s", type=float, default=600.0,
                   help="consensus-op timeout: a peer posting no value "
                        "for this long raises the one-line "
                        "CoordinatorTimeout naming the peer and round "
                        "instead of waiting forever (under --elastic "
                        "this is also how a stuck survivor unblocks "
                        "into reconfiguration — set it to seconds, "
                        "not minutes)")
    # elastic pod membership (docs/resilience.md "Elastic membership"):
    # a lost host becomes a shrink-and-continue reconfiguration inside
    # the SAME process — new membership epoch, smaller mesh, agreed-step
    # restore, re-sliced data stream — instead of an exit-98 pod
    # restart; replacement hosts join at the next checkpoint boundary
    p.add_argument("--elastic", action="store_true",
                   help="survive host loss by reconfiguring the pod "
                        "membership (resilience.membership) instead of "
                        "exiting: needs JAX_COORDINATOR_ADDRESS (+ "
                        "JAX_NUM_PROCESSES/JAX_PROCESS_ID on pods; a "
                        "solo incumbent may omit them), and exits 98 "
                        "only when recovery is impossible (--min_hosts, "
                        "rank-0 loss, reconfiguration timeout)")
    p.add_argument("--min_hosts", type=int, default=1,
                   help="elastic: refuse to shrink below this many "
                        "hosts — a deeper cascade falls back to the "
                        "exit-98 restart contract")
    p.add_argument("--join", default=None, metavar="NAME",
                   help="enter a running --elastic job as a replacement "
                        "host under this name: posts a join intent on "
                        "the membership board and is absorbed at the "
                        "incumbents' next checkpoint boundary "
                        "(implies --elastic and --resume)")
    # runtime guard mode (analysis/guards.py, docs/static_analysis.md):
    # the dynamic half of the jaxlint story. Off, drift still surfaces
    # as a one-line warning on the guard cadence.
    p.add_argument("--strict", action="store_true",
                   help="arm guards.strict_mode after warmup: implicit "
                        "host<->device transfers raise immediately and "
                        "any post-warmup recompile fails the run "
                        "(checkpoint/validation windows are exempt — "
                        "they are sanctioned host I/O)")
    return p


# flags that mean something for one family only; given for the other,
# `train` refuses them by name rather than ignoring them
_RAFT_ONLY = ("stage", "preset", "small", "mixed_precision", "corr_impl",
              "corr_dtype", "fused_update", "remat_lookup", "dexined_upconv",
              "dropout", "image_size", "gamma", "iters", "add_noise",
              "validation", "records_dir", "edge_root", "edge_sum_fusion",
              "fsdp", "elastic", "join")
_LM_ONLY = ("tokens", "seq_len", "layers", "dense_layers", "layer_types",
            "vocab_size", "heads_held", "kv_heads_held", "experts_held",
            "ssm_heads_held", "shared_columns_held")
# of them, the flags one architecture has and another has not, by the
# configuration's field
_LM_FIELDS = {"dense_layers": ("first_k_dense_replace", "num_dense_layers"),
              "layer_types": ("layer_types",),
              "kv_heads_held": ("kv_heads_held",),
              "experts_held": ("experts_held",),
              "ssm_heads_held": ("ssm_heads_held",),
              "shared_columns_held": ("shared_columns_held",)}


def _refuse_given(args, names, why: str) -> None:
    defaults = build_parser()
    given = [n for n in names if getattr(args, n) != defaults.get_default(n)]
    if given:
        raise SystemExit(f"train: {', '.join('--' + n for n in given)} "
                         f"{why}")


def resolve_lm_configs(args) -> "tuple[Any, TrainConfig]":
    _refuse_given(args, _RAFT_ONLY,
                  f"belong(s) to the RAFT variants; --variant "
                  f"{args.variant} is a language model (docs/lm.md: "
                  f"{', '.join('--' + n for n in _LM_ONLY)}, --remat for "
                  "whole layers)")
    if not args.tokens:
        raise SystemExit(f"train: --variant {args.variant} needs --tokens")
    make = LM_VARIANTS[args.variant]
    fields = {f.name for f in dataclasses.fields(make())}
    model = {k: v for k, v in (
        ("seq_len", args.seq_len), ("num_hidden_layers", args.layers),
        ("vocab_size", args.vocab_size), ("heads_held", args.heads_held))
        if v is not None}
    for flag, names in _LM_FIELDS.items():
        value = getattr(args, flag)
        if value is None:
            continue
        name = next((n for n in names if n in fields), None)
        if name is None:
            raise SystemExit(f"train: --{flag} is not a flag of --variant "
                             f"{args.variant}")
        model[name] = tuple(value) if isinstance(value, list) else value
    try:
        cfg = make(remat=args.remat, **model)
    except ValueError as e:
        raise SystemExit(f"train: {e}")
    tc = TrainConfig(
        name=args.name or args.variant, stage="tokens", clip=args.clip,
        precision=args.precision, accum_steps=args.accum_steps,
        prefetch_depth=args.prefetch_depth, val_freq=args.val_freq,
        sum_freq=args.sum_freq, seed=args.seed, validation=(),
        **{k: v for k, v in (("lr", args.lr), ("num_steps", args.num_steps),
                             ("batch_size", args.batch_size),
                             ("wdecay", args.wdecay)) if v is not None})
    return cfg, tc


def resolve_configs(args) -> "tuple[RAFTConfig, TrainConfig]":
    if args.variant in LM_VARIANTS:
        return resolve_lm_configs(args)
    _refuse_given(args, _LM_ONLY, "belong(s) to the language models "
                  f"(--variant {', '.join(sorted(LM_VARIANTS))})")
    if args.stage is None:
        raise SystemExit("train: --stage is required for --variant "
                         f"{args.variant}")
    try:
        cfg = VARIANTS[args.variant](
            small=args.small,
            mixed_precision=args.mixed_precision,
            dropout=args.dropout,
            corr_impl=args.corr_impl,
            corr_dtype=args.corr_dtype,
            fused_update=args.fused_update,
            remat=args.remat,
            remat_lookup=args.remat_lookup,
            dexined_upconv=args.dexined_upconv,
        )
    except ValueError as e:  # a combination RAFTConfig refuses
        raise SystemExit(f"train: {e}") from None

    if args.preset != "none":
        stages = (cfglib.STANDARD_STAGES if args.preset == "standard"
                  else cfglib.MIXED_STAGES)
        base = next(tc for tc in stages if tc.stage == args.stage)
    else:
        base = TrainConfig(stage=args.stage)

    overrides: Dict = dict(
        stage=args.stage,
        clip=args.clip,
        iters=args.iters,
        add_noise=args.add_noise,
        precision=args.precision,
        accum_steps=args.accum_steps,
        prefetch_depth=args.prefetch_depth,
        edge_sum_fusion=args.edge_sum_fusion,
        # freeze BN for every post-chairs stage (train.py:149-150)
        freeze_bn=args.stage != "chairs",
        val_freq=args.val_freq,
        sum_freq=args.sum_freq,
        seed=args.seed,
    )
    # None = "not given": keep the preset's per-stage name/validation
    if args.name is not None:
        overrides["name"] = args.name
    if args.validation is not None:
        overrides["validation"] = tuple(args.validation)
    for field, value in [("lr", args.lr), ("num_steps", args.num_steps),
                         ("batch_size", args.batch_size),
                         ("wdecay", args.wdecay), ("gamma", args.gamma)]:
        if value is not None:
            overrides[field] = value
    if args.image_size is not None:
        overrides["image_size"] = tuple(args.image_size)
    return cfg, dataclasses.replace(base, **overrides)


def _make_validators(cfg: RAFTConfig, names, variables_fn):
    """Jitted eval fns per validation set, built once, reading the CURRENT
    variables through variables_fn at call time."""
    from dexiraft_tpu.eval.validate import VALIDATORS
    from dexiraft_tpu.train.step import make_eval_step

    steps = {n: make_eval_step(cfg, iters=_VAL_ITERS[n]) for n in names}

    def run(name: str) -> Dict[str, float]:
        fn = steps[name]
        variables = variables_fn()
        # explicit H2D put: validators hand numpy frames straight to the
        # jitted step; device_put keeps the transfer visible and strict-
        # transfer-guard-clean (analysis.guards)
        return VALIDATORS[name](
            lambda im1, im2, flow_init=None: fn(
                variables, jax.device_put(im1), jax.device_put(im2),
                flow_init=(None if flow_init is None
                           else jax.device_put(flow_init))))

    return run


class _GrowBoundary(Exception):
    """Internal control flow: a checkpoint boundary collectively agreed
    that join intents are pending. The segment loop (_elastic_main)
    absorbs them and re-enters train() in the grown world."""

    def __init__(self, step: int):
        self.step = step
        super().__init__(f"grow at checkpoint boundary (step {step})")


def train(cfg: RAFTConfig, tc: TrainConfig, args, elastic=None,
          prune_above_restore: bool = False) -> None:
    """One training segment. Non-elastic runs: the whole job. Under
    --elastic: one membership epoch — a ReconfigureNeeded /
    CoordinatorTimeout / _GrowBoundary raise unwinds this function
    (closing loader, watchdog, guards on the way), the segment loop
    reconfigures the world, and re-enters with resume semantics; every
    world-derived object (mesh, loader slice, coordinator namespace,
    jitted step) is rebuilt here against the new world."""
    import os.path as osp

    from dexiraft_tpu.data import native
    from dexiraft_tpu.data.datasets import fetch_dataset
    from dexiraft_tpu.data.loader import Loader
    from dexiraft_tpu.data.prefetch import prefetch_to_device
    from dexiraft_tpu.parallel import layout
    from dexiraft_tpu.parallel.layout import make_train_mesh
    from dexiraft_tpu.profiling import device_banner, enable_persistent_cache
    from dexiraft_tpu.resilience import (
        Coordinator,
        HangWatchdog,
        LoaderKindMismatch,
        PreemptionHandler,
        RetentionPolicy,
        StreamPosition,
        load_position,
        restore_verified,
        save_position,
    )
    from dexiraft_tpu.train import checkpoint as ckpt
    from dexiraft_tpu.train.logger import Logger
    from dexiraft_tpu.train.state import create_state, param_count
    from dexiraft_tpu.train.step import make_train_step

    np.random.seed(tc.seed)
    ckpt_dir = osp.join(args.output, tc.name)

    # mesh policy lives in the canonical layout (parallel/layout.py):
    # data over the largest device count dividing the batch, plus an
    # fsdp axis over the leftover devices when --fsdp asks for one
    # (already 'auto'/int — the fsdp_arg parse-time type)
    mesh = make_train_mesh(tc.batch_size, fsdp=args.fsdp)
    if mesh.size < len(jax.devices()) or len(mesh.shape) > 1:
        print(f"[mesh] {dict(mesh.shape)} over {len(jax.devices())} "
              f"devices (batch {tc.batch_size})")

    if args.compile_cache:
        enable_persistent_cache()
    is_lm = isinstance(cfg, DecoderConfig)
    if is_lm:
        device_banner("train", model=args.variant, mesh=dict(mesh.shape),
                      heads_held=cfg.heads_held,
                      **{f.name: getattr(cfg, f.name)
                         for f in dataclasses.fields(cfg)
                         if f.name in ("kv_heads_held", "experts_held")})
    else:
        device_banner("train", corr_impl=cfg.corr_impl,
                      fused_update=cfg.fused_update, mesh=dict(mesh.shape),
                      decoder=native.status())
    state = create_state(jax.random.PRNGKey(tc.seed), cfg, tc)
    print(f"Parameter Count: {param_count(state.params)}")
    fsdp_live = layout.LAYOUT.has_fsdp(mesh)
    # storage layout from step one — the layout the step pins at its
    # jit boundary: on an fsdp mesh params/opt_state land sharded, so
    # every restore below (resume, rollback, partial) restores per
    # shard into the template's resolved shardings; on every mesh the
    # first call then sees the types the second will (a state that
    # first arrives unplaced carries no mesh in its types, and the step
    # would trace and compile twice)
    state = layout.shard_state(state, mesh)

    # last checkpoint that belongs to THIS trajectory — the only valid
    # rollback target. A stale dir from a previous experiment must never
    # be spliced into a fresh run by the guard.
    last_saved = None
    # which data plane feeds this run; stamped into every stream sidecar
    # (kind + pack fingerprint) so --resume refuses a raw<->records swap
    # AND a records-to-different-pack swap (LoaderKindMismatch)
    loader_kind = "records" if args.records_dir else "raw"
    records_ds = None
    pack_fingerprint = None
    if args.records_dir:
        # packed-record data plane (docs/data_plane.md), opened BEFORE
        # the resume path so its provenance gates both the dataset
        # selection and the stream-sidecar check. Same sample sequence
        # as the raw loader; decode is an O(1) indexed shard read and
        # each host touches only its slice's records.
        if args.edge_root:
            sys.exit("--records_dir cannot be combined with --edge_root: "
                     "edge-paired stages are not packable "
                     "(scripts/pack_records.py) — use the raw loader")
        from dexiraft_tpu.data.datasets import DEFAULT_TRAIN_DS
        from dexiraft_tpu.data.records import open_records

        records_ds = open_records(args.records_dir)
        man = records_ds.manifest
        if man.stage is not None and man.stage != tc.stage:
            sys.exit(f"--records_dir {args.records_dir} was packed from "
                     f"stage {man.stage!r} but this run trains stage "
                     f"{tc.stage!r} — pack the right stage or drop "
                     f"--records_dir")
        # the raw path always trains sintel with the default mixture
        # selector; a pack of a reduced mixture is a DIFFERENT epoch
        if (tc.stage == "sintel" and man.train_ds is not None
                and man.train_ds != DEFAULT_TRAIN_DS):
            sys.exit(f"--records_dir {args.records_dir} was packed with "
                     f"train_ds={man.train_ds!r} but the sintel stage "
                     f"trains the {DEFAULT_TRAIN_DS!r} mixture — repack "
                     f"with the default selector or use the raw loader")
        if (man.image_size is not None
                and tuple(man.image_size) != tuple(tc.image_size)):
            print(f"[records] WARNING: pack was made at image_size "
                  f"{tuple(man.image_size)}, run requests "
                  f"{tuple(tc.image_size)}; the pack-time crop recipe "
                  f"wins (repack to change it)")
        pack_fingerprint = man.fingerprint
    # position of the NEXT global batch to consume (resilience.stream):
    # checkpointed as a sidecar with every save, so --resume continues
    # the exact sample sequence instead of replaying from epoch 0
    stream_pos = StreamPosition()
    # host-consensus primitives (resilience.coord): identity on a single
    # process, one tiny allgather per decision on a multi-host mesh —
    # every failure verdict below (divergence, preemption, resume step)
    # is the SAME on every host, so no host ever rolls back or exits
    # alone into a hung collective. Elastic worlds get a per-epoch
    # namespace (stale rounds from a previous epoch can never collide)
    # and the CLI's consensus timeout, which doubles as the unblock
    # path into reconfiguration when a peer dies mid-exchange.
    coord = (Coordinator(namespace=elastic.coord_namespace(),
                         timeout_s=args.coord_timeout_s)
             if elastic is not None
             else Coordinator(timeout_s=args.coord_timeout_s))
    # hang watchdog (resilience.watchdog): created and started BEFORE
    # the first consensus exchange below, so a peer dying during the
    # startup restore is bounded and stack-dumped like any other hang.
    # Inert at timeout 0.
    wd = HangWatchdog(args.stall_timeout,
                      straggler_factor=args.straggler_factor,
                      label=f"train[{tc.name}]").start()
    if elastic is not None:
        # first stall verdict is handed to the membership runtime (one
        # reconfiguration attempt under a grace window) before the
        # watchdog's exit-98 fallback fires
        wd.on_stall = elastic.notify_stall
    # one throwaway consensus exchange FIRST: coordination-service
    # breakage surfaces here, loudly, before any real verdict depends
    # on it (no-op single-process)
    wd.arm(0, "coord-warmup", steady=False)
    try:
        coord.warmup()
    finally:
        # disarm on the error path too: a raise here skips the loop's
        # finally, and an armed region left over an exception teardown
        # would fire a bogus stall over the real traceback
        wd.disarm()
    # the resume decision must be COLLECTIVE: agree_step is a lockstep
    # exchange, so a host skipping it while peers enter would strand
    # them mid-round. All-hosts-have gates the restore; a MIXED mesh
    # (some hosts have checkpoints, some lost theirs) refuses: starting
    # fresh over a stale directory would silently collide with the old
    # run's step numbers (orbax no-ops a save onto an existing step
    # dir), splicing old state into the new run at the first rollback.
    # short-circuit on args.resume: latest_step constructs a cached
    # manager with create=True, and a non-resume run must not turn the
    # probe into a mkdir (checkpoint._fs_steps documents the hazard)
    have_ckpt = args.resume and ckpt.latest_step(ckpt_dir) is not None
    all_have = args.resume and not coord.any_flag(not have_ckpt)
    have_any = args.resume and coord.any_flag(have_ckpt)
    if have_any and not all_have:
        sys.exit(f"[resume] checkpoints under {ckpt_dir} exist on "
                 f"{'this host' if have_ckpt else 'a peer host'} but "
                 f"not on every host — resuming would desync the mesh, "
                 f"and training fresh over a stale directory would "
                 f"splice the old run's checkpoints into this one; "
                 f"restore or clear the checkpoint directories so all "
                 f"hosts agree, or drop --resume and use a fresh "
                 f"--name/--output")
    if all_have:
        # verified restore: a truncated/poisoned newest step falls back
        # to the previous one with a message instead of crashing here.
        # Multi-host: agree_step pins every host to the SAME restored
        # step (min over hosts of what each disk verifiably holds), so
        # a restart never straddles two checkpoints. clean_debris: the
        # trainer owns this directory's writes — crashed-flush tmp
        # dirs are swept here.
        wd.arm(0, "resume-restore", steady=False)
        try:
            state, last_saved = coord.agree_step(
                lambda bound: restore_verified(ckpt_dir, state, step=bound,
                                               clean_debris=True),
                None)
        finally:
            wd.disarm()
        try:
            pos = load_position(ckpt_dir, last_saved, seed=tc.seed,
                                loader_kind=loader_kind,
                                fingerprint=pack_fingerprint)
        except LoaderKindMismatch as e:
            sys.exit(f"[resume] {e}")
        if pos is not None:
            stream_pos = pos
        if prune_above_restore:
            # elastic re-entry after a reconfiguration: a zombie flush
            # from the lost world may still commit a step ABOVE this
            # agreement; later restores must never land on it, and the
            # new segment's own saves must not no-op onto stale dirs
            from dexiraft_tpu.resilience import prune_steps_above

            prune_steps_above(ckpt_dir, last_saved)
        print(f"Resumed full state at step "
              f"{int(jax.device_get(state.step))} "
              f"(data stream: epoch {stream_pos.epoch}, "
              f"batch {stream_pos.offset})")
    elif args.restore_ckpt:
        ckpt.require_checkpoints(args.restore_ckpt)
        prev = ckpt.restore_checkpoint(args.restore_ckpt, state)
        merged, skipped = ckpt.restore_params_into(
            state.params, prev.params, verbose=True,
            skipped_report_dir=osp.join(args.log_dir, tc.name))
        state = state.replace(params=merged, batch_stats=prev.batch_stats)
        print(f"Partial restore from {args.restore_ckpt} "
              f"({len(skipped)} leaves fresh)")

    loader_kwargs = dict(
        seed=tc.seed, num_workers=args.num_workers,
        worker_mode=args.worker_mode, mp_start_method="spawn",
        process_index=jax.process_index(), process_count=jax.process_count())
    if records_ds is not None:
        from dexiraft_tpu.data.records import RecordLoader

        man = records_ds.manifest
        print(f"Training with {len(records_ds)} packed samples "
              f"({man.num_records} records in {len(man.shards)} shard(s), "
              f"fingerprint {man.fingerprint[:12]})")
        loader = RecordLoader(records_ds, tc.batch_size, **loader_kwargs)
    elif is_lm:
        from dexiraft_tpu.data.tokens import PackedTokens

        dataset = PackedTokens(args.tokens, cfg.seq_len)
        print(f"Training with {len(dataset)} packed rows of {cfg.seq_len} "
              f"positions ({dataset.fill:.1%} filled)")
        loader = Loader(dataset, tc.batch_size, **loader_kwargs)
    else:
        dataset = fetch_dataset(tc.stage, tc.image_size,
                                edge_root=args.edge_root)
        print(f"Training with {len(dataset)} image pairs")
        loader = Loader(dataset, tc.batch_size, **loader_kwargs)
    batches_per_epoch = max(len(loader), 1)

    step_fn = make_train_step(cfg, tc, mesh=mesh)
    logger = Logger(tc.sum_freq, log_dir=osp.join(args.log_dir, tc.name),
                    model_iters=tc.iters, pipeline_stats=loader.stats)
    # fsdp: validation's eval step compiles WITHOUT the train step's
    # gather fences, so it must never see fsdp-sharded params — gather
    # explicitly (sanctioned host window; layout.gather_state is a
    # no-op on replicated leaves / non-fsdp meshes)
    # (a language model has no validation set here: tc.validation is ())
    validate = None if is_lm else _make_validators(
        cfg, tc.validation,
        (lambda: layout.gather_state(state.variables, mesh)) if fsdp_live
        else (lambda: state.variables))

    prof_start, prof_stop = args.profile_steps or (-1, -1)
    prof_dir = osp.join(args.log_dir, tc.name, "profile")
    prof_active = False

    from dexiraft_tpu.train.guard import DivergenceGuard

    total_steps = int(jax.device_get(state.step))
    guard = DivergenceGuard(args.guard_threshold, args.max_rollbacks)

    # runtime guard mode (analysis/guards.py): --strict arms the
    # transfer guard + recompile sentinel AFTER the first step — warmup's
    # compile (and its constant transfers) is legal; from then on the
    # steady-state contract holds: zero recompiles, explicit transfers
    # only. This is guards.strict_mode() unrolled, because the loop
    # needs mark_warm/check at phase boundaries (warmup, validation)
    # that a single `with` region cannot express. Non-strict runs keep
    # the observe-only watch so drift still surfaces as a one-line
    # warning on the guard cadence.
    import contextlib

    from dexiraft_tpu.analysis import guards as jaxguards

    guard_stack = contextlib.ExitStack()
    watch: Optional[jaxguards.RecompileWatch] = None
    # bound to ckpt_dir: --keep_best scores persist in
    # <ckpt_dir>/retention.json, so a preempted-and-resumed run still
    # knows which old step is the best and keeps protecting it
    retention = RetentionPolicy(args.keep, args.keep_best,
                                directory=ckpt_dir)
    metrics = None
    preempted = False
    batch_devices = 0  # devices one batch leaf spans, from the first step

    def note_flush(info) -> None:
        """Surface one committed (or failed) async flush in the logger:
        blocked_s is what the step loop actually paid, flush_s the work
        that overlapped training — the async-save win is their ratio."""
        if not info:
            return
        print(f"[ckpt] step {info['step']}: flush {info['flush_s']*1e3:.0f}"
              f" ms, train blocked {info['blocked_s']*1e3:.0f} ms"
              + (f" (FLUSH FAILED: {info['error']})" if info["error"]
                 else ""))
        logger.write_dict({"ckpt/save_blocked_s": info["blocked_s"],
                           "ckpt/flush_s": info["flush_s"]},
                          step=info["step"])

    def save_with_position(step: int, block: bool = False) -> None:
        """Checkpoint + stream-position sidecar + retention GC, as one
        operation — every save leaves a resumable, bounded directory.

        The checkpoint flush is ASYNC: the previous save's flush is
        barriered out first (wait_pending — its blocked/flush times go
        to the logger), retention GC runs against the committed
        directory, and only then is the new flush handed off; training
        overlaps it until the next barrier (save / validation window /
        rollback / exit). The guard verdict was taken by the caller
        BEFORE this runs, so a poisoned state is never handed off.
        block=True (emergency/final save) commits before returning."""
        nonlocal last_saved
        # checkpoint I/O is a sanctioned host sync — exempt from the
        # strict transfer guard, and from the recompile sentinel: the
        # fsdp per-shard snapshot compiles a one-time device copy per
        # leaf shape (train/checkpoint._host_snapshot), which the
        # end-of-run strict verdict must not read as steady-state drift
        ctx = (watch.sanctioned() if watch is not None
               else contextlib.nullcontext())
        with ctx, jax.transfer_guard("allow"):
            note_flush(ckpt.wait_pending(ckpt_dir))
            # GC BEFORE the new handoff: delete_step barriers on any
            # in-flight flush, so GC after would serialize save+GC and
            # surrender the overlap
            retention.apply(ckpt_dir, protect=(last_saved,))
            ckpt.save_checkpoint(ckpt_dir, state, step=step, block=False)
            save_position(ckpt_dir, step, stream_pos, seed=tc.seed,
                          loader_kind=loader_kind,
                          fingerprint=pack_fingerprint)
            if block:
                info = ckpt.wait_pending(ckpt_dir)
                note_flush(info)
                if info and info["error"]:
                    # an emergency/final save that did not commit must
                    # not be reported (or bookkept) as a checkpoint
                    raise RuntimeError(
                        f"checkpoint flush of step {step} failed: "
                        f"{info['error']}")
        last_saved = step

    # fault injection for the chaos tests/smoke: a real signal/fault
    # fired at a pinned step, flowing through the real recovery paths
    chaos_step = None
    if args.chaos:
        from dexiraft_tpu.resilience import chaos as chaos_lib

        chaos_step = chaos_lib.parse_spec(args.chaos)

    # device-side double buffering: batch N+1 is device_put with the
    # step's input shardings while step N runs — the synchronous
    # host->device hop leaves the critical path (data/prefetch.py).
    # The stream starts at the checkpointed position (exact resume).
    batches = prefetch_to_device(
        loader.batches(start_epoch=stream_pos.epoch,
                       start_offset=stream_pos.offset),
        mesh, depth=tc.prefetch_depth, pipeline_stats=loader.stats)
    preempt = PreemptionHandler()
    try:
        with preempt, mesh:
            # NOT armed over the first iteration: it contains the XLA
            # compile, whose minutes would either trip a steady-state
            # stall_timeout or deaden the straggler EWMA. The watchdog
            # arms once the steady-state contract does (watch warmup).
            for batch in batches:
                if elastic is not None:
                    # membership verdict check: lock-and-read local
                    # state (the RPCs live on the lease thread), raising
                    # ReconfigureNeeded/ElasticFallback out of this
                    # segment at a step boundary
                    elastic.poll()
                # range-based (not equality) so resumed runs landing inside
                # the window still profile, and stop only pairs with a start
                if (not prof_active and prof_start <= total_steps < prof_stop):
                    jax.profiler.start_trace(prof_dir)
                    prof_active = True
                state, metrics = step_fn(state, batch)
                total_steps += 1
                first_iteration = watch is None
                if first_iteration:
                    # the first step of this process just compiled —
                    # arm the steady-state contract from here (the
                    # watchdog included: its timeout is sized for
                    # steps, not compiles)
                    watch = jaxguards.RecompileWatch(f"train[{tc.name}]")
                    watch.mark_warm()
                    print(jaxguards.setup_line(), flush=True)
                    batch_devices = len(
                        jax.tree.leaves(batch)[0].sharding.device_set)
                    if args.strict:
                        guard_stack.enter_context(
                            jax.transfer_guard("disallow"))
                    wd.arm(total_steps + 1, "step+data")
                # note: advanced on CONSUMPTION, never rewound by a
                # rollback — the stream continues past a divergent
                # window instead of replaying it. The loader publishes
                # each yielded batch's true (epoch, offset), so batches
                # it dropped (zero survivors) can never desync the
                # checkpointed position from the actual stream
                epoch_b, offset_b = loader.positions.popleft()
                stream_pos = StreamPosition(epoch_b, offset_b).advance(
                    1, batches_per_epoch)
                logger.push(metrics)
                if chaos_step is not None:
                    chaos_step(total_steps)
                if prof_active and total_steps >= prof_stop:
                    jax.block_until_ready(metrics["loss"])
                    jax.profiler.stop_trace()
                    prof_active = False
                    print(f"[profile] trace -> {prof_dir}")

                # divergence guard: checked on its own cadence AND before
                # every checkpoint write, so a poisoned state is never saved
                if not args.no_guard and (
                        total_steps % args.guard_every == 0
                        or total_steps % tc.val_freq == 0):
                    loss_v = float(jax.device_get(metrics["loss"]))
                    # state_finite is the step's POST-update verdict — the
                    # loss alone certifies only the PRE-update params, not
                    # the state the checkpoint below would save
                    state_ok = bool(jax.device_get(
                        metrics.get("state_finite", True)))
                    # a poisoned verdict on ANY host rolls back ALL
                    # hosts — one host restoring alone while its peers
                    # keep stepping is a hung collective, not a
                    # recovery (identity single-process)
                    poisoned_here = guard.poisoned(loss_v, state_ok)
                    if coord.any_flag(poisoned_here):
                        # the agreed target: the newest step EVERY host
                        # has saved (-1 encodes "nothing saved yet", and
                        # min() makes any such host abort the mesh)
                        agreed = coord.min_int(
                            last_saved if last_saved is not None else -1)
                        target = None if agreed < 0 else agreed
                        guard.consume_rollback(
                            loss_v, state_ok, f"step {total_steps}"
                            + ("" if poisoned_here
                               else " (verdict from a peer host)"),
                            target, ckpt_dir=ckpt_dir)
                        # verified restore: should the rollback target
                        # itself turn out damaged, fall back further
                        # rather than crash mid-recovery — and re-agree
                        # across hosts until everyone restored the SAME
                        # step. Restore is sanctioned host I/O (strict-
                        # guard exempt); the guard must not turn
                        # recovery into a second failure.
                        wd.disarm(feed_ewma=False)
                        wd.arm(total_steps, "rollback-restore", steady=False)
                        with jax.transfer_guard("allow"):
                            state, last_saved = coord.agree_step(
                                lambda b: restore_verified(
                                    ckpt_dir, state, step=b,
                                    clean_debris=True),
                                target)
                        # the restored state has no fresh metrics; leaving
                        # the poisoned step's here would make the END-OF-RUN
                        # guard below veto the final save of a GOOD state
                        metrics = None
                        # printed AFTER the restore with the step it
                        # actually landed on — a verified fallback past
                        # the nominal target must not tell the operator
                        # to inspect a checkpoint that was never used
                        print(f"[guard] loss {loss_v:.4g} "
                              f"(state_finite={state_ok}, "
                              f"poisoned_here={poisoned_here}) at step "
                              f"{total_steps}; restored {ckpt_dir} step "
                              f"{last_saved} (rollback {guard.rollbacks}/"
                              f"{args.max_rollbacks})")
                        # relative rewind: the logger's counter is per-run
                        # (starts at 0 on resume), so subtract the rolled-
                        # back window rather than assigning the global step
                        logger.rewind(logger.total_steps
                                      - (total_steps - last_saved))
                        total_steps = last_saved
                        wd.disarm(feed_ewma=False)
                        wd.arm(total_steps + 1, "step+data")
                        continue  # never checkpoint on a rollback step

                # recompile sentinel, on the same cadence as the guard:
                # strict raises, non-strict warns once (satellite: drift
                # surfaces even when --strict is off)
                if total_steps % args.guard_every == 0:
                    if args.strict:
                        watch.check()
                    else:
                        watch.warn_if_drifted()

                # preemption is a COLLECTIVE verdict: one host's SIGTERM
                # must stop every host at the same step (a lone host
                # saving-and-exiting strands its peers in the next
                # collective). Single-process: the local flag, checked
                # every step, exactly as before; multi-host: one tiny
                # allgather every --coord_every steps.
                if coord.size == 1:
                    stop_now = preempt.triggered
                else:
                    stop_now = (total_steps % args.coord_every == 0
                                and coord.any_flag(preempt.triggered))
                if stop_now:
                    # graceful preemption: ONE emergency save at the
                    # step boundary (guard-checked — preemption is not a
                    # license to persist a poisoned state), then leave
                    # the loop; the position sidecar makes the later
                    # --resume continue the exact sample sequence
                    preempted = True
                    wd.disarm(feed_ewma=False)
                    wd.arm(total_steps, "emergency-save", steady=False)
                    if args.on_preempt == "save":
                        poisoned = False
                        if not args.no_guard and metrics is not None:
                            loss_v = float(jax.device_get(metrics["loss"]))
                            state_ok = bool(jax.device_get(
                                metrics.get("state_finite", True)))
                            poisoned = guard.poisoned(loss_v, state_ok)
                        # the save is all-hosts-or-none (orbax's save is
                        # itself collective): one host's poison vetoes
                        # the emergency save everywhere
                        if coord.any_flag(poisoned):
                            print(f"[preempt] state at step {total_steps} "
                                  f"is poisoned; NOT saving — latest good "
                                  f"checkpoint remains step {last_saved}")
                        else:
                            # block: the process exits right after — the
                            # flush must commit before it does
                            save_with_position(total_steps, block=True)
                            print(f"[preempt] emergency checkpoint: "
                                  f"{ckpt_dir} step {total_steps} (data "
                                  f"stream epoch {stream_pos.epoch}, batch "
                                  f"{stream_pos.offset}); resume with "
                                  f"--resume")
                    else:
                        print(f"[preempt] --on_preempt abort: stopping "
                              f"without saving (latest checkpoint: step "
                              f"{last_saved})")
                    break

                in_val_window = total_steps % tc.val_freq == 0
                if in_val_window:
                    # the step part of this iteration is done: feed its
                    # duration to the straggler EWMA (not on the first
                    # iteration — its armed window is partial) and
                    # re-arm over the sanctioned (slow)
                    # checkpoint+validation stretch
                    wd.disarm(feed_ewma=not first_iteration)
                    wd.arm(total_steps, "checkpoint+validation",
                           steady=False)
                    save_with_position(total_steps)
                    # grow-at-checkpoint: absorption is a COLLECTIVE
                    # decision (any_flag), so every incumbent leaves
                    # this segment at the same boundary; the segment
                    # loop commits the in-flight save, absorbs the
                    # joiners, and re-enters in the larger world
                    if elastic is not None and coord.any_flag(
                            bool(elastic.pending_joins())):
                        raise _GrowBoundary(total_steps)
                    # validation is a sanctioned window: its eval steps
                    # compile once per set (absorbed by mark_warm below)
                    # and its dataset readers are host-side by design
                    with jax.transfer_guard("allow"):
                        if tc.validation:
                            # barrier before the validation window —
                            # the resilience contract's barrier set
                            # (save/validation/rollback/GC/exit), kept
                            # deliberately even though it trades away
                            # flush-over-validation overlap: validation
                            # notes retention scores for the step being
                            # flushed, and a window where --keep_best
                            # ranks a checkpoint whose flush later
                            # FAILS would protect a step that does not
                            # exist. Runs without validation sets keep
                            # the full overlap.
                            note_flush(ckpt.wait_pending(ckpt_dir))
                        for vname in tc.validation:
                            results = validate(vname)
                            logger.write_dict(results, step=total_steps)
                            # retention's quality signal: the first
                            # EPE-like scalar of the FIRST validation set
                            # (lower = better) ranks this checkpoint for
                            # --keep_best
                            if vname == tc.validation[0] and results:
                                epe_keys = [k for k in results
                                            if "epe" in k or k == vname]
                                if epe_keys:
                                    retention.note_score(
                                        total_steps, results[epe_keys[0]])
                    watch.mark_warm()
                if total_steps >= tc.num_steps:
                    break
                # close this iteration's armed window (a validation
                # window stays out of the step-time EWMA, and the
                # first iteration's partial mid-body arm never seeds
                # it) and open the next — the re-arm also covers the
                # prefetch fetch between iterations. A first iteration
                # that landed on a val window still re-arms here, so
                # the non-steady validation region never leaks over
                # the next iteration.
                if not first_iteration or in_val_window:
                    wd.disarm(feed_ewma=not in_val_window)
                    wd.arm(total_steps + 1, "step+data")
    finally:
        # stop the host pipeline — on the happy path AND when the loop
        # dies (interrupt, OOM, failed restore): the Loader's feeder
        # thread / worker pool must not outlive the loop, and the
        # in-flight prefetched device batches have no work left to do
        # while validation and the final save run below
        batches.close()
        # disarm the transfer guard WITH the loop (also on the error
        # path — a leaked 'disallow' would poison later jax use in this
        # process); the final save below is host I/O, not steady state
        guard_stack.close()
        # the monitor must not outlive the loop: the exit path below is
        # host I/O whose duration has nothing to do with step progress
        wd.stop()
    if prof_active:  # window extended past the last step: finalize
        jax.profiler.stop_trace()
        print(f"[profile] trace (truncated at end of run) -> {prof_dir}")
    # the final save honors the guard too: a nan that arrives between
    # guard checks and the end of the run must not become the latest
    # checkpoint that --resume/eval would silently load. A preempted
    # run already made its one emergency save (or declined to) inside
    # the loop.
    final_ok = not preempted
    if final_ok and not args.no_guard and metrics is not None:
        loss_v = float(jax.device_get(metrics["loss"]))
        state_ok = bool(jax.device_get(metrics.get("state_finite", True)))
        if guard.poisoned(loss_v, state_ok):
            final_ok = False
            print(f"[guard] final state poisoned (loss {loss_v:.4g}, "
                  f"state_finite={state_ok}); "
                  f"skipping the final save — latest good checkpoint "
                  f"remains step {last_saved}")
    if final_ok:
        # block: this is the exit barrier — the process must not return
        # control with a flush still in flight
        save_with_position(total_steps, block=True)
    else:
        # even a vetoed final save barriers out any in-flight flush of
        # an earlier GOOD state before the process exits
        with jax.transfer_guard("allow"):
            note_flush(ckpt.wait_pending(ckpt_dir))
    cstats = ckpt.save_stats(ckpt_dir)
    if cstats.get("saves"):
        print(f"[ckpt] {cstats['saves']} async save(s): total flush "
              f"{cstats['total_flush_s']:.2f}s overlapped, total train "
              f"blocked {cstats['total_blocked_s']:.2f}s"
              + (f", {cstats['failed']} FAILED" if cstats.get("failed")
                 else ""))
    if wd.enabled and wd.straggler_warnings:
        print(f"[watchdog] {wd.straggler_warnings} straggler warning(s) "
              f"this run (EWMA step {wd.ewma_s:.2f}s)")
    logger.close()
    print(f"[prefetch] {batches.summary()}")
    # where the run lived: how many devices a batch leaf spanned and
    # what each mesh device holds (the CPU backend reports no memory
    # statistics) — chip_smoke.py --chips 4 reads this line
    print("[train] placement: " + json.dumps({
        "batch_devices": batch_devices,
        "bytes_in_use": {
            str(d.id): (d.memory_stats() or {}).get("bytes_in_use")
            for d in mesh.devices.flat}}))
    if loader.stats.faults:
        print(f"[pipeline] {loader.stats.summary()}")
    # end-of-run sentinel verdict: strict fails the run on any
    # unabsorbed post-warmup compile; non-strict gets the (once-only)
    # drift warning if the cadence check never fired
    if watch is not None:
        if args.strict:
            watch.check()
        else:
            watch.warn_if_drifted()
    if preempted:
        print(f"Preempted ({preempt.signal_name}) at step {total_steps} "
              f"-> {ckpt_dir}")
    else:
        print(f"Done: {total_steps} steps -> {ckpt_dir}")


def _elastic_main(cfg: RAFTConfig, tc: TrainConfig, args) -> None:
    """The elastic segment loop: each train() call is one membership
    epoch; membership verdicts unwind it, the world is reconfigured
    (shrink on loss, grow at checkpoint boundaries), and the next
    segment re-enters with resume semantics in the new world. Only the
    cases elastic cannot absorb — rank-0 loss, a cascade below
    --min_hosts, a failed agreement — exit 98, the watchdog's
    restart-the-pod contract."""
    import os
    import os.path as osp

    from dexiraft_tpu.data.loader import world_compatible
    from dexiraft_tpu.parallel.distributed import _env_int
    from dexiraft_tpu.resilience import (
        CoordinatorTimeout,
        ElasticConfig,
        ElasticFallback,
        MembershipRuntime,
        ReconfigureNeeded,
    )
    from dexiraft_tpu.resilience.watchdog import STALL_EXIT_CODE
    from dexiraft_tpu.train import checkpoint as ckpt

    ckpt_dir = osp.join(args.output, tc.name)
    ecfg = ElasticConfig(
        # how peers dial THIS host (the coordination service binds here
        # when this host becomes an epoch's rank 0)
        host=os.environ.get("DEXIRAFT_ELASTIC_HOST", "127.0.0.1"),
        # the one channel that exists before a joiner has KV access:
        # the shared checkpoint filesystem
        board_dir=osp.join(ckpt_dir, "membership"),
        min_hosts=args.min_hosts,
        global_batch=tc.batch_size,
        # survivors may arrive at the agreement only after their own
        # consensus op times out against the dead peer
        reconfig_timeout_s=max(30.0, args.coord_timeout_s * 2),
    )
    mrt = MembershipRuntime(ecfg)
    try:
        if args.join:
            mrt.join(args.join)
            args.resume = True  # a joiner always enters via restore
        else:
            addr = os.environ.get("JAX_COORDINATOR_ADDRESS")
            if addr is None:
                # solo incumbent: a one-host elastic world whose whole
                # point is absorbing joiners later
                addr = "127.0.0.1:7639"
                num, pid = 1, 0
            else:
                num = _env_int("JAX_NUM_PROCESSES")
                pid = _env_int("JAX_PROCESS_ID")
            mrt.bootstrap(addr, num, pid)
        prune = False
        while True:
            reason = world_compatible(tc.batch_size, mrt.size)
            if reason is not None:  # pre-checked by reconfigure; belt+braces
                raise ElasticFallback(reason)
            try:
                train(cfg, tc, args, elastic=mrt,
                      prune_above_restore=prune)
                return
            except (ReconfigureNeeded, CoordinatorTimeout) as verdict:
                print(f"[elastic] segment ended at epoch {mrt.epoch}: "
                      f"{verdict}", flush=True)
                mrt.reconfigure(dead=getattr(verdict, "dead", None))
                prune = True
            except _GrowBoundary as g:
                # commit the boundary's in-flight save before the
                # graceful teardown, so the joiners restore it
                ckpt.wait_pending(ckpt_dir)
                print(f"[elastic] absorbing "
                      f"{[j['name'] for j in mrt.pending_joins()]} at "
                      f"step {g.step}", flush=True)
                mrt.absorb_joins()
                prune = False
            args.resume = True  # every later segment enters via restore
    except ElasticFallback as e:
        print(f"[elastic] fallback to pod restart: {e}", flush=True)
        raise SystemExit(STALL_EXIT_CODE)
    finally:
        mrt.close()


def main(argv=None) -> None:
    from dexiraft_tpu.parallel.distributed import initialize

    args = build_parser().parse_args(argv)
    if args.coord_every < 1:
        sys.exit("train: --coord_every must be >= 1 (it is a step "
                 "modulus; there is no 'never poll' mode — preemption "
                 "broadcast is what keeps a multi-host mesh exiting "
                 "together)")
    if args.coord_timeout_s <= 0:
        sys.exit("train: --coord_timeout_s must be > 0 (a consensus op "
                 "with no timeout hangs the pod on the first dead peer)")
    cfg, tc = resolve_configs(args)
    if args.elastic or args.join:
        # elastic owns runtime initialization (per membership epoch);
        # the plain initialize() path must not claim the process first
        _elastic_main(cfg, tc, args)
        return
    initialize()  # no-op single-process; multi-host via env vars
    train(cfg, tc, args)


if __name__ == "__main__":
    main(sys.argv[1:])
