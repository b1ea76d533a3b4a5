#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the main path still starts on
the chip: train a few steps, evaluate, serve a few requests, all at the
full width of v5 (42.6 M parameters, DexiNed in front, two streams)
through the entry points a user calls.

    python chip_smoke.py              # one chip: train, eval, serve
    python chip_smoke.py --chips 4    # data-parallel train on four chips
                                      # against the same run on one

The parent process never imports JAX: a chip belongs to one process at a
time, so every phase is its own child (`python -m dexiraft_tpu ...`), one
after another. From --seed the parent writes a synthetic FlyingChairs
tree (384x512) and a Sintel-shaped tree (436x1024, padded 440x1024)
under .smoke_work/ and points DEXIRAFT_DATA_DIR at it; each child's log
stays under chiprun_out/. Weights are random: the
`train` phase's checkpoint is what `eval` and `serve` restore.

What passes:
  train  exit 0, finite per-step losses in metrics.jsonl, a checkpoint
         that the next phases restore
  eval   `--corr_impl auto` and `--corr_impl allpairs` write the same
         flows within AUTO_VS_ALLPAIRS_TOL (the kernel checked against
         the plain XLA path on the chip), and the lowered eval step of
         the auto run holds the Pallas kernel (`tpu_custom_call`) when
         auto names one
  serve  /healthz answers, a few 436x1024 pairs come back with the
         right shape, finite, and one matches the eval flow for the
         same pair; SIGTERM drains to exit 0
  --chips 4  the four-chip and the one-chip train agree per step within
         LOSS_TOL, every device holds memory, the batch spans 4 devices

It refuses to run when JAX finds no TPU (checked in a child) and then
prints no result. The LAST line of stdout is the result:
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}.
Wall times printed per phase are set-up plus run, not performance.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import glob
import http.client
import json
import os
import os.path as osp
import re
import shutil
import signal
import subprocess
import sys
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

REPO = osp.dirname(osp.abspath(__file__))

# Flow agreement between `--corr_impl auto` (the flash-fused Pallas
# kernel) and `--corr_impl allpairs` (plain XLA) on the chip, as mean
# end-point difference over mean flow magnitude. They are not bit-equal
# by construction: XLA's f32 matmul feeds the MXU bf16 inputs while the
# kernel's matmuls keep f32, the sums associate differently, and under
# --mixed_precision the bf16 update block carries that ~1e-3 input
# difference through 32 recurrent iterations of a random-weight model.
# Seen on the chip (PR 21): 0.0092-0.0106. A kernel that computed
# another function would be O(1) apart.
AUTO_VS_ALLPAIRS_TOL = 0.05
# The served flow against the eval flow of the same pair: same kernel
# configuration and iteration count; the programs differ only in batch
# size (serve pads its batch to 4) and a materialized zero flow_init.
# Seen on the chip (PR 21): 0.0105-0.0114.
SERVE_VS_EVAL_TOL = 0.05
# Per-step loss of the four-chip run against the one-chip run (same
# seed, steps and global batch), relative: the partitioned program sums
# the batch and the BatchNorm moments in another order, in bf16 compute,
# and the difference grows with each update. Seen on the chip (PR 21):
# 7e-5 at step 1 to 8e-4 at step 4.
LOSS_TOL = 0.01


class PhaseFailed(Exception):
    """A phase did not meet its pass condition; the smoke exits non-zero."""


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the phases run at. FULL is the contract; the CPU rehearsal
    (tests/test_chip_smoke.py, marked slow) passes a tiny one."""

    variant: str = "v5"
    small: bool = False
    chairs_hw: Tuple[int, int] = (384, 512)   # FlyingChairs frame size
    crop_hw: Tuple[int, int] = (368, 496)     # the chairs-stage crop
    sintel_hw: Tuple[int, int] = (436, 1024)  # pads to 440x1024
    # chairs batch of the mixed-precision recipe (config.MIXED_STAGES);
    # divides over four chips. Without remat the step wants 33.6 GB at
    # batch 10 and 20.2 GB with --remat_lookup (described-topology
    # compile, v5e 15.75 GB), hence full per-iteration --remat
    train_batch: int = 8
    train_steps: int = 4
    train_iters: int = 12
    train_flags: Tuple[str, ...] = ("--remat",)
    eval_iters: int = 32
    phase_timeout_s: float = 900.0


FULL = Sizes()


@dataclasses.dataclass
class Context:
    work: str                  # data, checkpoints, flows (hundreds of MB)
    logs: str                  # one log per child, small enough to keep
    sizes: Sizes
    platform: str              # what the probe must report
    chips: int
    seed: int
    env: Dict[str, str]
    device: Optional[dict] = None      # the probe's report
    children: List[subprocess.Popen] = dataclasses.field(default_factory=list)

    def path(self, *parts: str) -> str:
        return osp.join(self.work, *parts)

    def log_path(self, name: str) -> str:
        return osp.join(self.logs, f"{name}.log")


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


# ---- children -----------------------------------------------------------


def start_child(ctx: Context, name: str, argv: Sequence[str],
                env: Optional[Dict[str, str]] = None) -> subprocess.Popen:
    """Start one child with stdout+stderr in <logs>/<name>.log, in its
    own session so the whole group can be stopped."""
    log_f = open(ctx.log_path(name), "wb")
    try:
        proc = subprocess.Popen(list(argv), cwd=REPO, env=env or ctx.env,
                                stdout=log_f, stderr=subprocess.STDOUT,
                                start_new_session=True)
    finally:
        log_f.close()
    ctx.children.append(proc)
    return proc


def stop_child(proc: subprocess.Popen) -> None:
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except OSError:
            pass
        proc.wait()


def read_log(ctx: Context, name: str) -> str:
    with open(ctx.log_path(name), errors="replace") as f:
        return f.read()


def run_child(ctx: Context, name: str, argv: Sequence[str],
              env: Optional[Dict[str, str]] = None) -> str:
    """Run one child to its end; its output, or PhaseFailed with the tail."""
    proc = start_child(ctx, name, argv, env)
    try:
        rc = proc.wait(timeout=ctx.sizes.phase_timeout_s)
    except subprocess.TimeoutExpired:
        stop_child(proc)
        raise PhaseFailed(f"{name}: no exit after "
                          f"{ctx.sizes.phase_timeout_s:.0f}s; killed\n"
                          + read_log(ctx, name)[-4000:])
    text = read_log(ctx, name)
    if rc != 0:
        raise PhaseFailed(f"{name}: exit {rc}\n{text[-4000:]}")
    return text


def banner(text: str, label: str, ctx: Context) -> dict:
    """The `[label] device: {...}` line an entry point printed
    (profiling.device_banner), held to the platform the smoke expects."""
    m = re.search(rf"^\[{label}\] device: (\{{.*\}})$", text, re.M)
    if m is None:
        raise PhaseFailed(f"{label}: printed no device banner")
    info = json.loads(m.group(1))
    if info["platform"] != ctx.platform:
        raise PhaseFailed(f"{label}: ran on {info['platform']!r}, not "
                          f"{ctx.platform!r}")
    return info


def cache_entries(ctx: Context) -> int:
    d = ctx.env["JAX_COMPILATION_CACHE_DIR"]
    return len(os.listdir(d)) if osp.isdir(d) else 0


# ---- synthetic data -----------------------------------------------------


def _texture(rng, h: int, w: int):
    """A smooth random texture, so the encoders see structure."""
    import numpy as np

    coarse = rng.uniform(0, 255, (h // 16 + 2, w // 16 + 2, 3))
    img = np.kron(coarse, np.ones((16, 16, 1)))[:h, :w]
    img += rng.normal(0, 6, img.shape)
    return np.clip(img, 0, 255).astype(np.uint8)


def write_data(ctx: Context) -> None:
    """FlyingChairs_release/ and Sintel/test/ under <work>/data, from the
    seed: frame 2 is frame 1 shifted by a known integer flow."""
    import numpy as np
    from PIL import Image

    from dexiraft_tpu.data.flow_io import write_flo

    rng = np.random.default_rng(ctx.seed)
    s = ctx.sizes
    chairs = ctx.path("data", "FlyingChairs_release", "data")
    os.makedirs(chairs, exist_ok=True)
    n_pairs = max(2 * s.train_batch, 8)
    h, w = s.chairs_hw
    for i in range(n_pairs):
        dx, dy = (int(v) for v in rng.integers(-6, 7, 2))
        img1 = _texture(rng, h, w)
        Image.fromarray(img1).save(osp.join(chairs, f"{i:05d}_img1.ppm"))
        Image.fromarray(np.roll(img1, (dy, dx), (0, 1))).save(
            osp.join(chairs, f"{i:05d}_img2.ppm"))
        flow = np.broadcast_to(np.float32([dx, dy]), (h, w, 2))
        write_flo(osp.join(chairs, f"{i:05d}_flow.flo"), flow)
    with open(osp.join(chairs, "..", "chairs_split.txt"), "w") as f:
        f.write("\n".join(["1"] * n_pairs))

    h, w = s.sintel_hw
    for dstype in ("clean", "final"):
        scene = ctx.path("data", "Sintel", "test", dstype, "smoke_1")
        os.makedirs(scene, exist_ok=True)
        frame = _texture(rng, h, w)
        for i in range(1, 4):
            Image.fromarray(frame).save(osp.join(scene, f"frame_{i:04d}.png"))
            frame = np.roll(frame, (2, -3), (0, 1))


# ---- phases -------------------------------------------------------------

_PROBE = r"""
import importlib.metadata, json, time
import jax, jax.numpy as jnp, jaxlib
d = jax.devices()
out = {"platform": d[0].platform, "kind": d[0].device_kind, "count": len(d),
       "jax": jax.__version__, "jaxlib": jaxlib.__version__}
try:
    out["libtpu"] = importlib.metadata.version("libtpu")
except importlib.metadata.PackageNotFoundError:
    out["libtpu"] = None
# does block_until_ready block? Dispatch returns early, the block waits
# for the device, and a fetch after it finds the value already there.
# The work is sized to take the device tens of milliseconds.
@jax.jit
def work(x):
    return jax.lax.fori_loop(0, 64, lambda i, a: jnp.tanh(a @ a), x)
n = 4096 if d[0].platform == "tpu" else 512
x = jnp.ones((n, n), jnp.float32) * 1e-3
float(work(x)[0, 0])  # compile the work and the fetch outside the timing
t0 = time.perf_counter(); y = work(x)
t1 = time.perf_counter(); y.block_until_ready()
t2 = time.perf_counter(); float(y[0, 0])
t3 = time.perf_counter()
out.update(dispatch_ms=(t1 - t0) * 1e3, block_ms=(t2 - t1) * 1e3,
           fetch_after_block_ms=(t3 - t2) * 1e3)
print("PROBE " + json.dumps(out))
"""


def phase_probe(ctx: Context) -> None:
    """What JAX finds, in a child. Anything but the expected platform and
    chip count refuses the run."""
    text = run_child(ctx, "probe", [sys.executable, "-c", _PROBE])
    m = re.search(r"^PROBE (\{.*\})$", text, re.M)
    if m is None:
        raise PhaseFailed(f"probe: no report\n{text[-2000:]}")
    dev = json.loads(m.group(1))
    log(f"probe: {json.dumps(dev)}")
    if dev["platform"] != ctx.platform:
        raise PhaseFailed(
            f"JAX found platform {dev['platform']!r} "
            f"({dev['kind']}), not {ctx.platform!r}: refusing to run — "
            "the smoke proves the chip path and has no other mode")
    if dev["count"] != ctx.chips:
        raise PhaseFailed(f"JAX found {dev['count']} device(s), this run "
                          f"needs {ctx.chips}")
    if dev["fetch_after_block_ms"] > max(0.2 * dev["block_ms"], 2.0):
        raise PhaseFailed(
            "block_until_ready did not wait for the device: the fetch "
            f"after it still took {dev['fetch_after_block_ms']:.1f} ms "
            f"(block {dev['block_ms']:.1f} ms)")
    # what the spawners' chip count (dexiraft_tpu.chips) sees here: it
    # reads device files because those parents may not import JAX
    from dexiraft_tpu.chips import local_chip_count

    log(f"probe: {local_chip_count()} TPU device file(s) on this host")
    ctx.device = dev


def _model_flags(s: Sizes) -> List[str]:
    return (["--variant", s.variant, "--mixed_precision"]
            + (["--small"] if s.small else []))


def train_argv(ctx: Context, name: str) -> List[str]:
    s = ctx.sizes
    return [sys.executable, "-m", "dexiraft_tpu", "train",
            "--name", name, "--stage", "chairs", *_model_flags(s),
            "--image_size", *map(str, s.crop_hw),
            "--iters", str(s.train_iters), *s.train_flags,
            "--batch_size", str(s.train_batch),
            "--num_steps", str(s.train_steps),
            "--sum_freq", "1", "--val_freq", "1000000",
            "--compile_cache", "--seed", str(ctx.seed),
            "--output", ctx.path("ckpts"), "--log_dir", ctx.path("runs")]


def run_train(ctx: Context, name: str,
              env: Optional[Dict[str, str]] = None) -> Tuple[dict, List[float]]:
    """One `train` child; its banner and finite per-step losses."""
    import math

    # the native decoder must come from committed source: a stale .so
    # with a newer mtime would win (data/native.py), so build anew
    shutil.rmtree(osp.join(REPO, "native", "build"), ignore_errors=True)
    text = run_child(ctx, f"train_{name}", train_argv(ctx, name), env)
    info = banner(text, "train", ctx)
    losses = []
    with open(ctx.path("runs", name, "metrics.jsonl")) as f:
        for line in f:
            rec = json.loads(line)
            if "loss" in rec:
                losses.append(rec["loss"])
    if len(losses) != ctx.sizes.train_steps:
        raise PhaseFailed(f"train {name}: {len(losses)} loss records, "
                          f"expected {ctx.sizes.train_steps}")
    if not all(math.isfinite(v) for v in losses):
        raise PhaseFailed(f"train {name}: non-finite loss in {losses}")
    if not glob.glob(ctx.path("ckpts", name, "*")):
        raise PhaseFailed(f"train {name}: no checkpoint written")
    m = re.search(r"^\[train\] placement: (\{.*\})$", text, re.M)
    if m is None:
        raise PhaseFailed(f"train {name}: printed no placement line")
    info["placement"] = json.loads(m.group(1))
    log(f"train {name}: losses {losses}, decoder {info['decoder']}, "
        f"mesh {info['mesh']}")
    return info, losses


def phase_train(ctx: Context) -> dict:
    info, _ = run_train(ctx, "smoke")
    return info


def eval_argv(ctx: Context, corr_impl: str, out_dir: str) -> List[str]:
    s = ctx.sizes
    return [sys.executable, "-m", "dexiraft_tpu", "eval",
            "--model", ctx.path("ckpts", "smoke"), *_model_flags(s),
            "--submission", "sintel", "--iters", str(s.eval_iters),
            "--corr_impl", corr_impl, "--output", out_dir]


def _read_flows(root: str) -> Dict[str, "object"]:
    from dexiraft_tpu.data.flow_io import read_flo

    files = sorted(glob.glob(osp.join(root, "*", "*", "*.flo")))
    return {osp.relpath(p, root): read_flo(p) for p in files}


def check_flows(what: str, got, want, hw: Tuple[int, int],
                tol: float) -> float:
    import numpy as np

    if got.shape != (*hw, 2):
        raise PhaseFailed(f"{what}: flow shape {got.shape}, expected "
                          f"{(*hw, 2)}")
    if not np.isfinite(got).all():
        raise PhaseFailed(f"{what}: non-finite flow")
    epe = float(np.sqrt(((got - want) ** 2).sum(-1)).mean())
    mag = float(np.sqrt((want ** 2).sum(-1)).mean())
    rel = epe / max(mag, 1e-6)
    log(f"{what}: mean end-point difference {epe:.5f} px over mean "
        f"magnitude {mag:.4f} px = {rel:.5f} (tolerance {tol})")
    if rel > tol:
        raise PhaseFailed(f"{what}: flows differ by {rel:.5f} of their "
                          f"magnitude, tolerance {tol}")
    return rel


def phase_eval(ctx: Context) -> dict:
    ir_dir = ctx.path("ir_auto")
    text = run_child(ctx, "eval_auto",
                     eval_argv(ctx, "auto", ctx.path("flow_auto")),
                     dict(ctx.env, JAX_DUMP_IR_TO=ir_dir))
    info = banner(text, "eval", ctx)
    if info["corr_impl"] == "flash":
        # the lowered eval step must hold the kernel: no interpreter,
        # no reference standing in for it
        held = [p for p in glob.glob(osp.join(ir_dir, "*jit_step*"))
                if "tpu_custom_call" in open(p, errors="replace").read()]
        if not held:
            raise PhaseFailed(
                f"eval: auto resolved to {info['corr_impl']} but no "
                f"lowered step under {ir_dir} holds a tpu_custom_call")
        log(f"eval: kernel present in {osp.basename(held[0])}")
    text2 = run_child(ctx, "eval_allpairs",
                      eval_argv(ctx, "allpairs", ctx.path("flow_allpairs")))
    banner(text2, "eval", ctx)
    auto = _read_flows(ctx.path("flow_auto"))
    ref = _read_flows(ctx.path("flow_allpairs"))
    if not auto or auto.keys() != ref.keys():
        raise PhaseFailed(f"eval: flow files differ: {sorted(auto)} vs "
                          f"{sorted(ref)}")
    for name in sorted(auto):
        check_flows(f"eval auto vs allpairs {name}", auto[name], ref[name],
                    ctx.sizes.sintel_hw, AUTO_VS_ALLPAIRS_TOL)
    return info


def _http(port: int, method: str, path: str, body: Optional[bytes] = None,
          timeout: float = 300.0) -> Tuple[int, bytes]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(method, path, body=body)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def phase_serve(ctx: Context) -> dict:
    from dexiraft_tpu.data.flow_io import read_flo, read_image
    from dexiraft_tpu.serve.server import decode_response, encode_request

    s = ctx.sizes
    h, w = s.sintel_hw
    # the eval phase's configuration, so one answer can be held to the
    # eval flow: same iterations, pads to the same 8-multiple shape
    argv = [sys.executable, "-m", "dexiraft_tpu", "serve",
            "--model", ctx.path("ckpts", "smoke"), *_model_flags(s),
            "--port", "0", "--iters", str(s.eval_iters),
            "--bucket_multiple", "8", "--warmup", f"{h}x{w}",
            "--stream_sessions_mb", "0"]
    proc = start_child(ctx, "serve", argv)
    try:
        port = None
        deadline = time.monotonic() + s.phase_timeout_s
        while port is None:
            m = re.search(r"listening on http://[^:]+:(\d+)",
                          read_log(ctx, "serve"))
            if m:
                port = int(m.group(1))
            elif proc.poll() is not None:
                raise PhaseFailed(f"serve: exit {proc.returncode} before "
                                  f"listening\n{read_log(ctx, 'serve')[-4000:]}")
            elif time.monotonic() > deadline:
                raise PhaseFailed("serve: not listening after "
                                  f"{s.phase_timeout_s:.0f}s\n"
                                  + read_log(ctx, "serve")[-4000:])
            else:
                time.sleep(0.5)
        info = banner(read_log(ctx, "serve"), "serve", ctx)
        status, _ = _http(port, "GET", "/healthz", timeout=30.0)
        if status != 200:
            raise PhaseFailed(f"serve: /healthz answered {status}")

        pairs = [("clean", 1), ("clean", 2), ("final", 1)]

        def ask(pair):
            dstype, i = pair
            scene = ctx.path("data", "Sintel", "test", dstype, "smoke_1")
            body = encode_request(
                read_image(osp.join(scene, f"frame_{i:04d}.png")),
                read_image(osp.join(scene, f"frame_{i + 1:04d}.png")))
            status, resp = _http(port, "POST", "/v1/flow", body)
            if status != 200:
                raise PhaseFailed(f"serve: POST /v1/flow answered {status}: "
                                  f"{resp[:300]!r}")
            return decode_response(resp)

        with concurrent.futures.ThreadPoolExecutor(len(pairs)) as pool:
            flows = list(pool.map(ask, pairs))
        for (dstype, i), flow in zip(pairs, flows):
            want = read_flo(ctx.path("flow_auto", dstype, "smoke_1",
                                     f"frame{i:04d}.flo"))
            check_flows(f"serve vs eval {dstype}/{i}", flow, want,
                        s.sintel_hw, SERVE_VS_EVAL_TOL)
        proc.send_signal(signal.SIGTERM)
        try:
            rc = proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            raise PhaseFailed("serve: no drained exit 120s after SIGTERM")
        if rc != 0:
            raise PhaseFailed(f"serve: exit {rc} after SIGTERM\n"
                              + read_log(ctx, "serve")[-4000:])
        log(f"serve: {len(pairs)} requests answered, drained exit 0")
        return info
    finally:
        stop_child(proc)


def solo_env(env: Dict[str, str]) -> Dict[str, str]:
    """``env`` with the child held to chip 0 of the host through the TPU
    runtime's own environment (the recipe the serve spawners use); in
    the CPU rehearsal, to one virtual device."""
    from dexiraft_tpu.chips import one_chip_env

    out = one_chip_env(0, env)
    out["XLA_FLAGS"] = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "",
        out.get("XLA_FLAGS", "")).strip()
    return out


def phase_train4(ctx: Context) -> dict:
    """The train phase's command on all chips, then the same steps, seed
    and global batch on one chip of the same host."""
    info4, loss4 = run_train(ctx, "chips4")
    place = info4["placement"]
    if place["batch_devices"] != ctx.chips:
        raise PhaseFailed(f"train: batch leaves span "
                          f"{place['batch_devices']} device(s), not "
                          f"{ctx.chips}")
    if ctx.platform == "tpu":
        # the CPU backend reports no memory statistics
        idle = [d for d, b in place["bytes_in_use"].items() if not b]
        if idle or len(place["bytes_in_use"]) != ctx.chips:
            raise PhaseFailed(f"train: devices holding no memory: {idle} "
                              f"of {place['bytes_in_use']}")
    info1, loss1 = run_train(ctx, "chips1", solo_env(ctx.env))
    if info1["device_count"] != 1:
        raise PhaseFailed(f"train: the one-chip child saw "
                          f"{info1['device_count']} devices")
    for step, (a, b) in enumerate(zip(loss4, loss1), 1):
        rel = abs(a - b) / max(abs(b), 1e-6)
        log(f"step {step}: loss {a:.6f} on {ctx.chips} chips, {b:.6f} on "
            f"one = {rel:.5f} apart (tolerance {LOSS_TOL})")
        if rel > LOSS_TOL:
            raise PhaseFailed(f"train: step {step} losses {a} vs {b} "
                              f"differ by {rel:.4f}, tolerance {LOSS_TOL}")
    return info4


Phase = Tuple[str, Callable[[Context], Optional[dict]]]

ONE_CHIP_PHASES: Tuple[Phase, ...] = (
    ("probe", phase_probe), ("data", write_data), ("train", phase_train),
    ("eval", phase_eval), ("serve", phase_serve))
FOUR_CHIP_PHASES: Tuple[Phase, ...] = (
    ("probe", phase_probe), ("data", write_data), ("train4", phase_train4))


def run_phases(ctx: Context, phases: Sequence[Phase]) -> int:
    """Run the phases one after another; 0 and the result line only if
    every one passed. Stops every process it started."""
    try:
        for name, fn in phases:
            before = cache_entries(ctx)
            t0 = time.monotonic()
            try:
                info = fn(ctx)
            except PhaseFailed as e:
                log(f"phase {name}: FAILED\n{e}")
                return 1
            log(f"phase {name}: ok {json.dumps(info or {})} — "
                f"{time.monotonic() - t0:.1f}s wall, set-up plus run "
                f"(not a performance number); compile cache "
                f"{ctx.env['JAX_COMPILATION_CACHE_DIR']} "
                f"{before} -> {cache_entries(ctx)} entries")
    finally:
        for proc in ctx.children:
            stop_child(proc)
    if ctx.device is None:
        log("no phase reported the device")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": ctx.device["platform"], "kind": ctx.device["kind"],
        "count": ctx.device["count"]}}), flush=True)
    return 0


def make_context(work: str, logs: str, sizes: Sizes = FULL,
                 platform: str = "tpu", chips: int = 1,
                 seed: int = 0) -> Context:
    for d in (work, logs):
        shutil.rmtree(d, ignore_errors=True)
        os.makedirs(d)
    env = dict(os.environ)
    env["DEXIRAFT_DATA_DIR"] = osp.join(work, "data")
    env["PYTHONUNBUFFERED"] = "1"
    # all children share one compile cache: where the variable is set it
    # is placed from outside, else the checkout's fixed path
    # (profiling.DEFAULT_CACHE_DIR)
    env.setdefault("JAX_COMPILATION_CACHE_DIR", osp.join(REPO, ".jax_cache"))
    return Context(work=work, logs=logs, sizes=sizes, platform=platform,
                   chips=chips, seed=seed, env=env)


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the data-parallel train phase on four chips "
                         "and its one-chip comparison, nothing else")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not osp.isdir(osp.join(REPO, "dexiraft_tpu")):
        print(f"chip_smoke: no dexiraft_tpu package beside {__file__}; "
              "run it from a checkout", file=sys.stderr)
        return 2
    # both git-ignored; the work directory goes when the run passed
    work = osp.join(REPO, ".smoke_work")
    ctx = make_context(
        work, osp.join(REPO, "chiprun_out", f"smoke_chips{args.chips}"),
        FULL, "tpu", args.chips, args.seed)
    rc = run_phases(ctx, ONE_CHIP_PHASES if args.chips == 1
                    else FOUR_CHIP_PHASES)
    if rc == 0:
        shutil.rmtree(work, ignore_errors=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
