"""The kernels of the main path, compiled for the chip without the chip.

The TPU's compiler is installed here and compiles for a described
topology (`v5e:2x2`): what Mosaic refuses on the chip it refuses here, at
no chip time. Interpret-mode parity tests cannot see this — all three
kernel families passed them while none compiled. Each case is the kernel
alone at the shapes the v5 forward feeds it at 440x1024 (fmaps
55x128x256 at the eval cells' batch of 32, 4 levels, radius 4; at batch
1 the compiler keeps a whole level in VMEM and the kernel's row-block
copies are not DMAs), for every configuration
`--corr_impl auto` can resolve to on a TPU (flash, fused, at each
`--corr_dtype`) and the unfused flash lookup. A compile that passes is
not a chip run: chip_smoke.py runs the same configuration on the chip
against allpairs.

All cases live in this one file: one process at a time may hold the
topology plug-in's lock. The three whole train steps at a cell's real
size (`v5-train-chairs`, `evabyte-train-bytes32k`, `lfm2-train-pack32k`)
are marked `slow` (2-5 minutes each beside five other workers, PR 43):
the cell itself compiles and runs that step on the chip in every PR's
check. What a cell cannot say (the count of Mosaic calls, the
temporaries' size) they still assert, so after a change to `ops/corr.py`,
`ops/pallas_window.py`, `models/raft.py`, `models/lm/` or `train/step.py`
run `pytest -m slow tests/test_chip_compile.py` by hand.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dexiraft_tpu.config import resolve_corr_impl
from dexiraft_tpu.ops import pallas_corr as pc

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or it logs under /tmp

B, H, W, C = 32, 55, 128, 256
LEVELS, RADIUS, FEAT = 4, 4, 256
DTYPES = {"fp32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}


@pytest.fixture(scope="module", autouse=True)
def full_optimisation():
    """This file's subject is the program the chip's compiler makes, so it
    compiles at the full level (tests/conftest.py sets the lowest for the
    rest of tier 1). The flag is read at each compile, and every case
    here compiles anew."""
    was = jax.config.read("jax_disable_most_optimizations")
    jax.config.update("jax_disable_most_optimizations", False)
    yield
    jax.config.update("jax_disable_most_optimizations", was)


@pytest.fixture(scope="module")
def topo():
    """The described v5e:2x2; skipped where it cannot be described. The
    persistent compilation cache is off around these compiles: an entry
    written for a described chip cannot be read back without one, and the
    next run would warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or the lock is held elsewhere
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    """Sharding on one described v5e chip."""
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _shapes(chip, dtype, h=H, w=W, b=B):
    """The kernel's operands as `build_local_corr(kernel="flash")` hands
    them over: queries and levels already padded (`pad_flash_operands`),
    `level_shapes` the levels' true extents."""
    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    raw = tuple(sds((b, h >> i, w >> i, C), DTYPES[dtype])
                for i in range(LEVELS))
    f1, levels = jax.eval_shape(pc.pad_flash_operands, sds((b, h, w, C)), raw)
    win2 = (2 * RADIUS + 1) ** 2
    return dict(f1=sds(f1.shape), coords=sds((b, h, w, 2)),
                levels=tuple(sds(lv.shape, lv.dtype) for lv in levels),
                level_shapes=tuple(lv.shape[1:3] for lv in raw),
                weight=sds((LEVELS * win2, FEAT)), bias=sds((FEAT,)))


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_auto_on_tpu_names_what_is_compiled_here():
    assert resolve_corr_impl("auto", "tpu") == ("flash", True)


def test_this_file_compiles_at_the_full_level():
    """What the cases below pin is the optimised program for the chip."""
    assert jax.config.read("jax_disable_most_optimizations") is False


@pytest.mark.parametrize("dtype,hw", [("fp32", (H, W)), ("bf16", (H, W)),
                                      ("int8", (H, W)),
                                      ("fp32", (136, 240))])
def test_flash_fused_step_compiles_for_v5e(chip, dtype, hw):
    """What `auto` serves: one kernel per refinement iteration. The two
    row-block slots with their semaphore pair, the zero-filled x axis
    and the taps are what Mosaic has to accept and what has to fit under
    its default scoped limit: 1088x1920 (136x240: slots of 240 x 8 x 256,
    9.66 MiB of scratch) is the largest any entry point reaches. A bf16 or
    int8 level is copied and read in 8-row blocks like an fp32 one."""
    s = _shapes(chip, dtype, *hw)
    text = _compiled_text(
        lambda f1, lv, co, w, b: pc.flash_fused_step(
            f1, lv, co, w, b, RADIUS, s["level_shapes"], False),
        s["f1"], s["levels"], s["coords"], s["weight"], s["bias"])
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype,level", [("fp32", 0), ("int8", 0),
                                         ("fp32", 3), ("bf16", 3)])
def test_flash_lookup_compiles_for_v5e(chip, dtype, level):
    """corr_impl="flash" without fused_update, at the widest level and
    at the narrowest (16 columns as they are: no lane pad)."""
    s = _shapes(chip, dtype)
    text = _compiled_text(
        lambda f1, f2, co: pc.flash_local_corr_level(
            f1, f2, co, RADIUS, s["level_shapes"][level], False),
        s["f1"], s["levels"][level], s["coords"])
    assert "tpu_custom_call" in text


# ---- the eval loop around the kernel (models/raft.py RAFTStep) ------------

@pytest.fixture(scope="module", params=["v1", "v5"])
def eval_loop(chip, request):
    """An eval cell's forward (flash + fused, 440x1024, 32 iterations,
    bf16, the cell's batch of 32) compiled for the described chip, 20 s
    (v1) and 55 s (v5): the batch its loop carries (v5's two streams
    ride one batch of 64) and the instructions of its `while` body as
    (name, result type, opcode)."""
    import os.path as osp
    import sys

    sys.path.insert(0, osp.dirname(osp.dirname(osp.abspath(__file__))))
    from benchmarks import harness
    from _models import raft_shapes
    from dexiraft_tpu.train.step import make_eval_step

    cell = harness.load_cell(f"{request.param}-eval-sintel")
    tr = cell.traffic
    cfg = harness.build_config(cell.config, tr["model_flags"], "tpu")
    variables = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        raft_shapes(cfg))
    image = jax.ShapeDtypeStruct((tr["batch"], 440, 1024, 3), np.float32,
                                 sharding=chip)
    text = make_eval_step(cfg, iters=tr["iters"]).lower(
        variables, image, image).compile().as_text()
    body, = re.findall(r" while\(.*?body=%?([\w.\-]+)", text)
    block = text.split(f"\n%{body} (")[1].split("\n}\n")[0]
    loop = re.findall(r"^\s*(?:ROOT )?%([\w.\-]+) = (\S+) ([\w\-]+)\(", block,
                      re.M)
    assert len(loop) > 100 and any(
        op == "custom-call" and name.startswith("flash_fused_step")
        for name, _, op in loop)
    return tr["batch"] * (2 if cfg.has_edge_stream else 1), loop


def _elements(kind):
    return int(np.prod([int(d) for d in
                        re.search(r"\[([\d,]+)\]", kind).group(1).split(",")]))


def test_eval_loop_pads_no_kernel_operand_for_v5e(eval_loop):
    """The kernel's operands are padded where the pyramid is built, once
    a pair: the loop's only `pad` is the coordinates' own (`f32[32,2,
    7168]` in v1). The parent's v1 program fails this on `pad.458
    f32[32,7168,256]` (the queries), `pad.460 f32[32,56,128,256]` and
    `pad.461`-`pad.463` (the levels), 32 times a batch: XLA does not move
    a loop-invariant pad out of a loop, because it grows its operand."""
    nb, loop = eval_loop
    pads = [f"{name} {kind}" for name, kind, op in loop if op == "pad"]
    large = [p for p in pads
             if _elements(p) >= nb * 8 * 128 * C]  # the smallest level, padded
    assert pads and not large, pads


def test_eval_loop_keeps_the_queries_on_the_lanes_for_v5e(eval_loop):
    """No instruction of the loop holds an array of B x 55 x 128 or more
    positions whose lane dimension is the 2 coordinate components, but a
    convolution's own operand: the 7x7 flow convolution reads
    `bf16[B,55,128,2]{3,0,2,1}`, which one `copy` makes from the planes
    (and the memory-space moves of it). The parent's v1 program fails
    this on `copy.264 f32[32,55,128,2]{3,2,1,0}` and `pad.459
    f32[32,7168,2]` (the kernel's coordinate operand), `copy.265` and the
    carry itself, `f32[32,55,128,2]{3,0,2,1}` (64 times its size in
    tiles), and `subtract_convert_fusion.2`; v5's failed it again with
    the carry in planes, while the flow was taken against a concatenated
    grid (`copy.1203`, `copy.1204 f32[64,55,128,2]{3,0,2,1}`)."""
    nb, loop = eval_loop
    starved = set()
    for name, kind, op in loop:
        for dims, order in re.findall(r"\w+\[([\d,]+)\]\{([\d,]+)", kind):
            dims = [int(d) for d in dims.split(",")]
            if (dims[int(order.split(",")[0])] == 2
                    and np.prod(dims) >= nb * H * W * 2):
                starved.add(f"{name} {kind} {op}")
    own = {s for s in starved if re.search(  # the operand, made and moved
        rf"bf16\[{nb},55,128,2\]\{{3,0,2,1[^ ]* (copy|copy-done|slice-done|"
        r"custom-call)$", s)}
    assert not starved - own, sorted(starved - own)
    # the carry: the two planes of each pair, (8, 128) tiles over (h, w)
    assert any(kind.startswith(f"f32[{nb},55,128,2]{{2,1,3,0:T(8,128)")
               for _, kind, _ in loop)


def test_eval_loop_relays_nothing_of_the_kernel_for_v5e(eval_loop):
    """The whole `while` body read, not its ten longest: the kernel takes
    its levels as they are stored (x-major, `pad_flash_operands`), its
    queries and weights as the loop carries them, and its `(B, Np, F)`
    result goes to the motion encoder as it is. So the body's every
    `pad`, `transpose` and `copy` is one the hat-form kernel's loop had
    too (PR 38's program, read the same way): the coordinates' own pad,
    and the 7x7 flow convolution's operand made from the planes. A level
    relaid, a weight transposed or a result turned for its consumer
    inside the loop would show here."""
    nb, loop = eval_loop
    moved = {f"{kind.split('{')[0]} {op}" for _, kind, op in loop
             if op in ("pad", "transpose", "copy")}
    assert moved == {f"f32[{nb},2,7168] pad", f"bf16[{nb},55,128,2] copy",
                     "f32[55,128,2] copy"}, sorted(moved)
    # the levels reach the call in their stored form: x major, 8-row
    # blocks second-minor, no lane pad (level 3 is 16 columns, not 128)
    levels = [kind.split("{")[0] for _, kind, op in loop
              if op == "get-tuple-element" and kind.endswith("256]{3,2,1,0:T(8,128)}")
              and kind.startswith(f"f32[{nb},")]
    assert sorted(levels) == sorted(
        f"f32[{nb},{W >> i},{-(-(H >> i) // 8) * 8},{C}]"
        for i in range(LEVELS)), levels


def test_interpret_switch_is_an_error_on_a_tpu_backend(monkeypatch):
    """On a TPU backend the interpret switch is an error, not a mode."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("DEXIRAFT_PALLAS_INTERPRET", raising=False)
    assert pc._interpret_default() is False
    monkeypatch.setenv("DEXIRAFT_PALLAS_INTERPRET", "1")
    with pytest.raises(RuntimeError, match="DEXIRAFT_PALLAS_INTERPRET"):
        pc._interpret_default()


# ---- the language model's attention kernel (ops/lm_attention.py) ----------

LM_G, LM_HEADS, LM_S, LM_DQK, LM_DV = 16, 4, 8192, 192, 128


@pytest.mark.parametrize("which", ["forward", "dq", "dkv"])
def test_lm_attention_kernel_compiles_for_v5e(chip, which):
    """The three kernels of the packed-document attention alone, at
    `kanana2-train-pack8k`'s shapes: 4 rows x 4 heads, 8192 positions,
    widths 192 / 128, bf16. The backward's two calls are one function, so
    the `dq` and `dkv` cases each find their own kernel in its text."""
    from dexiraft_tpu.ops import lm_attention as la

    bq, bk = la.kernel_blocks(LM_S, LM_DQK, LM_DV)
    st = la._Static(LM_HEADS, LM_DQK ** -0.5, bq, bk, False)

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    q, k = sds((LM_G, LM_S, LM_DQK)), sds((LM_G, LM_S, LM_DQK))
    v = sds((LM_G, LM_S, LM_DV))
    seg = sds((LM_G // LM_HEADS, LM_S), jnp.int32)
    table = lambda s: la.block_table(s, bq, bk)  # noqa: E731
    if which == "forward":
        text = _compiled_text(
            lambda q, k, v, s: la._forward(st, q, k, v, s, table(s)),
            q, k, v, seg)
    else:
        text = _compiled_text(
            lambda q, k, v, s, o, lse, do: la._backward(
                st, q, k, v, s, table(s), o, lse, do),
            q, k, v, seg, v, sds((LM_G, LM_S), jnp.float32), v)
    name = {"forward": "lm_attention_fwd", "dq": "lm_attention_dq",
            "dkv": "lm_attention_dkv"}[which]
    assert "tpu_custom_call" in text and name in text


# `trinity-train-pack32k`: 1 row x 4 query heads over 1 key/value head,
# 32,768 positions, width 128; a sliding-window layer and a full one
TR_G, TR_KV, TR_S, TR_D, TR_WINDOW = 4, 1, 32768, 128, 2048


@pytest.mark.parametrize("window", [TR_WINDOW, None], ids=["window", "full"])
@pytest.mark.parametrize("which", ["forward", "dq", "dkv"])
def test_lm_attention_kernel_with_shared_heads_compiles_for_v5e(chip, which,
                                                                window):
    """The same three kernels at the second cell's shapes: the step's 4
    query heads read one K/V block, `dk`/`dv` sum over them in scratch,
    and the table carries the window's bound."""
    from dexiraft_tpu.ops import lm_attention as la

    bq, bk = la.kernel_blocks(TR_S, TR_D, TR_D)
    st = la._Static(TR_G, TR_D ** -0.5, bq, bk, False, TR_KV, window)
    assert (st.hb, st.rep, st.hkv) == (4, 4, 1)

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    q, kv = sds((TR_G, TR_S, TR_D)), sds((TR_KV, TR_S, TR_D))
    seg = sds((1, TR_S), jnp.int32)
    table = lambda s: la.block_table(s, bq, bk, window)  # noqa: E731
    if which == "forward":
        text = _compiled_text(
            lambda q, k, v, s: la._forward(st, q, k, v, s, table(s)),
            q, kv, kv, seg)
    else:
        text = _compiled_text(
            lambda q, k, v, s, o, lse, do: la._backward(
                st, q, k, v, s, table(s), o, lse, do),
            q, kv, kv, seg, q, sds((TR_G, TR_S), jnp.float32), q)
    name = {"forward": "lm_attention_fwd", "dq": "lm_attention_dq",
            "dkv": "lm_attention_dkv"}[which]
    assert "tpu_custom_call" in text and name in text


# `smallthinker-train-pack16k`: 1 row x 7 query heads over 1 key/value
# head, 16,384 positions, width 128; a sliding-window layer and the full one
ST_G, ST_KV, ST_S, ST_D, ST_WINDOW = 7, 1, 16384, 128, 4096


@pytest.mark.parametrize("window", [ST_WINDOW, None], ids=["window", "full"])
@pytest.mark.parametrize("which", ["forward", "dq", "dkv"])
def test_lm_attention_kernel_with_a_group_of_seven_compiles_for_v5e(
        chip, which, window):
    """The same three kernels at the fifth cell's shapes: a grid step
    holds the whole group of 7 query heads on one K/V block (7 x 512 x 128
    blocks of q, o, do and their fp32 statistics inside the VMEM limit),
    `dk`/`dv` sum over the 7 in scratch."""
    from dexiraft_tpu.ops import lm_attention as la

    bq, bk = la.kernel_blocks(ST_S, ST_D, ST_D)
    st = la._Static(ST_G, ST_D ** -0.5, bq, bk, False, ST_KV, window)
    assert (st.hb, st.rep, st.hkv) == (7, 7, 1)

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    q, kv = sds((ST_G, ST_S, ST_D)), sds((ST_KV, ST_S, ST_D))
    seg = sds((1, ST_S), jnp.int32)
    table = lambda s: la.block_table(s, bq, bk, window)  # noqa: E731
    if which == "forward":
        text = _compiled_text(
            lambda q, k, v, s: la._forward(st, q, k, v, s, table(s)),
            q, kv, kv, seg)
    else:
        text = _compiled_text(
            lambda q, k, v, s, o, lse, do: la._backward(
                st, q, k, v, s, table(s), o, lse, do),
            q, kv, kv, seg, q, sds((ST_G, ST_S), jnp.float32), q)
    name = {"forward": "lm_attention_fwd", "dq": "lm_attention_dq",
            "dkv": "lm_attention_dkv"}[which]
    assert "tpu_custom_call" in text and name in text


# `evabyte-train-bytes32k`: 1 row x 8 heads of 128, 32,768 positions in
# windows of 2,048 and chunks of 16
EVA_HEADS, EVA_S, EVA_D, EVA_WINDOW, EVA_CHUNK = 8, 32768, 128, 2048, 16


def _eva_on_the_chip(q, k, v, seg, *, scale, block, window=None,
                     return_lse=False):
    """`document_attention` as it resolves on a TPU; the backend here is
    the CPU."""
    from dexiraft_tpu.ops import lm_attention as la

    return la.flash_document_attention(q, k, v, seg, scale=scale,
                                       window=window, return_lse=return_lse)


def test_eva_mixer_compiles_for_v5e(chip, monkeypatch):
    """EVA's mixer at the third language cell's shapes, forward and the
    gradients of q, k, v, phi and mu: the exact part on the three kernels
    (the forward's log-sum-exp an output, its cotangent folded into the
    backward's row sums), the pooling, the fifteen prefix products over
    summaries and the merge as XLA gives them."""
    from dexiraft_tpu.ops import lm_eva

    monkeypatch.setattr(lm_eva, "document_attention", _eva_on_the_chip)

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    row = sds((1, EVA_S, EVA_HEADS, EVA_D))
    vec = sds((EVA_HEADS, EVA_D))

    def loss(q, k, v, phi, mu, seg):
        out = lm_eva.eva_attention(
            q, k, v, phi, mu, seg, window=EVA_WINDOW, chunk=EVA_CHUNK,
            scale=EVA_D ** -0.5, block=1024)
        return jnp.sum(out.astype(jnp.float32))

    text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)),
                          row, row, row, vec, vec,
                          sds((1, EVA_S), jnp.int32))
    for name in ("lm_attention_fwd", "lm_attention_dq", "lm_attention_dkv"):
        assert name in text
    # no summary of 2,048 chunks against every one of 32,768 queries: the
    # prefixes are static, the longest 1,920
    assert f"{EVA_S},{EVA_S // EVA_CHUNK}]" not in text
    assert f",{EVA_WINDOW},1920]" in text


@pytest.mark.slow
def test_evabyte_step_compiles_for_v5e_and_fits_the_chip(chip, monkeypatch):
    """The third language cell's whole train step at its real size (4
    layers, 8 of 32 heads, the SwiGLU whole, 620 M parameters, one row of
    32,768 bytes, bf16, every layer recomputed) with the kernel path it
    takes on the chip: `benchmarks/compile_check.py` compiles this cell
    on the XLA path, its stand-in being the other mixers' entry point.
    Arguments (masters and AdamW's moments) and temporaries fit 15.75 GB."""
    import os.path as osp
    import sys

    import numpy as np

    sys.path.insert(0, osp.dirname(osp.dirname(osp.abspath(__file__))))
    from benchmarks import harness
    from dexiraft_tpu.ops import lm_eva
    from dexiraft_tpu.parallel import layout
    from dexiraft_tpu.train.state import create_state
    from dexiraft_tpu.train.step import make_train_step

    cell = harness.load_cell("evabyte-train-bytes32k")
    cfg, tc = harness.load_runner("lm_train_packed")._configs(cell, 0)
    devices = list(chip.device_set)
    mesh = layout.make_train_mesh(tc.batch_size, devices=devices)
    repl = layout.replicated_sharding(mesh)
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=repl),
        jax.eval_shape(lambda: create_state(jax.random.PRNGKey(0), cfg, tc)))
    batch = {k: jax.ShapeDtypeStruct(
        (tc.batch_size, cfg.seq_len), np.int32,
        sharding=layout.batch_input_sharding(mesh))
        for k in ("tokens", "positions", "segment_ids")}

    monkeypatch.setattr(lm_eva, "document_attention", _eva_on_the_chip)
    with mesh:
        compiled = make_train_step(cfg, tc, mesh=mesh).lower(
            state, batch).compile()
    text = compiled.as_text()
    for name in ("lm_attention_fwd", "lm_attention_dq", "lm_attention_dkv"):
        assert name in text
    memory = compiled.memory_analysis()
    # fp32 masters and AdamW's two moments: 12 B a parameter
    assert memory.argument_size_in_bytes > 12 * 620_015_616
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 15.75 * 2 ** 30)


# `lfm2-train-pack32k`: 1 row x 8 query heads over 2 key/value heads,
# 32,768 positions, width 64: the narrowest heads the kernel runs, and
# two whole groups of four a row
LF_H, LF_KV, LF_S, LF_D = 8, 2, 32768, 64


@pytest.mark.parametrize("which", ["forward", "dq", "dkv"])
def test_lm_attention_kernel_at_heads_of_64_compiles_for_v5e(chip, which):
    """The three kernels at the fourth cell's shapes: blocks whose last
    dimension is the whole 64-wide head, a step of 4 query heads on one
    K/V block, two steps a row."""
    from dexiraft_tpu.ops import lm_attention as la

    bq, bk = la.kernel_blocks(LF_S, LF_D, LF_D)
    st = la._Static(LF_H, LF_D ** -0.5, bq, bk, False, LF_KV, None)
    assert (st.hb, st.rep, st.hkv, st.heads // st.hb) == (4, 4, 1, 2)

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    q, kv = sds((LF_H, LF_S, LF_D)), sds((LF_KV, LF_S, LF_D))
    seg = sds((1, LF_S), jnp.int32)
    table = lambda s: la.block_table(s, bq, bk)  # noqa: E731
    if which == "forward":
        text = _compiled_text(
            lambda q, k, v, s: la._forward(st, q, k, v, s, table(s)),
            q, kv, kv, seg)
    else:
        text = _compiled_text(
            lambda q, k, v, s, o, lse, do: la._backward(
                st, q, k, v, s, table(s), o, lse, do),
            q, kv, kv, seg, q, sds((LF_H, LF_S), jnp.float32), q)
    name = {"forward": "lm_attention_fwd", "dq": "lm_attention_dq",
            "dkv": "lm_attention_dkv"}[which]
    assert "tpu_custom_call" in text and name in text


# (tokens, hidden, rows of a dispatch chunk, experts held) of a step
ROWS_CELLS = {"kanana2": (32768, 2048, 32768, 16),
              "trinity": (32768, 2048, 49152, 16),
              "lfm2": (32768, 2048, 49152, 8),
              "smallthinker": (16384, 2560, 32768, 16)}


@pytest.mark.parametrize("weighted", [True, False],
                         ids=["weighted_fp32", "plain_bf16"])
@pytest.mark.parametrize("cell", ROWS_CELLS)
def test_rows_segment_sum_compiles_for_v5e(chip, cell, weighted):
    """The expert layer's segment sum (ops/rows.py) at the four sparse
    cells' real shapes, as combine's forward calls it (bf16 rows, fp32
    weights, an fp32 sum) and as dispatch's backward does (bf16 in and
    out). The call asks for no VMEM limit, so Mosaic holds it to the one
    a kernel is given unasked."""
    from dexiraft_tpu.ops import rows

    tokens, hidden, chunk, held = ROWS_CELLS[cell]

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    args = [sds((chunk, hidden), jnp.bfloat16), sds((chunk,), jnp.int32),
            sds((held, tokens // rows.block_tokens(tokens) + 1), jnp.int32)]
    if weighted:
        args.append(sds((chunk,), jnp.float32))
    text = _compiled_text(
        lambda r, t, lo, w=None: rows.kernel_segment_sum(
            r, t, lo, tokens, w, jnp.float32 if weighted else jnp.bfloat16),
        *args)
    assert "tpu_custom_call" in text and "rows_segment_sum" in text


def _row_scatters_under_the_moves(text, tokens, hidden):
    """The compiled step's `scatter` instructions with a `[tokens,
    hidden]` result under `lm/moe/dispatch` or `lm/moe/combine`: what
    the expert layer's row moves were before ops/rows.py (16 of them in
    SmallThinker's step on the plain path, none on the kernel's)."""
    found = []
    for line in text.splitlines():
        if " scatter(" not in line or f"[{tokens},{hidden}]" not in (
                line.split(" scatter(")[0]):
            continue
        if "lm/moe/dispatch" in line or "lm/moe/combine" in line:
            found.append(line.strip()[:200])
    return found


def _step_on_the_chips_paths(cell_name, topo):
    """`_step_lowered`'s step, compiled."""
    return _step_lowered(cell_name, topo).compile()


def _step_lowered(cell_name, topo):
    """The cell's step as `benchmarks/compile_check.py` lowers it, the
    expert layer's row moves on the kernel too (`ops/rows.py` picks its
    path from the backend, which here is the CPU)."""
    import os.path as osp
    import sys
    from unittest import mock

    sys.path.insert(0, osp.dirname(osp.dirname(osp.abspath(__file__))))
    from benchmarks import harness
    from dexiraft_tpu.ops import rows

    cell = harness.load_cell(cell_name)
    lowered = []
    with mock.patch.object(rows, "_on_tpu", lambda: True):
        harness.load_runner("lm_train_packed").compile_for(
            cell, topo, lambda label, program: lowered.append(program))
    return lowered[0]  # the step; the check's program is
    #                    compile_check.py's to compile


@pytest.mark.slow
def test_lfm2_step_compiles_for_v5e_and_fits_the_chip(topo):
    """The fourth language cell's whole train step at its real size (5
    layers c f c c c, 8 of 32 experts and heads, 500 M parameters with a
    tied head, one row of 32,768 positions, bf16, every layer recomputed)
    as `benchmarks/compile_check.py` lowers it, the attention layer on
    the kernel path it takes on the chip and the expert layers' rows
    added back by `rows_segment_sum`, no `[T, D]` scatter left under
    dispatch or combine. Arguments (masters and AdamW's moments) and
    temporaries fit 15.75 GB."""
    compiled = _step_on_the_chips_paths("lfm2-train-pack32k", topo)
    text = compiled.as_text()
    for name in ("lm_attention_fwd", "lm_attention_dq", "lm_attention_dkv",
                 "rows_segment_sum"):
        assert name in text
    assert not _row_scatters_under_the_moves(text, 32768, 2048)
    for scope in ("lm/conv/in", "lm/conv/gate", "lm/conv/out",
                  "lm/gqa/full/kernel", "lm/moe/experts"):
        assert scope in text, scope
    assert "lm/moe/shared" not in text
    memory = compiled.memory_analysis()
    # fp32 masters and AdamW's two moments: 12 B a parameter
    assert memory.argument_size_in_bytes > 12 * 499_955_840
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 15.75 * 2 ** 30)


@pytest.mark.slow
def test_smallthinker_step_compiles_for_v5e_and_fits_the_chip(topo):
    """The fifth language cell's whole train step at its real size (4
    layers f s s s, 16 of 64 experts, 7 of 28 query heads on 1 key/value
    head, 594 M parameters, one row of 16,384 positions, bf16, every
    layer recomputed) as `benchmarks/compile_check.py` lowers it, the
    attention on the kernel path it takes on the chip and the expert
    layers' rows added back by `rows_segment_sum`, no `[T, D]` scatter
    left under dispatch or combine (the router's `take_along_axis` and
    `weights[order]` keep their small ones). The routing opens ahead of
    attention under the same scopes; nothing is built for a shared
    expert or a dense layer. Arguments and temporaries fit 15.75 GB."""
    compiled = _step_on_the_chips_paths("smallthinker-train-pack16k", topo)
    text = compiled.as_text()
    for name in ("lm_attention_fwd", "lm_attention_dq", "lm_attention_dkv",
                 "rows_segment_sum"):
        assert name in text
    assert not _row_scatters_under_the_moves(text, 16384, 2560)
    for scope in ("lm/gqa/window/kernel", "lm/gqa/full/kernel",
                  "lm/moe/router", "lm/moe/dispatch", "lm/moe/experts",
                  "lm/moe/combine"):
        assert scope in text, scope
    assert "lm/moe/shared" not in text and "lm/mlp" not in text
    memory = compiled.memory_analysis()
    # fp32 masters and AdamW's two moments: 12 B a parameter
    assert memory.argument_size_in_bytes > 12 * 593_615_360
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 15.75 * 2 ** 30)


@pytest.mark.slow
def test_nemotron_step_compiles_for_v5e_and_fits_the_chip(topo):
    """The sixth language cell's whole train step at its real size (11
    layers M E M E M E M E M * E, each one block; 16 of 128 Mamba heads,
    4 query heads on 1 key/value head, 8 of 512 experts top 22 in a
    latent space of 1,024, 672 shared columns; 508 M parameters, one row
    of 32,768 positions, bf16, every layer recomputed) as
    `benchmarks/compile_check.py` lowers it, the attention layer on the
    kernel path it takes on the chip and the expert layers' rows of 1,024
    added back by `rows_segment_sum`, no `[T, D]` scatter left under
    dispatch or combine at either width. The 43 overflow chunks keep no
    copy of the rows or of the experts' matrices (`[43, 32768, 1024]`,
    `[43, 8, 1024, 2688]`: 6.2 GB where each chunk's branch stood around
    its checkpoint). The routing is made once a layer: the lowered step
    holds 15 router products (a layer's forward one `[32768, 512]` and
    the two of its transpose), 5 top-k, 5 sorts of the 720,896 slots and
    5 gathers of their weights (20, 10, 10 and 10 while a layer's
    checkpoint kept nothing), for 72 MB kept. Arguments and
    temporaries fit 15.75 GB (6.10 + 7.84; 7.81 before the routing was
    kept)."""
    from _lm_common import routing_ops

    lowered = _step_lowered("nemotron3-train-pack32k", topo)
    assert routing_ops(lowered.as_text(), 32768, 512, 22) == dict(
        products=15, top_k=5, sorts=5, weight_gathers=5)
    compiled = lowered.compile()
    text = compiled.as_text()
    for name in ("lm_attention_fwd", "lm_attention_dq", "lm_attention_dkv",
                 "rows_segment_sum"):
        assert name in text
    assert not _row_scatters_under_the_moves(text, 32768, 1024)
    assert not _row_scatters_under_the_moves(text, 32768, 4096)
    for scope in ("lm/ssm/in", "lm/ssm/conv", "lm/ssm/scan",
                  "lm/ssm/gate_norm", "lm/ssm/out", "lm/gqa/full/kernel",
                  "lm/moe/latent", "lm/moe/router", "lm/moe/dispatch",
                  "lm/moe/experts", "lm/moe/shared", "lm/moe/combine"):
        assert scope in text, scope
    assert "lm/mlp" not in text and "lm/gqa/window" not in text
    assert "[43,32768,1024]" not in text and "[43,8,1024,2688]" not in text
    memory = compiled.memory_analysis()
    # fp32 masters and AdamW's two moments: 12 B a parameter
    assert memory.argument_size_in_bytes > 12 * 508_187_120
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 15.75 * 2 ** 30)
    print("nemotron step: temp", memory.temp_size_in_bytes / 1e9, "args",
          memory.argument_size_in_bytes / 1e9)


# ---- the convex upsample (ops/upsample.py) --------------------------------

def test_convex_upsample_is_lane_dense_for_v5e(chip):
    """The function with its gradient at `v5-train-chairs`' shapes (the
    image stream's batch of 8, 368x496 / 8, the mask in bf16): until PR 29
    its arrays were laid out as `f32[8,46,62,9,8,8]{3,5,4,2,1,0:T(8,128)}`,
    the 9 taps on the 128 lanes, and the compiler counted 1.40 GB of
    temporaries for a function whose largest array holds 52 MB. The limit
    is 1.5 x what the lane-dense form reads (0.062 GB)."""
    from dexiraft_tpu.ops.upsample import upsample_flow_convex

    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    def weighted(flow, mask, weight):
        return jnp.sum(upsample_flow_convex(flow, mask) * weight)

    compiled = jax.jit(jax.grad(weighted, (0, 1))).lower(
        sds((8, 46, 62, 2)), sds((8, 46, 62, 576), jnp.bfloat16),
        sds((8, 368, 496, 2))).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 0.093e9
    # no large array with the taps, a sub-pixel 8 or the 2 components as
    # its minor-most (lane) dimension: `[8,46,62,9,8,8]{3,...` and the like
    starved = set()
    for m in re.finditer(r"\w+\[([\d,]+)\]\{(\d+)[,:}]", compiled.as_text()):
        dims = [int(d) for d in m.group(1).split(",")]
        if len(dims) >= 4 and max(dims) >= 46 and dims[int(m.group(2))] in (2, 8, 9):
            starved.add(m.group(0))
    assert not starved, sorted(starved)


# ---- the all-pairs pyramid (ops/corr.py) ----------------------------------

_ITEM = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "s8": 1, "u8": 1, "pred": 1}


def _tiled_arrays(text):
    """Every array type in a compiled module as (printed type, dims, lane
    dimension, physical bytes / value bytes): the two minor-most
    dimensions of the layout fill (sublane, 128) tiles as `T(s,128)` says."""
    seen = {}
    for m in re.finditer(
            r"(\w+)\[([\d,]+)\]\{([\d,]+):T\((\d+),(\d+)\)[^}]*\}", text):
        dims = [int(d) for d in m.group(2).split(",")]
        order = [int(o) for o in m.group(3).split(",")]
        if m.group(1) not in _ITEM or len(order) < 2 or 0 in dims:
            continue
        lane, sub = dims[order[0]], dims[order[1]]
        ts, tl = int(m.group(4)), int(m.group(5))
        pad = (-(-lane // tl) * tl / lane) * (-(-sub // ts) * ts / sub)
        nbytes = _ITEM[m.group(1)]
        for d in dims:
            nbytes *= d
        seen[m.group(0)] = (dims, lane, pad, nbytes)
    return seen


def _twelve_lookups(f1, f2, coords, weight):
    """What the train step's scan does with `consts["pyr"]`: the build,
    twelve lookups under remat, a weighted sum; like the model's, the loop
    runs under `place_once`."""
    from dexiraft_tpu.ops.corr import (build_corr_pyramid, lookup_centres,
                                       place_once)

    def loop(pyr, probe, coords, weight):
        def body(shift, probe):
            at = coords + shift
            out = jax.checkpoint(lambda p, c, z: p(c, z))(pyr, at, probe)
            step = 0.01 * jax.lax.stop_gradient(jnp.mean(out))
            return shift + step, (jnp.sum(out * weight), lookup_centres(at))

        return jax.lax.scan(body, jnp.float32(0), probe, length=12)[1]

    pyr = build_corr_pyramid(f1, f2, LEVELS, RADIUS)
    return jnp.sum(place_once(loop, pyr, coords, weight, iters=12))


def _while_bodies(text):
    """Each `while` body of a compiled module as (the body's text, its
    instructions as (name, result type, opcode)), in the module's order."""
    out = []
    for body in re.findall(r" while\(.*?body=%?([\w.\-]+)", text):
        block = text.split(f"\n%{body} (")[1].split("\n}\n")[0]
        out.append((block, re.findall(
            r"^\s*(?:ROOT )?%([\w.\-]+) = (.+?) ([\w\-]+)\(", block, re.M)))
    return out


def _arrays_of(kind, dims):
    """The array types in a result type whose extents are ``dims`` in any
    order (a kernel takes a level as `[46,62,16,2852]`)."""
    return [m for m in re.findall(r"\w+\[([\d,]+)\]", kind)
            if sorted(int(d) for d in m.split(",") if int(d) > 1)
            == sorted(dims)]


def _backward_loop_holds_only_the_level(text, level0):
    """The backward `while` body (the one whose instructions are transposes
    of the forward's) holds no array of level 0's extent but the level
    itself, read from the loop's state and bitcast for the kernel: no
    gradient of it is written, read back or added inside the loop."""
    backward = [loop for block, loop in _while_bodies(text)
                if "transpose(jvp" in block]
    assert len(backward) == 1, len(backward)
    made = [f"{name} {kind} {op}" for name, kind, op in backward[0]
            if _arrays_of(kind, level0)
            and op not in ("parameter", "get-tuple-element", "bitcast",
                           "tuple")]  # the loop's state, in and out
    assert not made, made
    return backward[0]


@pytest.fixture
def lookup_on_the_chip(monkeypatch):
    """`corr_lookup` asks the backend, which is the CPU here: hand it the
    answer a TPU gives (the Pallas kernels, compiled)."""
    from dexiraft_tpu.ops import corr

    monkeypatch.setattr(corr, "_kernel_interpret", lambda: False)


def test_corr_pyramid_is_lane_dense_for_v5e(chip, lookup_on_the_chip):
    """`build_corr_pyramid`, twelve lookups under remat and the gradient
    with respect to the feature maps at `v5-train-chairs`' shapes (both
    streams' batch of 16, 368x496 / 8, 256 features): what the train
    step's scan does with `consts["pyr"]`. Until PR 32 a level was
    `f32[45632,46,62,1]` and the scan's carried gradient sum likewise
    (2.24 GB each for 0.69 GB of values), the lookup's hat products
    `bf16[45632,9,46]`; the compiler counted 4.17 GB of temporaries for
    this function. With the queries on the lanes every level, its
    gradient and the carried sum are within 1.15x of their values (read:
    1.03x, `f32[16,46,62,2852]{3,0,2,1:T(8,128)}`, the batch on the
    sublanes). Since PR 34 the lookup is the alignment kernels
    (ops/pallas_window.py), which take a level as `f32[46,62,16,2852]` in
    the default layout: the same bytes in the same order, so no copy of a
    level stands between the two. Since PR 42 the loop runs under
    `place_once`: the backward scan hands back twelve window cotangents a
    level (`f32[12,16,9,9,2852]` four times, 0.71 GB: what the carried sum
    was) and holds no level-sized array but the level; after it a level's
    gradient is two kernels on its stack as the loop left it, the second
    writing each block once. The temporaries read 1.58 GB (with the
    carried sum 1.51, with the dense hats 1.73). The limit is 1.25 x that
    reading."""
    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    b, h, w, d = 16, 46, 62, 256
    compiled = jax.jit(jax.grad(_twelve_lookups, (0, 1))).lower(
        sds((b, h, w, d)), sds((b, h, w, d)), sds((b, h, w, 2)),
        sds((b, h, w, LEVELS * (2 * RADIUS + 1) ** 2))).compile()
    text = compiled.as_text()
    arrays = _tiled_arrays(text)

    # no large array with a lone 1, the nine taps or an unpacked level
    # width on the lanes: `f32[45632,46,62,1]`, `bf16[45632,9,46]`, ...
    widths = {1, 2 * RADIUS + 1} | {w >> i for i in range(LEVELS)}
    starved = sorted(k for k, (dims, lane, pad, nbytes) in arrays.items()
                     if nbytes > 64e6 and lane in widths)
    assert not starved, starved
    level_dims = [[b, h >> i, w >> i, h * w] for i in range(LEVELS)]
    levels = {k: v for k, v in arrays.items() if v[0] in level_dims}
    assert {tuple(v[0]) for v in levels.values()} == {
        tuple(s) for s in level_dims}, sorted(levels)
    padded = {k: round(v[2], 2) for k, v in levels.items() if v[2] > 1.15}
    assert not padded, padded
    assert compiled.memory_analysis().temp_size_in_bytes < 1.25 * 1.58e9
    # the forward scan's x and y alignment of four levels, and after the
    # backward scan (whose forward is dead here: the lookup is linear in
    # the level) the stack placed along y and summed along x, a level each
    assert text.count("tpu_custom_call") == 16
    assert len(re.findall(r"%corr_window_place_sum[\w.]* = ", text)) == LEVELS
    loop = _backward_loop_holds_only_the_level(text, level_dims[0])
    assert not [name for name, _, op in loop if op == "custom-call"]
    # a level reaches its kernel as a bitcast: no copy the size of level 0
    copies = [m for m in re.findall(r"= f32\[([\d,]+)\]\S* copy\(", text)
              if sorted(int(d) for d in m.split(",")) == sorted(level_dims[0])]
    assert not copies, copies


@pytest.mark.parametrize("axis", [2, 1], ids=["x", "y"])
@pytest.mark.parametrize("level", [(46, 62), (5, 7)],
                         ids=["level0", "level3"])
def test_lookup_window_kernels_compile_for_v5e(chip, lookup_on_the_chip,
                                               level, axis):
    """The alignment kernel and its mirror image alone, at the chairs
    crop's level 0 `[16,46,62,2852]` and level 3 `[16,5,7,2852]` (a target
    axis shorter than the window: every stage of the shifter meets the
    zero fill), along x on the level and along y on the nine rows it
    leaves: two Mosaic calls a case, each within the default scoped VMEM."""
    from dexiraft_tpu.ops.corr import _axis_window

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=chip)

    hl, wl = level
    vol = (16, hl, wl, 2852) if axis == 2 else (16, hl, 2 * RADIUS + 1, 2852)
    out = list(vol)
    out[axis] = 2 * RADIUS + 1

    def weighted(vol, center, weight):
        return jnp.sum(_axis_window(vol, center, RADIUS, axis) * weight)

    text = jax.jit(jax.value_and_grad(weighted)).lower(
        sds(vol), sds((16, 2852)), sds(tuple(out))).compile().as_text()
    assert text.count("tpu_custom_call") == 2


def test_lookup_kernels_stay_on_their_chip_under_a_data_mesh(
        topo, lookup_on_the_chip):
    """`v5-train-chairs-dp4`'s share of the same function: a global batch
    of 64 (both streams of 32 pairs) over four chips. The partitioner
    cannot split a kernel; the call names its own axes (`_per_chip`), so
    every chip aligns its 16 rows and no level is gathered; the stack of
    window cotangents stays split by its batch with the iterations whole
    on every chip, so placing it moves nothing between chips."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding

    from dexiraft_tpu.parallel.layout import LAYOUT

    mesh = Mesh(np.array(topo.devices), (LAYOUT.data_axis,))
    data = NamedSharding(mesh, LAYOUT.batch())

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=data)

    b, h, w, d = 64, 46, 62, 256
    text = jax.jit(jax.grad(_twelve_lookups, (0, 1))).lower(
        sds((b, h, w, d)), sds((b, h, w, d)), sds((b, h, w, 2)),
        sds((b, h, w, LEVELS * (2 * RADIUS + 1) ** 2))).compile().as_text()
    assert text.count("tpu_custom_call") == 16
    assert "all-gather" not in text
    assert f"f32[{h},{w},16,{h * w}]" in text      # a chip's rows of level 0
    assert f"f32[{h},{w},64,{h * w}]" not in text
    assert f"f32[12,16,9,9,{h * w}]" in text       # and of a level's stack
    assert f"f32[12,64,9,9,{h * w}]" not in text
    # what crosses chips is the loop's scalar mean; nothing the size of a
    # level's window cotangent (16 x 81 x 2852), let alone of a stack or a
    # level
    moved = [line.strip()[:160] for line in text.splitlines() if re.search(
        r" (all-reduce|all-to-all|collective-permute|reduce-scatter)"
        r"(-start)?\(", line) and any(
            _elements(kind) >= 16 * 81 * h * w
            for kind in re.findall(r"\w+\[[\d,]+\]", line.split("(")[0]))]
    assert not moved, moved
    _backward_loop_holds_only_the_level(text, [16, h, w, h * w])


@pytest.mark.slow
def test_v5_train_step_compiles_with_the_lookup_kernels_and_fits_the_chip(
        topo, lookup_on_the_chip):
    """`v5-train-chairs`' whole step at its real size (batch 8, 368x496,
    12 iterations, bf16, every iteration recomputed, `corr_impl=allpairs`)
    with the lookup it takes on the chip; `benchmarks/compile_check.py`
    sees the CPU backend and compiles the plain form. Per level the x and
    the y alignment, forward and recomputed, and after the backward loop
    the stack placed along y and summed along x: 24 Mosaic calls, and no
    level-sized array in the backward loop but the levels. The compiler
    counts 9.41 GB of temporaries beside 0.62 GB of arguments (8.74
    with the gradient summed inside the loop, with the dense hats 8.94)."""
    import os.path as osp
    import sys

    import numpy as np

    sys.path.insert(0, osp.dirname(osp.dirname(osp.abspath(__file__))))
    from benchmarks import harness
    from dexiraft_tpu.parallel import layout
    from dexiraft_tpu.train.state import create_state
    from dexiraft_tpu.train.step import make_train_step

    cell = harness.load_cell("v5-train-chairs")
    runner = harness.load_runner(cell.traffic["kind"])
    cfg = harness.build_config(cell.config, cell.traffic["model_flags"], "tpu")
    tc = runner._train_config(cell.traffic, 0)
    mesh = layout.make_train_mesh(tc.batch_size, devices=topo.devices[:1])
    repl = layout.replicated_sharding(mesh)
    data = layout.batch_input_sharding(mesh)
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=repl),
        jax.eval_shape(lambda: create_state(jax.random.PRNGKey(0), cfg, tc)))
    (h, w), b = tc.image_size, tc.batch_size
    batch = {k: jax.ShapeDtypeStruct(shape, np.float32, sharding=data)
             for k, shape in {"image1": (b, h, w, 3), "image2": (b, h, w, 3),
                              "flow": (b, h, w, 2), "valid": (b, h, w)}.items()}
    with mesh:
        compiled = make_train_step(cfg, tc, mesh=mesh).lower(
            state, batch).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 24
    assert len(re.findall(r"%corr_window_place_sum[\w.]* = ", text)) == 4
    loop = _backward_loop_holds_only_the_level(text, [16, 46, 62, 46 * 62])
    assert sum(op == "custom-call" and name.startswith("corr_window_align")
               for name, _, op in loop) == 8
    assert not [name for name, _, _ in loop
                if name.startswith("corr_window_place")]
    memory = compiled.memory_analysis()
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 15.75 * 2 ** 30)
    assert memory.temp_size_in_bytes < 1.1 * 9.41e9
