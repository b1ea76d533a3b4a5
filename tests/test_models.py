"""Model parity tests.

The strongest cheap parity signal: exact parameter-count matches against
the reference (counted from /root/reference logs line 2 and verified by
instantiating the torch modules — see BASELINE.md):

  v1 vanilla RAFT (full)           5,257,536
  v2 early fusion 6-ch             5,276,352
  v4 early fusion 10-ch + DexiNed  40,483,149
  v5 dual stream + DexiNed         42,600,909
  raft-small (v1 small)              990,162
  DexiNed alone                    35,181,709
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _models import init_module, init_raft, jit_apply, raft_shapes
from dexiraft_tpu.config import raft_v1, raft_v2, raft_v3, raft_v4, raft_v5
from dexiraft_tpu.models import DexiNed, RAFT


def n_params(tree):
    return sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(tree))


@pytest.mark.parametrize(
    "cfg,expected",
    [
        (raft_v1(), 5_257_536),
        (raft_v2(), 5_276_352),
        (raft_v4(), 40_483_149),
        (raft_v5(), 42_600_909),
        (raft_v1(small=True), 990_162),
    ],
    ids=["v1", "v2", "v4", "v5", "small"],
)
def test_param_count_parity(cfg, expected):
    variables = raft_shapes(cfg, with_edges=cfg.variant == "early" and not cfg.embed_dexined)
    assert n_params(variables["params"]) == expected


def test_param_count_v3_corrected_refineflow():
    # reference v3 counts 5,257,541 with its buggy 4->1 RefineFlow (5 params);
    # ours is corrected to 4->2 (10 params): 5,257,546.
    variables = raft_shapes(raft_v3(), with_edges=True)
    assert n_params(variables["params"]) == 5_257_546


def test_dexined_param_count_and_shapes():
    model = DexiNed()
    x = jnp.zeros((1, 64, 64, 3))
    variables = init_module(model, x)
    assert n_params(variables["params"]) == 35_181_709

    outs = jit_apply(model)(variables, x)
    assert len(outs) == 7  # 6 scales + fused (core/DexiNed/model.py:260-268)
    for o in outs:
        assert o.shape == (1, 64, 64, 1)


def test_dexined_cofusion_head():
    # the reference's defined-but-unused CoFusion (core/DexiNed/model.py:25-47)
    # is a live option here; its output is a per-pixel convex combination of
    # the 6 scale maps, so it must lie within their pointwise min/max.
    model = DexiNed(fusion="cofusion")
    x = jax.random.uniform(jax.random.PRNGKey(1), (1, 32, 32, 3))
    variables = init_module(model, x)
    outs = jit_apply(model)(variables, x)
    assert len(outs) == 7
    scales = jnp.concatenate(outs[:6], axis=-1)
    fused = outs[6][..., 0]
    assert bool(jnp.all(fused <= scales.max(axis=-1) + 1e-5))
    assert bool(jnp.all(fused >= scales.min(axis=-1) - 1e-5))


def test_conv_transpose_matches_torch_geometry():
    torch = pytest.importorskip("torch")
    import flax.linen as nn

    from dexiraft_tpu.models.dexined import _conv_transpose_torchlike

    for up_scale, pad in [(1, 0), (2, 1), (3, 3), (4, 7)]:
        k = 2**up_scale
        t = torch.nn.ConvTranspose2d(3, 3, k, stride=2, padding=pad)
        t_out = t(torch.zeros(1, 3, 10, 10)).shape[-2:]
        m = _conv_transpose_torchlike(3, k, pad, jnp.float32)
        x = jnp.zeros((1, 10, 10, 3))
        j_out = jax.eval_shape(
            lambda: m.apply(m.init(jax.random.PRNGKey(0), x), x)).shape[1:3]
        assert tuple(t_out) == tuple(j_out) == (20, 20)


def test_subpixel_conv_transpose_equivalent():
    # the phase-decomposed form is the SAME linear operator as
    # lax.conv_transpose — same params (tree and values), same outputs —
    # for every (kernel, padding) geometry DexiNed uses
    from dexiraft_tpu.models.dexined import _conv_transpose_torchlike

    for up_scale, pad in [(1, 0), (2, 1), (3, 3), (4, 7)]:
        k = 2**up_scale
        x = jax.random.normal(jax.random.PRNGKey(up_scale), (2, 9, 11, 5))
        ref = _conv_transpose_torchlike(4, k, pad, jnp.float32,
                                        name="ConvTranspose_0")
        sub = _conv_transpose_torchlike(4, k, pad, jnp.float32,
                                        impl="subpixel",
                                        name="ConvTranspose_0")
        v = init_module(ref, x)
        v2 = jax.eval_shape(sub.init, jax.random.PRNGKey(0), x)
        assert jax.tree_util.tree_structure(v) == jax.tree_util.tree_structure(v2)
        out_ref = jit_apply(ref)(v, x)
        out_sub = jit_apply(sub)(v, x)  # reference params through subpixel math
        assert out_ref.shape == out_sub.shape == (2, 18, 22, 4)
        np.testing.assert_allclose(np.asarray(out_ref), np.asarray(out_sub),
                                   rtol=1e-5, atol=1e-5)


def test_subpixel_conv_transpose_grad_equivalent():
    # the standalone DexiNed CLI trains through the upsamplers, so the
    # backward pass must agree between impls too
    from dexiraft_tpu.models.dexined import _conv_transpose_torchlike

    x = jax.random.normal(jax.random.PRNGKey(2), (1, 7, 9, 3))
    ref = _conv_transpose_torchlike(2, 4, 1, jnp.float32, name="ConvTranspose_0")
    sub = _conv_transpose_torchlike(2, 4, 1, jnp.float32, impl="subpixel",
                                    name="ConvTranspose_0")
    v = init_module(ref, x)

    def loss(model, variables, inp):
        return jnp.sum(jnp.sin(model.apply(variables, inp)))

    g_ref = jax.jit(jax.grad(lambda vv: loss(ref, vv, x)))(v)
    g_sub = jax.jit(jax.grad(lambda vv: loss(sub, vv, x)))(v)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6), g_ref, g_sub)


def test_dexined_upconv_impls_equivalent():
    # whole-model check incl. checkpoint interop: variables initialized by
    # the transpose impl drive the subpixel impl to the same 7 maps
    x = jax.random.uniform(jax.random.PRNGKey(1), (1, 48, 64, 3), maxval=255.0)
    m_t = DexiNed(upconv="transpose")
    m_s = DexiNed(upconv="subpixel")
    variables = init_module(m_t, x)
    out_t = jit_apply(m_t)(variables, x)
    out_s = jit_apply(m_s)(variables, x)
    for a, b in zip(out_t, out_s):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_forward_shapes_and_test_mode():
    cfg = raft_v1(small=True)
    model, variables = init_raft(cfg)
    img = jnp.ones((2, 64, 72, 3)) * 127.0

    forward = jit_apply(model)
    preds = forward(variables, img, img, iters=3)
    assert preds.shape == (3, 2, 64, 72, 2)

    flow_low, flow_up = forward(variables, img, img, iters=3, test_mode=True)
    assert flow_low.shape == (2, 8, 9, 2)
    assert flow_up.shape == (2, 64, 72, 2)
    # test-mode upsamples once after the scan; the train path upsamples
    # inside the compiled scan body — same math, different fusion, so
    # allow reassociation-level noise
    np.testing.assert_allclose(np.asarray(preds[-1]), np.asarray(flow_up),
                               rtol=1e-5, atol=1e-4)


def test_scan_unroll_identical():
    # unroll is an XLA pipelining knob: same params tree, same outputs
    cfg = raft_v1(small=True)
    model, variables = init_raft(cfg)
    model_u = RAFT(raft_v1(small=True, scan_unroll=4))
    img = jnp.asarray(np.random.RandomState(5).rand(1, 64, 64, 3) * 255.0)
    a = jit_apply(model)(variables, img, img, iters=6, test_mode=True)
    b = jit_apply(model_u)(variables, img, img, iters=6, test_mode=True)
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-6, atol=1e-6)


def test_forward_identical_images_small_flow():
    # identical frames => the model should keep flow near its zero init
    cfg = raft_v1(small=True)
    model, variables = init_raft(cfg)
    rng = np.random.RandomState(0)
    img = jnp.asarray(rng.rand(1, 64, 64, 3) * 255.0)
    preds = jit_apply(model)(variables, img, img, iters=4)
    assert np.isfinite(np.asarray(preds)).all()


def test_flow_init_warm_start_shifts_result():
    cfg = raft_v1(small=True)
    model, variables = init_raft(cfg)
    img = jnp.ones((1, 64, 64, 3)) * 100.0
    flow_init = jnp.ones((1, 8, 8, 2)) * 2.0
    forward = jit_apply(model)
    low0, _ = forward(variables, img, img, iters=1, test_mode=True)
    low1, _ = forward(variables, img, img, iters=1, flow_init=flow_init, test_mode=True)
    # warm start must move the starting coords (core/raft.py:165-166)
    assert float(jnp.abs(low1 - low0).max()) > 0.5


def test_dual_stream_jit_and_grad():
    cfg = raft_v5(small=True)
    model, variables = init_raft(cfg)
    img = jnp.ones((1, 64, 64, 3)) * 127.0

    preds = jit_apply(model)(variables, img, img, iters=2)
    assert preds.shape == (2, 1, 64, 64, 2)

    # gradients must NOT flow into the frozen DexiNed (no_grad contract)
    def loss(params):
        p = model.apply({"params": params, **{k: v for k, v in variables.items() if k != "params"}},
                        img, img, iters=2)
        return jnp.abs(p).sum()

    grads = jax.jit(jax.grad(loss))(variables["params"])
    dexi_grad = grads["dexined"] if "dexined" in grads else grads["DexiNed_0"]
    assert max(float(jnp.abs(g).max()) for g in jax.tree_util.tree_leaves(dexi_grad)) == 0.0
    fnet_grad = grads["fnet"]
    assert max(float(jnp.abs(g).max()) for g in jax.tree_util.tree_leaves(fnet_grad)) > 0.0


def test_mixed_precision_runs_bf16():
    cfg = raft_v1(small=True, mixed_precision=True)
    model, variables = init_raft(cfg)
    img = jnp.ones((1, 64, 64, 3)) * 127.0
    preds = jit_apply(model)(variables, img, img, iters=2)
    # predictions come back fp32 (corr + coords path stays fp32)
    assert preds.dtype == jnp.float32
    assert np.isfinite(np.asarray(preds)).all()


# ---- tests/_models.py: the one way the tests build a model ---------------


def test_init_raft_is_the_eager_init_as_one_program():
    """Same tree, shapes and dtypes as the eager `RAFT(cfg).init`, and
    every leaf the same numbers to the last few bits. Not bit for bit:
    as one program XLA fuses an initializer's normal draw with its scale,
    and the fused arithmetic rounds 35 of raft-small's 106 leaves
    differently, by at most 2 units in the last place (measured, PR 43)."""
    cfg = raft_v1(small=True)
    _, variables = init_raft(cfg, 32, 32)
    img = jnp.zeros((1, 32, 32, 3))
    # eager on purpose: the init the helper replaced is the subject
    eager = RAFT(cfg).init(jax.random.PRNGKey(0), img, img, iters=1)
    assert (jax.tree_util.tree_structure(variables)
            == jax.tree_util.tree_structure(eager))
    for got, want in zip(jax.tree.leaves(variables), jax.tree.leaves(eager)):
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_array_max_ulp(np.asarray(got), np.asarray(want),
                                        maxulp=4)


def test_init_raft_is_kept_for_the_process():
    cfg = raft_v1(small=True)
    first = init_raft(cfg, 32, 32)
    assert init_raft(raft_v1(small=True), 32, 32) is first
    assert first[0] == RAFT(cfg)


def test_raft_shapes_is_the_tree_of_init_raft():
    cfg = raft_v1(small=True)
    shapes = raft_shapes(cfg, 32, 32)
    _, variables = init_raft(cfg, 32, 32)
    assert (jax.tree.map(lambda x: (x.shape, x.dtype), shapes)
            == jax.tree.map(lambda x: (x.shape, x.dtype), variables))
    assert all(isinstance(x, jax.ShapeDtypeStruct)
               for x in jax.tree.leaves(shapes))


def test_jit_apply_takes_the_choice_of_program_as_static():
    model, variables = init_raft(raft_v1(small=True), 32, 32)
    forward = jit_apply(model)
    img = jnp.zeros((1, 32, 32, 3))
    shapes = jax.eval_shape(
        lambda: (forward(variables, img, img, iters=2),
                 forward(variables, img, img, iters=3, test_mode=True),
                 forward(variables, img, mode="encode")))
    assert shapes[0].shape == (2, 1, 32, 32, 2)
    assert [s.shape for s in shapes[1]] == [(1, 4, 4, 2), (1, 32, 32, 2)]
    assert set(shapes[2]) == {"fmap", "ctx"}
