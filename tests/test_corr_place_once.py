"""The levels' gradient placed once a loop (ops/corr.py `place_once`):
the wrapper against the loop as written, and RAFT's scanned refinement
with and without it. A file of its own so that xdist's `loadfile` hands
the tests that build a RAFT and the rest of tests/test_corr.py to
different workers.
"""

import numpy as np
import pytest

from _corr_reference import _probe_coords, build_corr_pyramid


def _scanned_lookups(pyr, probe, coords, weight, scale):
    """Three lookups under `jax.checkpoint` whose coordinates move with
    what the one before read, as a loop for `place_once`."""
    import jax
    import jax.numpy as jnp

    from dexiraft_tpu.ops.corr import lookup_centres

    def body(shift, probe):
        at = coords + shift
        out = jax.checkpoint(lambda p, c, z: p(c, z))(pyr, at, probe)
        step = 0.3 * jax.lax.stop_gradient(jnp.mean(out))
        return shift + step, (jnp.sum(out * weight) * scale,
                              lookup_centres(at))

    return jax.lax.scan(body, jnp.float32(0), probe, length=3)[1]


@pytest.mark.parametrize("path,corr_dtype", [
    ("plain", "fp32"), ("plain", "bf16"), ("kernel", "fp32")])
def test_place_once_matches_the_gradient_of_the_loop_as_written(
        path, corr_dtype, monkeypatch):
    """The wrapper against `jax.grad` of the same loop unwrapped (each
    backward iteration places its level's gradient whole and the scan sums
    them): both feature maps' gradients within 1e-5 (the order of an fp32
    sum over the iterations differs), the value and the other argument's
    gradient equal. A bf16 pyramid's gradient is summed in fp32 and cast
    once; the loop as written rounds every iteration's to bf16."""
    import jax
    import jax.numpy as jnp

    from dexiraft_tpu.ops import corr as corr_mod
    from dexiraft_tpu.ops.corr import place_once

    monkeypatch.setattr(corr_mod, "_kernel_interpret",
                        lambda: True if path == "kernel" else None)
    b, h, w, d = 2, 8, 10, 16
    rng = np.random.RandomState(3)
    f1 = jnp.asarray(rng.randn(b, h, w, d).astype(np.float32))
    f2 = jnp.asarray(rng.randn(b, h, w, d).astype(np.float32))
    coords = jnp.asarray(_probe_coords(rng, b, h, w))
    weight = jnp.asarray(rng.randn(b, h, w, 2 * 81).astype(np.float32))

    def loss(f1, f2, scale, wrapped):
        pyr = build_corr_pyramid(f1, f2, num_levels=2, radius=4,
                                 dtype=corr_dtype)
        if wrapped:
            out = place_once(_scanned_lookups, pyr, coords, weight, scale,
                             iters=3)
        else:
            out = _scanned_lookups(pyr, None, coords, weight, scale)[0]
        return jnp.sum(out)

    scale = jnp.float32(1.5)
    results = [jax.jit(jax.value_and_grad(
        lambda a, c, s: loss(a, c, s, wrapped), (0, 1, 2)))(f1, f2, scale)
        for wrapped in (True, False)]
    (value, grads), (want_value, want_grads) = results
    assert value == want_value
    assert grads[2] == want_grads[2] and abs(float(grads[2])) > 1.0
    tol = 1e-5 if corr_dtype == "fp32" else 2.0**-7
    for g, want in zip(grads[:2], want_grads[:2]):
        scale = np.abs(np.asarray(want)).max()
        assert scale > 0.1
        np.testing.assert_allclose(np.asarray(g), np.asarray(want), rtol=0,
                                   atol=tol * scale)
    # no gradient taken: the loop as written
    assert jax.jit(lambda a, c: loss(a, c, scale, True))(f1, f2) == \
        jax.jit(lambda a, c: loss(a, c, scale, False))(f1, f2)


def test_place_once_leaves_other_pyramids_to_the_loop():
    """An int8 pyramid has no tangent space and a pyramid of another type
    no levels to place: the loop runs as written, its probe None."""
    import jax.numpy as jnp

    from dexiraft_tpu.ops.corr import place_once

    seen = []

    def loop(pyr, probe, x):
        seen.append(probe)
        return 2.0 * x, None

    f = jnp.ones((1, 4, 4, 8), jnp.float32)
    for pyr in (build_corr_pyramid(f, f, num_levels=2, radius=2, dtype="int8"),
                {"levels": (f,)}):
        assert float(place_once(loop, pyr, jnp.float32(2.0), iters=3)) == 4.0
    assert seen == [None, None]


_REFINE_CASES = [("v1", {}), ("v1", {"remat": True}),
                 ("v3", {"small": True, "remat_lookup": True}),
                 ("v5", {}), ("v5", {"remat": True})]


@pytest.mark.parametrize("variant,flags", _REFINE_CASES, ids=[
    "-".join([v] + [f"{k}={f[k]}" for k in f]) for v, f in _REFINE_CASES])
def test_raft_refinement_places_the_levels_gradient_once(variant, flags,
                                                         monkeypatch):
    """RAFT's scanned refinement (mode="step": the pyramid build and the
    loop, from given features) in train mode, against the same model with
    `place_once` taken out, which is the path the scan took before: the
    levels differentiated inside the loop through `corr_lookup`'s own rule.
    The predictions are equal, the gradients of the parameters and of the
    features (the pyramid's operands) within fp32 rounding, and the
    wrapper is entered only on the train path: a test_mode trace never
    reaches it. v3 with the small update block stands for the variants no
    cell runs (v2 and v4 scan as v1 does)."""
    import jax
    import jax.numpy as jnp

    from dexiraft_tpu import config
    from dexiraft_tpu.models import raft as raft_mod

    cfg = getattr(config, f"raft_{variant}")(
        **({"embed_dexined": True} if variant == "v3" else {}), **flags)
    model = raft_mod.RAFT(cfg)
    b, h, w, iters = 2, 8, 10, 2
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 12))

    def features():
        f = {"fmap": jax.random.normal(next(keys), (b, h, w, cfg.fnet_dim)),
             "ctx": jax.random.normal(
                 next(keys), (b, h, w, cfg.hidden_dim + cfg.context_dim))}
        if cfg.has_edge_stream:
            f["efmap"] = jax.random.normal(next(keys), f["fmap"].shape)
            f["ectx"] = jax.random.normal(next(keys), f["ctx"].shape)
        return f

    f1, f2 = features(), features()
    target = jax.random.normal(next(keys), (iters, b, 8 * h, 8 * w, 2))
    variables = jax.jit(lambda: model.init(
        jax.random.PRNGKey(0), None, mode="step", features1=f1, features2=f2,
        iters=1))()

    def loss(variables, f1, f2):
        preds = model.apply(variables, None, mode="step", features1=f1,
                            features2=f2, iters=iters, train=True)
        return jnp.sum(jnp.abs(preds - target)) / (64 * h * w), preds

    entered = []
    real = raft_mod.place_once

    def counted(*args, **kwargs):
        entered.append(kwargs["iters"])
        return real(*args, **kwargs)

    grad = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)
    monkeypatch.setattr(raft_mod, "place_once", counted)
    (value, preds), grads = jax.jit(grad)(variables, f1, f2)
    assert entered == [iters]
    jax.eval_shape(lambda v: model.apply(
        v, None, mode="step", features1=f1, features2=f2, iters=iters,
        test_mode=True), variables)
    assert entered == [iters]

    monkeypatch.setattr(
        raft_mod, "place_once",
        lambda loop, pyr, *args, iters: loop(pyr, None, *args)[0])
    (want_value, want_preds), want_grads = jax.jit(grad)(variables, f1, f2)

    assert value == want_value
    np.testing.assert_array_equal(np.asarray(preds), np.asarray(want_preds))
    leaves, want_leaves = jax.tree.leaves(grads), jax.tree.leaves(want_grads)
    assert len(leaves) == len(want_leaves) > 10
    for tree, want_tree in zip(grads, want_grads):  # parameters, features
        top = max(float(jnp.abs(x).max()) for x in jax.tree.leaves(want_tree))
        assert top > 1e-4
        for got, want in zip(jax.tree.leaves(tree),
                             jax.tree.leaves(want_tree)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=0, atol=2e-5 * top)


def test_raft_train_path_wraps_every_kernel_call_on_a_data_mesh(monkeypatch):
    """`v5-train-chairs-dp4`'s condition at toy size: the model's gradient
    traced with a batch split over four devices. A kernel call reads its
    mesh from its operand's type, and the stack of window cotangents, the
    cotangent of zeros made where no mesh is in sight, carries none:
    `place_once` hands the level's mesh on. Every Pallas call of the traced
    gradient, the two a level after the loop among them, sits inside a
    `shard_map` (left bare, the chip's compiler refuses it)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding

    from dexiraft_tpu.config import raft_v1
    from dexiraft_tpu.models.raft import RAFT
    from dexiraft_tpu.ops import corr as corr_mod
    from dexiraft_tpu.parallel import layout

    devices = jax.devices()
    if len(devices) < 4:
        pytest.skip("needs 4 (virtual) devices")
    monkeypatch.setattr(corr_mod, "_kernel_interpret", lambda: True)
    cfg = raft_v1(small=True, remat=True)
    model = RAFT(cfg)
    b, h, w, iters = 4, 8, 10, 2
    mesh = Mesh(np.array(devices[:4]), (layout.LAYOUT.data_axis,))
    data = NamedSharding(mesh, layout.LAYOUT.batch())

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=data)

    f = {"fmap": sds(b, h, w, cfg.fnet_dim),
         "ctx": sds(b, h, w, cfg.hidden_dim + cfg.context_dim)}
    variables = jax.eval_shape(lambda f: model.init(
        jax.random.PRNGKey(0), None, mode="step", features1=f, features2=f,
        iters=1), jax.tree.map(lambda x: jnp.zeros(x.shape), f))
    variables = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=layout.replicated_sharding(mesh)),
        variables)

    def loss(variables, f1, f2):
        return jnp.sum(model.apply(variables, None, mode="step", features1=f1,
                                   features2=f2, iters=iters, train=True))

    calls = []

    def walk(jaxpr, wrapped):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                calls.append((eqn.params["name"], wrapped))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, wrapped or eqn.primitive.name == "shard_map")

    walk(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(variables, f, f).jaxpr,
         False)
    names = {name for name, _ in calls}
    assert {"corr_window_align", "corr_window_place",
            "corr_window_place_sum"} <= names, names
    bare = [name for name, wrapped in calls if not wrapped]
    assert not bare, bare
