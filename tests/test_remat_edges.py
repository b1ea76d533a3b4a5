"""Remat option and the edge-pair (v2/v3 data-edge) training path."""

import numpy as np
import pytest

from dexiraft_tpu.data.flow_io import write_flo


class TestRemat:
    @pytest.mark.parametrize("kwarg", ["remat", "remat_lookup"])
    def test_remat_matches_plain(self, kwarg):
        """Full-iteration remat AND the selective lookup remat (which
        drops the stored hat matrices) must both leave loss and every
        gradient leaf numerically identical to the plain path."""
        import jax
        import jax.numpy as jnp

        from _models import init_raft
        from dexiraft_tpu.config import raft_v1
        from dexiraft_tpu.models.raft import RAFT

        img = jax.random.uniform(jax.random.PRNGKey(1), (1, 64, 64, 3),
                                 jnp.float32, 0, 255)
        # the flag chooses the backward program, not the tree
        _, variables = init_raft(raft_v1(small=True))
        outs = {}
        for flag in (False, True):
            model = RAFT(raft_v1(small=True, **{kwarg: flag}))

            def loss(v):
                preds = model.apply(v, img, img, iters=3, train=False)
                return jnp.sum(preds ** 2)

            value, grads = jax.jit(jax.value_and_grad(loss))(variables)
            outs[flag] = (float(value), jax.tree.leaves(grads))
        np.testing.assert_allclose(outs[True][0], outs[False][0], rtol=1e-5)
        # recompute reorders fp32 fusions; conv biases directly followed
        # by InstanceNorm have a TRUE gradient of zero (the norm subtracts
        # the mean), so their computed grads are cancellation residue of
        # ~global-magnitude terms — tolerance must scale with the global
        # gradient magnitude, not the (near-zero) leaf's own
        gmax = max(float(np.abs(np.asarray(b)).max())
                   for b in outs[False][1])
        for a, b in zip(outs[True][1], outs[False][1]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4 * gmax)


class TestFreezeBN:
    def test_freeze_bn_stops_stat_updates(self):
        """freeze_bn=True (post-chairs stages, train.py:149-150) must run
        BN on running stats and leave them untouched."""
        import jax
        import jax.numpy as jnp

        from _models import init_raft, jit_apply
        from dexiraft_tpu.config import raft_v1

        # full model: cnet uses batch norm
        model, variables = init_raft(raft_v1())
        forward = jit_apply(model)
        img = jax.random.uniform(jax.random.PRNGKey(0), (1, 64, 64, 3),
                                 jnp.float32, 0, 255)
        stats0 = variables["batch_stats"]

        def run(freeze):
            _, mut = forward(
                variables, img, img, iters=1, train=True, freeze_bn=freeze,
                mutable=("batch_stats",))
            return mut["batch_stats"]

        frozen = run(True)
        for a, b in zip(jax.tree.leaves(stats0), jax.tree.leaves(frozen)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        live = run(False)
        changed = any(
            not np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(jax.tree.leaves(stats0), jax.tree.leaves(live)))
        assert changed, "train-mode BN must update running stats"


@pytest.fixture()
def chairs_with_edges(tmp_path, monkeypatch):
    import imageio.v2 as imageio

    root = tmp_path / "FlyingChairs_release"
    data = root / "data"
    edges = tmp_path / "edges"
    data.mkdir(parents=True)
    edges.mkdir(parents=True)
    rng = np.random.default_rng(0)
    for i in range(4):
        for suffix in ("img1", "img2"):
            img = rng.integers(0, 256, (96, 128, 3), dtype=np.uint8)
            imageio.imwrite(data / f"{i:05d}_{suffix}.ppm", img)
            imageio.imwrite(edges / f"{i:05d}_{suffix}.png", img)
        write_flo(data / f"{i:05d}_flow.flo",
                  rng.normal(size=(96, 128, 2)).astype(np.float32))
    (root / "chairs_split.txt").write_text("\n".join(["1"] * 4))
    monkeypatch.setenv("DEXIRAFT_DATA_DIR", str(tmp_path))
    return tmp_path, str(edges)


class TestEdgePairPath:
    def test_fetch_dataset_with_edge_root(self, chairs_with_edges):
        from dexiraft_tpu.data.datasets import fetch_dataset

        _, edge_root = chairs_with_edges
        ds = fetch_dataset("chairs", (64, 64), edge_root=edge_root)
        s = ds.sample(0, np.random.default_rng(0))
        assert s["edges1"].shape == (64, 64, 3)
        assert s["image1"].shape == (64, 64, 3)

    def test_v2_training_through_cli(self, chairs_with_edges, monkeypatch):
        from dexiraft_tpu.train_cli import main
        from dexiraft_tpu.train import checkpoint as ckpt

        tmp, edge_root = chairs_with_edges
        monkeypatch.chdir(tmp)
        main(["--name", "e", "--stage", "chairs", "--variant", "v2",
              "--small", "--num_steps", "2", "--batch_size", "2",
              "--image_size", "64", "64", "--iters", "2",
              "--num_workers", "1", "--edge_root", edge_root,
              "--output", str(tmp / "ck"), "--log_dir", str(tmp / "runs")])
        assert ckpt.latest_step(str(tmp / "ck" / "e")) == 2
