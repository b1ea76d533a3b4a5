"""Halo compute-sharding tests: widths, exchange parity, step parity.

Named to sort LAST (tier-1 870 s budget convention, after test_zzzfsdp).
The cheap pins — the halo-width table, the support matrix's refusals,
the padder's seq alignment, the per-block gather schedule, and the
bit-level single-conv exchange parity — run in tier-1; the full
fence-vs-halo train/eval parity compiles two complete train steps and
is marked ``slow`` (the repo's declared category for multi-minute
full-model parity), shared through one module-scoped fixture.

What is pinned here and why:

  * ``halo_rows()`` — the per-module exchange widths, derived from the
    declarative conv chains next to the modules. A kernel-size change
    that forgets its exchange width fails THIS table, not a pod run.
  * ``halo_conv`` vs the unsharded conv, bit level — the non-circular
    ppermute zero-fill must be byte-identical to global symmetric zero
    padding, for stride-1 AND the stride-2 stem shape.
  * fence-vs-halo loss/param/eval parity — the halo step's whole claim
    is that the explicit shard_map program computes the SAME math as
    the replicated-compute fence step while rows shard over 'seq' and
    params stay fsdp-sharded through compute.
  * ``check_halo_support`` — every refusal in the v1 support matrix is
    a one-line actionable error, not a wrong answer downstream.
"""

from __future__ import annotations

import numpy as np
import pytest


# --------------------------------------------------------------------------
# halo arithmetic pins (pure — no compiles)
# --------------------------------------------------------------------------


class TestChainHalo:
    def test_single_conv_margins(self):
        from dexiraft_tpu.parallel.halo import chain_halo

        # (k, s, p): lo = p rows above, hi = max(0, k - s - p) below
        assert chain_halo(((3, 1, 1),)) == (1, 1)
        assert chain_halo(((7, 2, 3),)) == (3, 2)  # the encoder stem
        assert chain_halo(((1, 1, 0),)) == (0, 0)  # 1x1 never exchanges

    def test_chain_composition(self):
        from dexiraft_tpu.parallel.halo import chain_halo

        # two 3x3s stack linearly...
        assert chain_halo(((3, 1, 1), (3, 1, 1))) == (2, 2)
        # ...but a downstream margin m costs s*m rows through a
        # stride-s conv: stem (7,2,3) then 3x3 -> lo=3+2*1, hi=2+2*1
        assert chain_halo(((7, 2, 3), (3, 1, 1))) == (5, 4)


class TestHaloRowsTable:
    def test_pinned_widths(self):
        """THE table. Derived live from the conv chains declared next to
        the modules; these pins are what makes a silent kernel-size /
        stride / padding change a test failure instead of a wrong pod
        answer. Update BOTH the module's chain and this pin when a
        receptive field legitimately changes."""
        from dexiraft_tpu.parallel.halo import halo_rows

        assert halo_rows() == {
            "encoder_basic": 53,   # 7/2 stem + 3 residual stages
            "encoder_small": 25,   # bottleneck stages, fewer 3x3s
            "motion_encoder": 5,
            "gru_sep": 4,          # two passes of the 1x5/5x1 pair
            "gru_conv": 2,
            "flow_head": 2,
            "mask_head": 1,
            "upsample_convex": 1,  # 3x3 mask taps one coarse row over
            "upflow8": 1,          # bilinear hat support
        }

    def test_exchange_perms_are_non_circular(self):
        from dexiraft_tpu.parallel.layout import seq_halo_perms

        fwd, bwd = seq_halo_perms(4)
        # no (n-1, 0) / (0, n-1) wrap: the mesh-edge halos arrive
        # ZERO-filled, which is exactly the global conv's zero padding
        assert fwd == [(0, 1), (1, 2), (2, 3)]
        assert bwd == [(1, 0), (2, 1), (3, 2)]


# --------------------------------------------------------------------------
# bit-level exchange parity: one conv, sharded vs unsharded
# --------------------------------------------------------------------------


class TestHaloConvBitParity:
    def _run(self, kh: int, stride: int):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from dexiraft_tpu.parallel.halo import halo_conv, shard_map
        from dexiraft_tpu.parallel.layout import LAYOUT, make_mesh_2d

        mesh = make_mesh_2d(2, 4)  # rows split 4 ways
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
        x = jax.random.normal(k1, (2, 16, 8, 3), jnp.float32)
        kernel = jax.random.normal(k2, (kh, kh, 3, 4), jnp.float32)
        bias = jax.random.normal(k3, (4,), jnp.float32)
        p = kh // 2

        ref = jax.lax.conv_general_dilated(
            x, kernel, (stride, stride), ((p, p), (p, p)),
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + bias

        bsc = LAYOUT.batch_spatial_compute()
        fn = shard_map(
            lambda xl, kl, bl: halo_conv(xl, kl, bl, stride=stride,
                                         n_seq=4),
            mesh=mesh, in_specs=(bsc, P(), P()), out_specs=bsc)
        with mesh:
            got = fn(x, kernel, bias)
        return np.asarray(got), np.asarray(ref)

    def test_stride1_3x3(self):
        got, ref = self._run(kh=3, stride=1)
        # BIT parity: same convolution on the same rows — the exchange
        # moved bytes, it did not change the math
        assert np.array_equal(got, ref)

    def test_stride2_7x7_stem(self):
        # the encoder stem's (7, 2, 3): asymmetric lo=3 / hi=2 margins
        # and output rows that must land on the device owning them
        got, ref = self._run(kh=7, stride=2)
        assert np.array_equal(got, ref)


# --------------------------------------------------------------------------
# support matrix: every unsupported configuration refuses loudly
# --------------------------------------------------------------------------


def _ok_setup():
    from dexiraft_tpu.config import TrainConfig, raft_v1

    cfg = raft_v1(small=True)
    tc = TrainConfig(name="halo-test", stage="chairs", num_steps=20,
                     batch_size=4, image_size=(48, 64), iters=2)
    return cfg, tc


class TestSupportMatrix:
    @pytest.fixture()
    def mesh(self):
        from dexiraft_tpu.parallel.layout import make_mesh_fsdp

        return make_mesh_fsdp(2, 2, 2)

    def test_supported_config_passes(self, mesh):
        from dexiraft_tpu.parallel.halo import check_halo_support

        cfg, tc = _ok_setup()
        check_halo_support(cfg, tc, mesh)  # no raise

    def test_needs_seq_axis(self):
        from dexiraft_tpu.parallel.halo import check_halo_support
        from dexiraft_tpu.parallel.layout import make_mesh_fsdp

        cfg, tc = _ok_setup()
        with pytest.raises(ValueError, match="'seq' axis"):
            check_halo_support(cfg, tc, None)
        with pytest.raises(ValueError, match="'seq' axis"):
            check_halo_support(cfg, tc, make_mesh_fsdp(2, 2))

    def test_v1_variant_only(self, mesh):
        from dexiraft_tpu.config import raft_v5
        from dexiraft_tpu.parallel.halo import check_halo_support

        _, tc = _ok_setup()
        with pytest.raises(ValueError, match="variant='raft'"):
            check_halo_support(raft_v5(), tc, mesh)

    def test_fp32_allpairs_only(self, mesh):
        import dataclasses

        from dexiraft_tpu.config import raft_v1
        from dexiraft_tpu.parallel.halo import check_halo_support

        cfg, tc = _ok_setup()
        with pytest.raises(ValueError, match="allpairs"):
            check_halo_support(raft_v1(small=True, corr_impl="local"),
                               tc, mesh)
        with pytest.raises(ValueError, match="fp32"):
            check_halo_support(raft_v1(small=True, mixed_precision=True),
                               tc, mesh)
        with pytest.raises(ValueError, match="fp32"):
            check_halo_support(
                cfg, dataclasses.replace(tc, precision="bf16"), mesh)

    def test_train_mode_restrictions(self, mesh):
        import dataclasses

        from dexiraft_tpu.config import raft_v1
        from dexiraft_tpu.parallel.halo import check_halo_support

        cfg, tc = _ok_setup()
        with pytest.raises(ValueError, match="dropout"):
            check_halo_support(raft_v1(small=True, dropout=0.5), tc, mesh)
        with pytest.raises(ValueError, match="accum_steps=1"):
            check_halo_support(
                cfg, dataclasses.replace(tc, accum_steps=2), mesh)
        with pytest.raises(ValueError, match="freeze_bn"):
            # the FULL model trains BatchNorm; halo runs BN frozen only
            check_halo_support(raft_v1(), tc, mesh)
        check_halo_support(raft_v1(),
                           dataclasses.replace(tc, freeze_bn=True), mesh)

    def test_geometry_restrictions(self, mesh):
        import dataclasses

        from dexiraft_tpu.parallel.halo import check_halo_support

        cfg, tc = _ok_setup()
        with pytest.raises(ValueError, match="not divisible"):
            check_halo_support(
                cfg, dataclasses.replace(tc, batch_size=3), mesh)
        with pytest.raises(ValueError, match="divisible by 8"):
            check_halo_support(
                cfg, dataclasses.replace(tc, image_size=(40, 64)), mesh)
        with pytest.raises(ValueError, match=">= 3"):
            # 32 rows over 2 seq shards = 2 rows/device at 1/8 res
            check_halo_support(
                cfg, dataclasses.replace(tc, image_size=(32, 64)), mesh)


class TestPadderSeqAlignment:
    def test_height_aligns_to_stride_times_seq(self):
        from dexiraft_tpu.data.padder import InputPadder

        # 44 rows, seq=2: height must hit a multiple of 8*2=16 while
        # width keeps plain stride-8
        p = InputPadder((1, 44, 60, 3), seq=2)
        assert p.padded_shape == (48, 64)
        # already aligned: no height pad
        assert InputPadder((1, 48, 64, 3), seq=2).padded_shape == (48, 64)

    def test_seq_one_is_reference_behavior(self):
        from dexiraft_tpu.data.padder import InputPadder

        assert InputPadder((1, 44, 60, 3)).padded_shape == \
            InputPadder((1, 44, 60, 3), seq=1).padded_shape == (48, 64)

    def test_bad_seq_refused(self):
        from dexiraft_tpu.data.padder import InputPadder

        with pytest.raises(ValueError, match="seq"):
            InputPadder((1, 48, 64, 3), seq=0)

    def test_unaligned_bucket_refused(self):
        from dexiraft_tpu.data.padder import InputPadder

        # 40 is stride-8 aligned but not 16-aligned: a seq=2 bucket
        # cannot split it into whole-stride row slabs
        with pytest.raises(ValueError, match="stride\\*seq"):
            InputPadder((1, 40, 64, 3), target=(40, 64), seq=2)


# --------------------------------------------------------------------------
# fence vs halo: full-step parity (slow — two train-step compiles)
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def halo_run():
    """Three fence steps and three halo steps of the SAME schedule,
    computed once and shared.

    Mesh asymmetry is deliberate and load-bearing: the fence arm runs on
    a (data 2, fsdp 2) mesh WITHOUT a seq axis because GSPMD's spatial
    partitioning of convolutions miscompiles on this CPU backend (wrong
    loss — the same class of bug as the feature-dim conv miscompile that
    motivated the fence design, tests/test_zzzfsdp.py). The halo arm on
    (data 2, fsdp 2, seq 2) replaces exactly that GSPMD path with
    explicit collectives, so comparing it against the KNOWN-GOOD no-seq
    fence pins both parity and the motivation in one test.
    """
    import dataclasses

    import jax
    import jax.numpy as jnp

    from dexiraft_tpu.parallel.layout import (
        gather_state,
        make_mesh_fsdp,
        shard_state,
    )
    from dexiraft_tpu.train.state import create_state
    from dexiraft_tpu.train.step import make_train_step

    cfg, tc = _ok_setup()
    tc = dataclasses.replace(tc, batch_size=8)
    h, w = tc.image_size

    def batches(n):
        out = []
        for i in range(n):
            k1, k2, k3 = jax.random.split(jax.random.PRNGKey(100 + i), 3)
            out.append(dict(
                image1=jax.random.uniform(k1, (8, h, w, 3), jnp.float32,
                                          0, 255),
                image2=jax.random.uniform(k2, (8, h, w, 3), jnp.float32,
                                          0, 255),
                flow=jax.random.normal(k3, (8, h, w, 2)) * 2.0,
                valid=jnp.ones((8, h, w), jnp.float32)))
        return out

    mesh_f = make_mesh_fsdp(2, 2)      # fence: fsdp storage, no seq
    mesh_h = make_mesh_fsdp(2, 2, 2)   # halo: + seq compute sharding
    fence = make_train_step(cfg, tc, mesh=mesh_f)
    halo = make_train_step(cfg, tc, mesh=mesh_h, compute_sharding="halo")
    s_f = shard_state(create_state(jax.random.PRNGKey(0), cfg, tc), mesh_f)
    s_h = shard_state(create_state(jax.random.PRNGKey(0), cfg, tc), mesh_h)

    fence_metrics, halo_metrics = [], []
    for b in batches(3):
        s_f, m_f = fence(s_f, b)
        s_h, m_h = halo(s_h, b)
        fence_metrics.append(
            {k: float(jax.device_get(v)) for k, v in m_f.items()})
        halo_metrics.append(
            {k: float(jax.device_get(v)) for k, v in m_h.items()})

    # host-side gathered copies: the two states live on DIFFERENT meshes
    # (4 vs 8 devices), so any comparison must cross through numpy
    params_f = jax.tree.map(np.asarray, gather_state(s_f.params, mesh_f))
    params_h = jax.tree.map(np.asarray, gather_state(s_h.params, mesh_h))
    return dict(cfg=cfg, tc=tc, batches=batches, mesh_h=mesh_h,
                state_h=s_h, fence_metrics=fence_metrics,
                halo_metrics=halo_metrics, params_f=params_f,
                params_h=params_h)


@pytest.mark.slow
class TestFenceHaloParity:
    # fp32 accumulation-order tolerance, same as the fsdp parity pins
    # (tests/test_zzzfsdp.py): the two programs sum losses and grads in
    # different orders (psum trees vs replicated reductions), so bit
    # equality is not expected — agreement to atol=1e-4 / rtol=1e-3
    # over three optimizer steps is.
    ATOL, RTOL = 1e-4, 1e-3

    def test_loss_parity_over_steps(self, halo_run):
        for mf, mh in zip(halo_run["fence_metrics"],
                          halo_run["halo_metrics"]):
            assert mh["loss"] == pytest.approx(
                mf["loss"], rel=self.RTOL, abs=self.ATOL)
            assert mh["epe"] == pytest.approx(
                mf["epe"], rel=self.RTOL, abs=self.ATOL)

    def test_state_stays_finite(self, halo_run):
        assert all(m["state_finite"] for m in halo_run["halo_metrics"])

    def test_params_track_after_three_steps(self, halo_run):
        import jax

        worst = max(
            float(np.max(np.abs(a - b))) for a, b in zip(
                jax.tree.leaves(halo_run["params_f"]),
                jax.tree.leaves(halo_run["params_h"])))
        assert worst < 5e-4, (
            f"fence/halo params diverged: max|Δ|={worst:.3e}")

    def test_halo_state_stored_sharded(self, halo_run):
        """Params must STAY fsdp-sharded through the halo step — a
        silently replicated train state would defeat the per-block
        gather design."""
        import jax

        from dexiraft_tpu.parallel.layout import LAYOUT

        mesh_h = halo_run["mesh_h"]
        n_fsdp = LAYOUT.fsdp_size(mesh_h)
        sharded = 0
        for leaf in jax.tree.leaves(halo_run["state_h"].params):
            shard = leaf.sharding.shard_shape(leaf.shape)
            if int(np.prod(shard)) * n_fsdp == int(np.prod(leaf.shape)):
                sharded += 1
        assert sharded > 0, "no param leaf is fsdp-sharded after the step"


@pytest.mark.slow
class TestHaloEval:
    def test_eval_matches_unsharded_apply(self, halo_run):
        import jax
        import jax.numpy as jnp

        from dexiraft_tpu.models.raft import RAFT
        from dexiraft_tpu.train.step import make_eval_step

        cfg, tc = halo_run["cfg"], halo_run["tc"]
        h, w = tc.image_size
        ev = make_eval_step(cfg, iters=4, mesh=halo_run["mesh_h"],
                            compute_sharding="halo")
        # contract: variables arrive in STORAGE layout (the train
        # state's own shardings), not gathered copies
        variables = {"params": halo_run["state_h"].params}
        b = halo_run["batches"](1)[0]
        flow_init = jnp.zeros((8, h // 8, w // 8, 2), jnp.float32)
        fl, fu = ev(variables, b["image1"], b["image2"], flow_init)

        model = RAFT(cfg)
        rl, ru = jax.jit(
            lambda v, a, bb: model.apply(v, a, bb, iters=4, train=False,
                                         test_mode=True))(
            {"params": jax.tree.map(jnp.asarray, halo_run["params_h"])},
            b["image1"], b["image2"])
        d_low = float(np.max(np.abs(np.asarray(fl) - np.asarray(rl))))
        d_up = float(np.max(np.abs(np.asarray(fu) - np.asarray(ru))))
        assert d_low < 1e-3 and d_up < 1e-3, (
            f"halo eval diverges: low={d_low:.3e} up={d_up:.3e}")


# --------------------------------------------------------------------------
# per-block gather schedule
# --------------------------------------------------------------------------


class TestParamBlockSchedule:
    def test_blocks_are_top_level_modules(self):
        """The gather→use→drop schedule partitions the tree by top-level
        module key; every param leaf must belong to exactly one block
        (a new top-level module automatically becomes its own block —
        the schedule can't silently skip one)."""
        from _models import raft_shapes
        from dexiraft_tpu.parallel.layout import param_block_names

        cfg, _ = _ok_setup()
        params = raft_shapes(cfg, 48, 64)["params"]
        blocks = param_block_names(params)
        assert set(blocks) == {"fnet", "cnet", "ScanRAFTStep_0"}
        assert blocks == tuple(params), "schedule must follow tree order"
