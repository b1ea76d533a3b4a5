"""Numerical parity: reference torch models vs our flax models under
converted weights — validates every conversion rule (conv transpose
orientation, BN stats routing, block name maps) and the forward numerics
(encoders, correlation pyramid + bilinear lookup, ConvGRU update, convex
upsampling; SURVEY.md §7 hard parts 2 and 4) end to end.

Skipped when the reference checkout or torch is unavailable.
"""

import os
import sys

import numpy as np
import pytest

_REF = "/root/reference/core/DexiNed"
_REF_CORE = "/root/reference/core"

torch = pytest.importorskip("torch")
pytestmark = pytest.mark.skipif(not os.path.isdir(_REF),
                                reason="reference checkout not mounted")


def _import_from(path, module):
    sys.path.insert(0, path)
    try:
        return __import__(module)
    finally:
        sys.path.remove(path)


def _randomize_bn_stats(model):
    """Fresh-init BN buffers are all (0, 1); randomize so a converter that
    routes stats to the wrong same-shaped module fails the test."""
    with torch.no_grad():
        for name, buf in model.named_buffers():
            if name.endswith("running_mean"):
                buf.normal_(0, 0.05)
            elif name.endswith("running_var"):
                buf.uniform_(0.5, 1.5)


def _reference_model():
    TorchDexiNed = _import_from(_REF, "model").DexiNed
    torch.manual_seed(0)
    m = TorchDexiNed()
    m.eval()
    _randomize_bn_stats(m)
    return m


@pytest.fixture(scope="module")
def parity_pair():
    import jax
    import jax.numpy as jnp

    from dexiraft_tpu.interop.torch_convert import (
        convert_dexined_state_dict,
        verify_against,
    )
    from dexiraft_tpu.models.dexined import DexiNed

    tm = _reference_model()
    variables = convert_dexined_state_dict(tm.state_dict())

    jm = DexiNed()
    template = jax.eval_shape(
        lambda: jm.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 64, 64, 3)), train=False))
    verify_against(template, variables)
    return tm, jm, variables


def test_full_model_parity(parity_pair):
    import jax.numpy as jnp

    tm, jm, variables = parity_pair
    rng = np.random.default_rng(0)
    x = rng.normal(0, 1, (1, 96, 128, 3)).astype(np.float32)

    with torch.no_grad():
        t_out = tm(torch.from_numpy(x.transpose(0, 3, 1, 2)))
    j_out = jm.apply(variables, jnp.asarray(x), train=False)

    assert len(t_out) == len(j_out) == 7
    for i, (t, j) in enumerate(zip(t_out, j_out)):
        t_np = t.numpy().transpose(0, 2, 3, 1)
        np.testing.assert_allclose(
            np.asarray(j), t_np, rtol=2e-3, atol=2e-3,
            err_msg=f"output {i} diverges")


def test_stacked_edge_maps_shape(parity_pair):
    import jax.numpy as jnp

    from dexiraft_tpu.models.dexined import stack_edge_maps

    _, jm, variables = parity_pair
    x = jnp.zeros((2, 64, 64, 3))
    maps = stack_edge_maps(jm.apply(variables, x, train=False))
    assert maps.shape == (2, 64, 64, 7)


def _raft_parity_case(torch_model, cfg, *, small=False, seed=1, tol=5e-3):
    """Shared harness: convert weights, verify the tree, compare the
    test-mode forward (both low- and full-resolution flow) at 128x160 —
    frames large enough that the level-3 volume is >= 2x2; at 1x1 the
    REFERENCE's grid_sample normalization divides by zero
    (core/utils/utils.py:64-65) and emits NaN."""
    import jax.numpy as jnp

    from _models import jit_apply, raft_shapes
    from dexiraft_tpu.interop.torch_convert import (
        convert_raft_state_dict,
        verify_against,
    )
    from dexiraft_tpu.models.raft import RAFT

    torch_model.eval()
    _randomize_bn_stats(torch_model)

    variables = convert_raft_state_dict(torch_model.state_dict(), small=small)
    jm = RAFT(cfg)
    verify_against(raft_shapes(cfg, 128, 160), variables)

    rng = np.random.default_rng(seed)
    im1 = rng.uniform(0, 255, (1, 128, 160, 3)).astype(np.float32)
    im2 = rng.uniform(0, 255, (1, 128, 160, 3)).astype(np.float32)

    with torch.no_grad():
        t_low, t_up = torch_model(
            torch.from_numpy(im1.transpose(0, 3, 1, 2)),
            torch.from_numpy(im2.transpose(0, 3, 1, 2)),
            iters=4, test_mode=True)
    j_low, j_up = jit_apply(jm)(variables, jnp.asarray(im1), jnp.asarray(im2),
                                iters=4, test_mode=True)

    np.testing.assert_allclose(
        np.asarray(j_low), t_low.numpy().transpose(0, 2, 3, 1),
        rtol=tol, atol=tol)
    np.testing.assert_allclose(
        np.asarray(j_up), t_up.numpy().transpose(0, 2, 3, 1),
        rtol=tol, atol=tol)


def _v1_args(small):
    import argparse

    return argparse.Namespace(small=small, dropout=0.0,
                              mixed_precision=False, alternate_corr=False)


class TestRAFTParity:
    def test_full_model(self):
        from dexiraft_tpu.config import raft_v1

        TorchRAFT = _import_from(_REF_CORE, "raft_1").RAFT
        torch.manual_seed(0)
        _raft_parity_case(TorchRAFT(_v1_args(False)), raft_v1(), seed=1)

    def test_small_model(self):
        from dexiraft_tpu.config import raft_v1

        TorchRAFT = _import_from(_REF_CORE, "raft_1").RAFT
        torch.manual_seed(1)
        _raft_parity_case(TorchRAFT(_v1_args(True)), raft_v1(small=True),
                          small=True, seed=2)

    def test_v5_dual_stream(self, monkeypatch):
        """Flagship v5: embedded frozen DexiNed, dual streams, shared
        update block, coupled delta-f + delta-ef update (core/raft.py:183)."""
        from dexiraft_tpu.config import raft_v5

        # the reference RAFT.__init__ loads a DexiNed checkpoint from disk
        # (core/raft.py:30-33) that ships outside the repo — feed it a
        # randomly initialized DexiNed state dict instead
        TorchDexiNed = _import_from(_REF, "model").DexiNed
        torch.manual_seed(3)
        dexi_sd = TorchDexiNed().state_dict()
        monkeypatch.setattr(torch, "load", lambda *a, **k: dexi_sd)

        TorchRAFTv5 = _import_from(_REF_CORE, "raft").RAFT
        tm = TorchRAFTv5(_v1_args(False))
        _raft_parity_case(tm, raft_v5(), seed=4, tol=1e-2)


class TestExportRoundTrip:
    """export_*_state_dict must exactly invert the import converter: a
    torch state_dict converted to flax and exported back is bitwise
    identical (and torch can load_state_dict the result strict=True)."""

    def _assert_round_trip(self, sd, exported):
        assert set(exported) == set(sd)
        for k in sd:
            a = sd[k].detach().cpu().numpy()
            b = exported[k]
            assert a.shape == tuple(np.shape(b)), k
            np.testing.assert_array_equal(a, np.asarray(b), err_msg=k)

    def test_dexined(self):
        from dexiraft_tpu.interop.torch_convert import (
            convert_dexined_state_dict,
            export_dexined_state_dict,
        )

        tm = _reference_model()
        sd = tm.state_dict()
        variables = convert_dexined_state_dict(sd)
        exported = export_dexined_state_dict(variables, sd)
        self._assert_round_trip(sd, exported)
        tm.load_state_dict(
            {k: torch.from_numpy(np.asarray(v)) for k, v in exported.items()},
            strict=True)

    def test_raft_v1_full_and_small(self):
        from dexiraft_tpu.interop.torch_convert import (
            convert_raft_state_dict,
            export_raft_state_dict,
        )

        TorchRAFT = _import_from(_REF_CORE, "raft_1").RAFT
        for small, seed in ((False, 10), (True, 11)):
            torch.manual_seed(seed)
            tm = TorchRAFT(_v1_args(small))
            tm.eval()
            _randomize_bn_stats(tm)
            sd = tm.state_dict()
            variables = convert_raft_state_dict(sd, small=small)
            exported = export_raft_state_dict(variables, sd, small=small)
            self._assert_round_trip(sd, exported)

    def test_raft_v5_with_embedded_dexined(self, monkeypatch):
        from dexiraft_tpu.interop.torch_convert import (
            convert_raft_state_dict,
            export_raft_state_dict,
        )

        TorchDexiNed = _import_from(_REF, "model").DexiNed
        torch.manual_seed(12)
        dexi_sd = TorchDexiNed().state_dict()
        monkeypatch.setattr(torch, "load", lambda *a, **k: dexi_sd)
        TorchRAFTv5 = _import_from(_REF_CORE, "raft").RAFT
        tm = TorchRAFTv5(_v1_args(False))
        tm.eval()
        _randomize_bn_stats(tm)
        sd = tm.state_dict()
        variables = convert_raft_state_dict(sd)
        exported = export_raft_state_dict(variables, sd)
        self._assert_round_trip(sd, exported)
