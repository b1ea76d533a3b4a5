"""Update operators: motion encoders, ConvGRU cells, flow/mask heads.

Flax re-design of the reference update blocks (core/update.py) plus the
corrected RefineFlow fusion head from the v3 variant (core/update_3.py:138-151
— the reference's version outputs 1 channel where flow needs 2, which made
v3 diverge; ours outputs 2 and documents the deviation).

The motion encoders own the fused refinement-step seam
(config.fused_update): their first layer — the 1x1 conv over the
(2r+1)^2-per-level correlation features — is exactly a per-pixel matmul,
so it can run INSIDE the Pallas lookup kernel while each pixel block's
correlation window is still VMEM-resident (the pyramid's fused_step,
ops/local_corr.py). ``FusedCorrEncoder`` declares parameters with the
same names/shapes/initializers as the ``nn.Conv`` it replaces, under the
same module name ("Conv_0"), so the parameter tree — and therefore every
checkpoint and the torch interop name map (interop/torch_convert.py) —
is identical with and without fusion. The convs are explicitly named
with the auto-names they have always had, pinning that contract.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp


class FlowHead(nn.Module):
    """conv3x3 -> relu -> conv3x3 to a 2-channel flow delta.

    Reference: core/update.py:6-14.
    """

    hidden_dim: int = 256
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        x = nn.relu(nn.Conv(self.hidden_dim, (3, 3), padding=1, dtype=self.dtype)(x))
        return nn.Conv(2, (3, 3), padding=1, dtype=self.dtype)(x)


class ConvGRU(nn.Module):
    """3x3 convolutional GRU. Reference: core/update.py:16-31."""

    hidden_dim: int = 128
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h, x):
        hx = jnp.concatenate([h, x], axis=-1)
        z = nn.sigmoid(nn.Conv(self.hidden_dim, (3, 3), padding=1, dtype=self.dtype)(hx))
        r = nn.sigmoid(nn.Conv(self.hidden_dim, (3, 3), padding=1, dtype=self.dtype)(hx))
        q = nn.tanh(
            nn.Conv(self.hidden_dim, (3, 3), padding=1, dtype=self.dtype)(
                jnp.concatenate([r * h, x], axis=-1)
            )
        )
        return (1 - z) * h + z * q


class SepConvGRU(nn.Module):
    """Separable GRU: a 1x5 horizontal pass then a 5x1 vertical pass.

    Reference: core/update.py:33-60.
    """

    hidden_dim: int = 128
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h, x):
        def gru_pass(h, x, ksize):
            conv = lambda: nn.Conv(  # noqa: E731
                self.hidden_dim, ksize,
                padding=((ksize[0] // 2, ksize[0] // 2), (ksize[1] // 2, ksize[1] // 2)),
                dtype=self.dtype,
            )
            hx = jnp.concatenate([h, x], axis=-1)
            z = nn.sigmoid(conv()(hx))
            r = nn.sigmoid(conv()(hx))
            q = nn.tanh(conv()(jnp.concatenate([r * h, x], axis=-1)))
            return (1 - z) * h + z * q

        h = gru_pass(h, x, (1, 5))  # horizontal
        h = gru_pass(h, x, (5, 1))  # vertical
        return h


class FusedCorrEncoder(nn.Module):
    """The motion encoder's 1x1 corr conv, executed INSIDE the
    pyramid's fused lookup kernel (pre-activation; the relu stays in
    XLA).

    Declares ``kernel``/``bias`` with ``nn.Conv``'s exact shapes and
    initializers, so instantiating it under the name the conv would have
    had ("Conv_0") keeps the parameter tree bit-identical to the unfused
    path — the same checkpoint serves both, which is what makes the
    fused/unfused A/B (and the parity tests) meaningful.

    Per-level int8 dequantization scales are linear, so they are folded
    into the weight's per-level row blocks here, in XLA, before the
    kernel sees them — the kernel reads the pyramid in its storage
    dtype. (The 1/sqrt(C) correlation normalization is NOT folded: the
    kernel applies it itself, like every other corr path.)
    """

    features: int = 256
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, pyr, coords):
        num_levels = len(pyr.fmap2_pyramid)
        win = 2 * pyr.radius + 1
        in_ch = num_levels * win * win
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (1, 1, in_ch, self.features))
        bias = self.param("bias", nn.initializers.zeros_init(),
                          (self.features,))
        w = kernel.reshape(in_ch, self.features).astype(jnp.float32)
        if pyr.scales is not None:
            ww = win * win
            w = jnp.concatenate(
                [w[lvl * ww:(lvl + 1) * ww] * pyr.scales[lvl]
                 for lvl in range(num_levels)], axis=0)
        out = pyr.fused_step(coords, w, bias.astype(jnp.float32))
        return out.astype(self.dtype)


class SmallMotionEncoder(nn.Module):
    """Embed (corr, flow) -> 82-channel motion features.

    Reference: core/update.py:62-77. ``pyr``/``coords`` select the fused
    path: the Conv_0 lookup-conv runs inside the Pallas kernel and
    ``corr`` is never materialized (pass corr=None there).
    """

    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, flow, corr, pyr=None, coords=None):
        if pyr is not None:
            cor = nn.relu(FusedCorrEncoder(96, self.dtype,
                                           name="Conv_0")(pyr, coords))
        else:
            cor = nn.relu(nn.Conv(96, (1, 1), dtype=self.dtype,
                                  name="Conv_0")(corr))
        flo = nn.relu(nn.Conv(64, (7, 7), padding=3, dtype=self.dtype,
                              name="Conv_1")(flow))
        flo = nn.relu(nn.Conv(32, (3, 3), padding=1, dtype=self.dtype,
                              name="Conv_2")(flo))
        out = nn.relu(
            nn.Conv(80, (3, 3), padding=1, dtype=self.dtype, name="Conv_3")(
                jnp.concatenate([cor, flo], axis=-1)
            )
        )
        return jnp.concatenate([out, flow], axis=-1)


class BasicMotionEncoder(nn.Module):
    """Embed (corr, flow) -> 128-channel motion features.

    Reference: core/update.py:79-97. ``pyr``/``coords`` select the fused
    path (see SmallMotionEncoder).
    """

    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, flow, corr, pyr=None, coords=None):
        if pyr is not None:
            cor = nn.relu(FusedCorrEncoder(256, self.dtype,
                                           name="Conv_0")(pyr, coords))
        else:
            cor = nn.relu(nn.Conv(256, (1, 1), dtype=self.dtype,
                                  name="Conv_0")(corr))
        cor = nn.relu(nn.Conv(192, (3, 3), padding=1, dtype=self.dtype,
                              name="Conv_1")(cor))
        flo = nn.relu(nn.Conv(128, (7, 7), padding=3, dtype=self.dtype,
                              name="Conv_2")(flow))
        flo = nn.relu(nn.Conv(64, (3, 3), padding=1, dtype=self.dtype,
                              name="Conv_3")(flo))
        out = nn.relu(
            nn.Conv(128 - 2, (3, 3), padding=1, dtype=self.dtype,
                    name="Conv_4")(
                jnp.concatenate([cor, flo], axis=-1)
            )
        )
        return jnp.concatenate([out, flow], axis=-1)


class SmallUpdateBlock(nn.Module):
    """Motion encoder + ConvGRU + flow head; no upsampling mask.

    Reference: core/update.py:99-112.
    """

    hidden_dim: int = 96
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, net, inp, corr, flow, pyr=None, coords=None):
        motion = SmallMotionEncoder(self.dtype)(flow, corr,
                                                pyr=pyr, coords=coords)
        net = ConvGRU(self.hidden_dim, self.dtype)(net, jnp.concatenate([inp, motion], axis=-1))
        delta_flow = FlowHead(128, self.dtype)(net)
        return net, None, delta_flow


class BasicUpdateBlock(nn.Module):
    """Motion encoder + SepConvGRU + flow head + convex-upsampling mask head.

    The mask logits are scaled by 0.25 to balance gradients
    (core/update.py:114-136).
    """

    hidden_dim: int = 128
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, net, inp, corr, flow, pyr=None, coords=None):
        motion = BasicMotionEncoder(self.dtype)(flow, corr,
                                                pyr=pyr, coords=coords)
        net = SepConvGRU(self.hidden_dim, self.dtype)(net, jnp.concatenate([inp, motion], axis=-1))
        delta_flow = FlowHead(256, self.dtype)(net)

        mask = nn.relu(nn.Conv(256, (3, 3), padding=1, dtype=self.dtype)(net))
        mask = 0.25 * nn.Conv(64 * 9, (1, 1), dtype=self.dtype)(mask)
        return net, mask, delta_flow


# --------------------------------------------------------------------------
# Declarative H-axis conv chains — the halo machinery's source of truth
# --------------------------------------------------------------------------

#: (kernel, stride, padding) along the H axis, deepest sequential path,
#: forward order — parallel/halo.py composes these into per-module
#: receptive-field halo widths (see models/extractor.py for the
#: convention). Parallel branches take the longest path: both motion
#: encoders are bounded by flow(7x7) -> 3x3 -> concat-conv(3x3); the
#: GRUs by the r -> q dependency (z is parallel to r), which for the
#: separable GRU only counts the VERTICAL (5x1) pass — the (1x5)
#: horizontal pass has H-kernel 1.
MOTION_ENCODER_CHAIN = ((7, 1, 3), (3, 1, 1), (3, 1, 1))
CONV_GRU_CHAIN = ((3, 1, 1), (3, 1, 1))
SEP_CONV_GRU_CHAIN = ((5, 1, 2), (5, 1, 2))
FLOW_HEAD_CHAIN = ((3, 1, 1), (3, 1, 1))
MASK_HEAD_CHAIN = ((3, 1, 1), (1, 1, 0))


class RefineFlow(nn.Module):
    """1x1-conv fusion of (flow_up, eflow_up) -> refined 2-channel flow.

    Capability parity with the v3 variant's refine block
    (core/update_3.py:138-151) with the output-width bug fixed: the
    reference conv maps 4 channels to **1**, shape-incompatible with the
    2-channel flow loss (this is why v3 diverged, SURVEY.md §2.5); ours
    maps 4 -> 2.
    """

    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, flow_up, eflow_up):
        fused = jnp.concatenate([flow_up, eflow_up], axis=-1)
        return nn.Conv(2, (1, 1), dtype=self.dtype)(fused)
