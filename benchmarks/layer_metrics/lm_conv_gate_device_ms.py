"""Device time a step spends under `lm/conv/gate`: B * z, the taps under
the document mask and C *, between the convolution mixer's two
projections; elementwise, so bound by memory where the compiler makes
one pass of it (`lm_conv_device_ms` for the whole mixer).
"""

from benchmarks.layer_metrics.lm_moe_device_ms import scope_ms


def read(obs):
    return scope_ms(obs, "lm/conv/gate")
