"""Device time a step spends in the expert layers: every op traced under
a `lm/moe/*` scope (router, dispatch, experts, shared, combine), forward,
recomputation and backward, summed over the layers held.

The trace's instruction names joined with `op_name` in the compiled text
of the same executable (benchmarks/lm_scopes.py), leaf events of the
traced window over the steps traced. A program without the scopes, or a
run without a device trace, reads as nothing.
"""

PREFIX = "scope_s:"


def scope_ms(obs, *needles):
    """ms a step under the scopes whose name holds one of `needles`."""
    picked = [v for k, v in obs.counters.items()
              if k.startswith(PREFIX) and any(n in k for n in needles)]
    if obs.trace is None or not picked:
        return None
    return sum(picked) * 1e3


def read(obs):
    return scope_ms(obs, "lm/moe/")
