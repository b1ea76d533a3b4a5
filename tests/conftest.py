"""Test configuration: force CPU with 8 virtual devices.

Multi-chip sharding logic is exercised on a virtual CPU mesh (no TPU
needed): JAX_PLATFORMS=cpu selects the CPU whatever the host holds, and
the config update after import says the same to a jax that was imported
before this file ran.

Where a run's time went is read from the JUnit file the tier-1 command
writes (`python scripts/test_slowest.py`, which also holds the budget);
ROADMAP.md's note under "Tier-1 verify" has the rules that keep a new
test cheap.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = _flags + " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# arm the lock-order runtime for the whole suite (analysis/locks): any
# rank inversion or ABBA acquisition cycle in the serve/resilience
# thread fabric RAISES at the offending acquisition instead of warning
# — every threaded tier-1 test doubles as a lock-discipline canary
# (the armed-replication-canary idiom). Seeded-violation tests use
# private LockRegistry instances, so the global registry stays clean.
from dexiraft_tpu.analysis import locks as _locks  # noqa: E402

_locks.set_strict(True)
