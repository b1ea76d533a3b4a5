"""Test configuration: force CPU with 8 virtual devices, compiled cheaply.

Multi-chip sharding logic is exercised on a virtual CPU mesh (no TPU
needed): JAX_PLATFORMS=cpu selects the CPU whatever the host holds, and
the config updates after import say the same to a jax that was imported
before this file ran.

Every program is compiled at the backend's lowest level
(JAX_DISABLE_MOST_OPTIMIZATIONS: XLA's optimisation level 0, LLVM's
expensive passes off). The tests read what a program computes and never
how fast, and almost all of a run's seconds were the CPU backend
optimising toy programs that then ran for milliseconds: the same 1,704
tests took 6,266 s at the full level and 4,703 s at this one (ISSUE 46's
runs, six workers). It is set in the environment so that the children
the tests start compile the same way. A file whose subject is the
compiled program (tests/test_chip_compile.py) puts the full level back
for itself, with one autouse module fixture.

Where a run's time went is read from the JUnit file the tier-1 command
writes (`python scripts/test_slowest.py`, which also holds the budget);
ROADMAP.md's note under "Tier-1 verify" has the rules that keep a new
test cheap. A run the clock cut leaves no JUnit file: to read a tree
that is over its clock, run the command by hand with a longer `timeout`.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = _flags + " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_DISABLE_MOST_OPTIMIZATIONS"] = "true"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_disable_most_optimizations", True)

# arm the lock-order runtime for the whole suite (analysis/locks): any
# rank inversion or ABBA acquisition cycle in the serve/resilience
# thread fabric RAISES at the offending acquisition instead of warning
# — every threaded tier-1 test doubles as a lock-discipline canary
# (the armed-replication-canary idiom). Seeded-violation tests use
# private LockRegistry instances, so the global registry stays clean.
from dexiraft_tpu.analysis import locks as _locks  # noqa: E402

_locks.set_strict(True)
