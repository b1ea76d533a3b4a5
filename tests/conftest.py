"""Test configuration: force CPU with 8 virtual devices + tier-1 budget guard.

Multi-chip sharding logic is exercised on a virtual CPU mesh (no TPU
needed): JAX_PLATFORMS=cpu selects the CPU whatever the host holds, and
the config update after import says the same to a jax that was imported
before this file ran.

Budget guard: the tier-1 suite runs under a hard 870 s wall-clock cap
(ROADMAP.md), so one inadvertently expensive test silently evicts the
tests scheduled after it. Every run records per-test call durations to
logs/test_durations.json (rewritten after each test, so a timeout-killed
session still leaves the completed prefix). At COLLECTION time the next
run fails loudly if any collected test not marked `slow` exceeded the
per-test ceiling last time — the author finds out immediately, not by
watching DOTS_PASSED sag. Ceiling: DEXIRAFT_TEST_CEILING_S (default 420:
the heaviest legitimate test — a CLI guard-rollback training loop — has
measured 149-234s across runs (±30% machine-weather variance), so the
tripwire sits at ~1.8x the worst observed while still catching any new
multi-minute test; 0 disables). `scripts/test_slowest.py` prints the
top offenders.
"""

import json
import os
import os.path as osp

import pytest

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

DURATIONS_PATH = osp.join(osp.dirname(osp.dirname(osp.abspath(__file__))),
                          "logs", "test_durations.json")
CEILING_S = float(os.environ.get("DEXIRAFT_TEST_CEILING_S", "420"))

_durations: dict = {}


def _last_durations() -> dict:
    try:
        with open(DURATIONS_PATH) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def pytest_collection_modifyitems(config, items):
    if CEILING_S <= 0:
        return
    last = _last_durations()
    over = [(it.nodeid, last[it.nodeid]) for it in items
            if "slow" not in it.keywords and last.get(it.nodeid, 0) > CEILING_S]
    if over:
        detail = "\n".join(f"  {d:7.1f}s  {nid}" for nid, d in
                           sorted(over, key=lambda x: -x[1]))
        raise pytest.UsageError(
            f"tier-1 budget guard: {len(over)} unmarked test(s) exceeded "
            f"the {CEILING_S:.0f}s per-test ceiling on the last recorded "
            f"run (logs/test_durations.json). Mark them `slow` or make "
            f"them cheaper — then delete logs/test_durations.json (or "
            f"run once with DEXIRAFT_TEST_CEILING_S=0) so the next run "
            f"re-records fresh timings:\n{detail}")


_seen_this_run: set = set()


def pytest_runtest_logreport(report):
    # sum ALL phases (setup + call + teardown): module/session-scoped
    # fixtures charge their cost to the first requesting test's setup,
    # and a 500s fixture evicts tail tests from the budget window just
    # as surely as a 500s test body would
    if report.when not in ("setup", "call", "teardown"):
        return
    if not _durations:
        # merge into the previous record so a partial invocation (one
        # file, -k filter) doesn't erase the rest of the suite's data
        _durations.update(_last_durations())
    if report.nodeid not in _seen_this_run:
        _seen_this_run.add(report.nodeid)
        _durations[report.nodeid] = 0.0
    _durations[report.nodeid] = round(
        _durations[report.nodeid] + report.duration, 3)
    if report.when != "teardown":
        return  # write once per test, at its last phase
    # rewrite after every test: the tier-1 runner kills the session at
    # the 870 s cap, and the completed prefix must survive the kill
    try:
        os.makedirs(osp.dirname(DURATIONS_PATH), exist_ok=True)
        tmp = DURATIONS_PATH + ".tmp"
        with open(tmp, "w") as f:
            json.dump(_durations, f, indent=0, sort_keys=True)
        os.replace(tmp, DURATIONS_PATH)
    except OSError:
        pass
