"""Where a tier-1 run's time went, read from the JUnit file it wrote.

The driver's command (ROADMAP.md "Tier-1 verify", /root/TESTS_LAST_RUN.json)
passes `--junitxml=/tmp/_t1.xml`; pytest writes that file once, from the
controller, when the run reaches its end, so six xdist workers cannot
corrupt it and a run the clock cut leaves none. This prints the sum of
all tests' seconds (setup + call + teardown), the sum by file, the slowest
tests, and the wall clock six workers need at best: the larger of the sum
over six and the largest file, since `--dist loadfile` keeps a file on
one worker. Exit code 1 when that exceeds BUDGET_S, three quarters of the
driver's 1470 s limit, or when the file is missing or cut.

Usage: python scripts/test_slowest.py [/tmp/_t1.xml]
"""

from __future__ import annotations

import collections
import sys
import xml.etree.ElementTree as ET

WORKERS = 6
BUDGET_S = 1100.0
SHOWN = 15


def read(path: str) -> dict[str, float]:
    """{test id: seconds} of every testcase in a JUnit file."""
    tests = {}
    for case in ET.parse(path).iter("testcase"):
        # classname is the dotted module, then any classes; a module
        # skipped whole has none, and its dotted name as its name
        name, dotted = case.get("name", ""), case.get("classname", "")
        parts = (dotted or name).split(".")
        module = next((i for i, p in enumerate(parts)
                       if p.startswith("test_")), len(parts) - 1)
        nodeid = "::".join(["/".join(parts[:module + 1]) + ".py",
                            *parts[module + 1:], *([name] if dotted else [])])
        tests[nodeid] = tests.get(nodeid, 0.0) + float(case.get("time", 0))
    return tests


def main(argv: list[str]) -> int:
    path = argv[1] if len(argv) > 1 else "/tmp/_t1.xml"
    try:
        tests = read(path)
    except (OSError, ET.ParseError) as e:
        print(f"no whole run to read ({path}: {e}): pytest writes the file "
              f"when a run with --junitxml reaches its end", file=sys.stderr)
        return 1
    if not tests:
        print(f"{path} holds no testcase", file=sys.stderr)
        return 1

    total = sum(tests.values()) or 1e-9
    files: collections.Counter = collections.Counter()
    for nodeid, seconds in tests.items():
        files[nodeid.split("::")[0]] += seconds
    largest, largest_s = files.most_common(1)[0]
    at_best = max(total / WORKERS, largest_s)

    print(f"{len(tests)} tests, {total:.1f} s in all "
          f"(setup + call + teardown), {len(files)} files")
    print(f"by file, top {min(SHOWN, len(files))}:")
    for name, seconds in files.most_common(SHOWN):
        print(f"  {seconds:8.1f} s  {100 * seconds / total:4.1f} %  {name}")
    print(f"slowest tests, top {min(SHOWN, len(tests))}:")
    for nodeid, seconds in sorted(tests.items(), key=lambda kv: -kv[1])[:SHOWN]:
        print(f"  {seconds:8.1f} s  {100 * seconds / total:4.1f} %  {nodeid}")
    print(f"{WORKERS} workers need {at_best:.1f} s at best (the larger of "
          f"{total / WORKERS:.1f} s, the sum over {WORKERS}, and {largest_s:.1f}"
          f" s, {largest}); the budget is {BUDGET_S:.0f} s")
    if at_best > BUDGET_S:
        print(f"OVER the budget by {at_best - BUDGET_S:.1f} s: cheapen the "
              f"tests above, or mark a whole-cell compile `slow`")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
