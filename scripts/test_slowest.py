"""Where a tier-1 run's time went, read from the JUnit file it wrote.

The driver's command (ROADMAP.md "Tier-1 verify", /root/TESTS_LAST_RUN.json)
passes `--junitxml=/tmp/_t1.xml`; pytest writes that file once, from the
controller, when the run reaches its end, so six xdist workers cannot
corrupt it and a run the clock cut leaves none: to read a tree that is
over its clock, run the command by hand with a longer `timeout`. The
tests' programs are compiled at the backend's lowest level
(tests/conftest.py): tier 1 reads what a program computes, never how
fast.

This prints the sum of all tests' seconds (setup + call + teardown), the
sum by file, the slowest tests, and the makespan: the run played as the
command's `--dist loadfile` schedules it (`play`). Whole files are handed
out, those with the most tests first, so a file of few, long tests starts
late and can end the run alone. The best case six workers could reach
(the larger of the sum over six and the largest file) is printed beside
it: the gap between the two is that tail. Exit code 1 when the makespan
exceeds BUDGET_S, three quarters of the driver's 1470 s limit (a worker's
start-up and a busy machine take the rest), or when the file is missing
or cut.

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


def play(files: dict[str, list[float]]) -> tuple[float, str, float]:
    """(makespan, the file that ends the run, when that file started) of
    the run as xdist 3.8's `loadfile` schedules it: the files queue by
    their number of tests, most first (ties as collected: by name), and
    a worker takes the next one whenever it has two tests or fewer left:
    xdist looks twice before the first test and once after every test."""
    queue = collections.deque(
        sorted(files, key=lambda name: (-len(files[name]), name)))
    workers = [collections.deque() for _ in range(min(WORKERS, len(queue)))]
    clock = [0.0] * len(workers)

    def hand(w):
        if queue and len(workers[w]) <= 2:
            name = queue.popleft()
            workers[w].extend((name, seconds) for seconds in files[name])

    for _ in range(2):
        for w in range(len(workers)):
            hand(w)
    started: dict[str, float] = {}
    while any(workers):  # the tests in the order they end
        w = min((w for w in range(len(workers)) if workers[w]),
                key=lambda w: clock[w] + workers[w][0][1])
        last, seconds = workers[w].popleft()
        started.setdefault(last, clock[w])
        clock[w] += seconds
        hand(w)
    return max(clock), last, started[last]


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
    by_file: dict[str, list[float]] = {}
    for nodeid, seconds in tests.items():
        by_file.setdefault(nodeid.split("::")[0], []).append(seconds)
    files = collections.Counter({f: sum(s) for f, s in by_file.items()})
    largest, largest_s = files.most_common(1)[0]
    at_best = max(total / WORKERS, largest_s)
    makespan, last, last_started = play(by_file)

    print(f"{len(tests)} tests, {total:.1f} s in all "
          f"(setup + call + teardown), {len(files)} files")
    print(f"by file, top {min(SHOWN, len(files))}:")
    for name, seconds in files.most_common(SHOWN):
        print(f"  {seconds:8.1f} s  {100 * seconds / total:4.1f} %  {name}")
    print(f"slowest tests, top {min(SHOWN, len(tests))}:")
    for nodeid, seconds in sorted(tests.items(), key=lambda kv: -kv[1])[:SHOWN]:
        print(f"  {seconds:8.1f} s  {100 * seconds / total:4.1f} %  {nodeid}")
    print(f"{WORKERS} workers need {makespan:.1f} s as `loadfile` hands the "
          f"files out: the run ends with {last}, {files[last]:.1f} s, started "
          f"at {last_started:.1f} s; {at_best:.1f} s at best (the larger "
          f"of {total / WORKERS:.1f} s, the sum over {WORKERS}, and "
          f"{largest_s:.1f} s, {largest}); the budget is {BUDGET_S:.0f} s")
    if makespan > BUDGET_S:
        print(f"OVER the budget by {makespan - BUDGET_S:.1f} s: cheapen the "
              f"tests above, move a late file of few, long tests into the "
              f"file of its subject that starts early, or mark a whole-cell "
              f"compile `slow`")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
