"""scripts/test_slowest.py: the reader of a tier-1 run's JUnit file that
holds the run's budget (ROADMAP.md, the note under "Tier-1 verify")."""

import importlib.util
import os.path as osp

import pytest

REPO = osp.dirname(osp.dirname(osp.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "test_slowest", osp.join(REPO, "scripts", "test_slowest.py"))
slowest = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(slowest)


def _junit(path, cases):
    """A file as pytest writes it: the module dotted, classes after it."""
    rows = "".join(
        f'<testcase classname="{cls}" name="{name}" time="{seconds}" />'
        for cls, name, seconds in cases)
    path.write_text(
        '<?xml version="1.0" encoding="utf-8"?><testsuites>'
        f'<testsuite name="pytest" errors="0" failures="0" skipped="0" '
        f'tests="{len(cases)}" time="1.0">{rows}</testsuite></testsuites>')
    return str(path)


CASES = [("tests.test_a", "test_one", 100.0),
         ("tests.test_a.TestThing", "test_two[v1-4]", 50.5),
         ("tests.test_b", "test_three", 30.0),
         ("", "tests.test_skipped_whole", 0.0)]


def test_seconds_are_summed_by_test_and_by_file(tmp_path):
    tests = slowest.read(_junit(tmp_path / "t.xml", CASES))
    assert tests == {"tests/test_a.py::test_one": 100.0,
                     "tests/test_a.py::TestThing::test_two[v1-4]": 50.5,
                     "tests/test_b.py::test_three": 30.0,
                     "tests/test_skipped_whole.py": 0.0}


def test_a_run_under_the_budget_exits_0_and_prints_where_the_time_went(
        tmp_path, capsys):
    assert slowest.main(["", _junit(tmp_path / "t.xml", CASES)]) == 0
    out = capsys.readouterr().out
    assert "4 tests, 180.5 s in all" in out
    assert "150.5 s  83.4 %  tests/test_a.py" in out
    assert "100.0 s  55.4 %  tests/test_a.py::test_one" in out


def test_six_workers_need_the_largest_file_at_least(tmp_path, capsys):
    """`--dist loadfile` keeps a file on one worker: one file of 1,200 s
    is over the budget although the sum over six workers is 205 s."""
    cases = CASES + [("tests.test_c.TestBig", f"test_{i}", 400.0)
                     for i in range(3)]
    assert sum(c[2] for c in cases) / slowest.WORKERS < slowest.BUDGET_S
    assert slowest.main(["", _junit(tmp_path / "t.xml", cases)]) == 1
    out = capsys.readouterr().out
    assert "need 1200.0 s as `loadfile`" in out
    assert ("the run ends with tests/test_c.py, 1200.0 s, started at 0.0 s"
            in out)
    assert "; 1200.0 s at best" in out
    assert "OVER the budget by 100.0 s" in out


def test_a_sum_over_the_budget_exits_1(tmp_path, capsys):
    """Twenty files of one test: a worker holds two, so two of the six
    run four and the others three."""
    cases = [(f"tests.test_f{i:02}", "test_it", 340.0) for i in range(20)]
    assert max(c[2] for c in cases) < slowest.BUDGET_S
    assert slowest.main(["", _junit(tmp_path / "t.xml", cases)]) == 1
    out = capsys.readouterr().out
    assert "need 1360.0 s as `loadfile`" in out
    assert "; 1133.3 s at best" in out


def test_a_file_of_few_long_tests_starts_last_and_ends_the_run(tmp_path,
                                                               capsys):
    """The files are handed out by their number of tests, most first:
    sixty files of ten one-second tests keep six workers for 100 s, and
    only then does the file of two 300 s tests start, on one worker. The
    best case (its 600 s) knows nothing of that tail; the makespan does."""
    cases = [(f"tests.test_f{i:02}", f"test_{j}", 1.0)
             for i in range(60) for j in range(10)]
    cases += [("tests.test_long", f"test_{j}", 300.0) for j in range(2)]
    assert slowest.main(["", _junit(tmp_path / "t.xml", cases)]) == 0
    out = capsys.readouterr().out
    assert "need 700.0 s as `loadfile`" in out
    assert ("the run ends with tests/test_long.py, 600.0 s, started at "
            "100.0 s" in out)
    assert "; 600.0 s at best" in out


def test_a_worker_takes_the_next_file_with_two_tests_left():
    """xdist hands a worker its next file when it has two tests or fewer
    left, not when it is free: the worker whose last two tests are long
    takes the file another worker would have reached sooner."""
    files = {"a.py": [1.0, 100.0, 100.0], "b.py": [1.0, 1.0, 1.0, 5.0, 5.0],
             **{f"c{i}.py": [2.0] * 4 for i in range(4)},
             "late.py": [50.0]}
    makespan, last, started = slowest.play(files)
    # b.py's worker has two left at 3 s and a.py's at 1 s: late.py is a.py's
    assert (makespan, last, started) == (251.0, "late.py", 201.0)


@pytest.mark.parametrize("content", [None, "", "<testsuites><testsuite>"
                                     '<testcase classname="tests.test_a"',
                                     "<testsuites></testsuites>"],
                         ids=["missing", "empty", "cut", "no_testcase"])
def test_a_missing_or_cut_file_exits_1_and_says_why(tmp_path, capsys, content):
    path = tmp_path / "t.xml"
    if content is not None:
        path.write_text(content)
    assert slowest.main(["", str(path)]) == 1
    err = capsys.readouterr().err
    assert str(path) in err and ("no whole run" in err or "no testcase" in err)
