"""chip_smoke.py's parent logic, on the CPU with stub phase commands.

What the contract asks of the parent and a CPU run can show: it loads no
JAX, runs its phases one after another as separate processes, turns any
failed phase into a non-zero exit with no result line, refuses a host
without a TPU, and ends a passing run with exactly the result line. The
real phases at a tiny size are the `slow` test at the bottom (the
rehearsal the builder makes before spending chip time); the full-width
run is `python chip_smoke.py` on the chip.

This module imports no jax itself: the sys.modules test imports it in a
subprocess to share the stubs.
"""

import json
import os
import os.path as osp
import shutil
import subprocess
import sys
import time

import pytest

REPO = osp.dirname(osp.dirname(osp.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402

DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}

# a stub child: notes who it is and when it ran, then exits with argv[2]
_CHILD = ("import json, os, sys, time; t0 = time.time(); time.sleep(0.3); "
          "open(sys.argv[1], 'w').write(json.dumps({'pid': os.getpid(), "
          "'t0': t0, 't1': time.time()})); sys.exit(int(sys.argv[2]))")


def stub_phase(name, rc=0):
    def phase(ctx):
        cs.run_child(ctx, name, [sys.executable, "-c", _CHILD,
                                 ctx.path(f"{name}.json"), str(rc)])
        if name == "probe":
            ctx.device = dict(DEVICE)
        return {"stub": name}

    return name, phase


def make_ctx(tmp):
    return cs.make_context(osp.join(tmp, "work"), osp.join(tmp, "logs"))


def run_stub_phases(tmp, failing=None):
    """(exit code, per-phase child notes) of a stubbed four-phase run."""
    ctx = make_ctx(tmp)
    names = ["probe", "train", "eval", "serve"]
    rc = cs.run_phases(ctx, [stub_phase(n, 3 if n == failing else 0)
                             for n in names])
    notes = {}
    for n in names:
        if osp.exists(ctx.path(f"{n}.json")):
            with open(ctx.path(f"{n}.json")) as f:
                notes[n] = json.load(f)
    return rc, notes


def test_parent_loads_no_jax(tmp_path):
    code = ("import sys; sys.path.insert(0, sys.argv[1]); "
            "import test_chip_smoke as t; "
            "rc, notes = t.run_stub_phases(sys.argv[2]); "
            "assert rc == 0 and len(notes) == 4, (rc, notes); "
            "bad = [m for m in sys.modules if m == 'jax' or "
            "m.startswith(('jax.', 'jaxlib'))]; "
            "assert not bad, bad")
    r = subprocess.run([sys.executable, "-c", code, osp.dirname(__file__),
                        str(tmp_path)], capture_output=True, text=True,
                       timeout=120)
    assert r.returncode == 0, r.stderr


def test_phases_run_one_after_another_as_separate_processes(tmp_path):
    rc, notes = run_stub_phases(str(tmp_path))
    assert rc == 0
    order = [notes[n] for n in ("probe", "train", "eval", "serve")]
    pids = {n["pid"] for n in order}
    assert len(pids) == 4 and os.getpid() not in pids
    for before, after in zip(order, order[1:]):
        assert before["t1"] <= after["t0"]  # never two at once


def test_failing_phase_exits_nonzero_and_prints_no_result(tmp_path, capsys):
    rc, notes = run_stub_phases(str(tmp_path), failing="eval")
    out = capsys.readouterr().out
    assert rc != 0
    assert '"ok"' not in out
    assert "phase eval: FAILED" in out
    assert "serve" not in notes  # nothing runs after a failure


def test_last_line_is_exactly_the_result(tmp_path, capsys):
    rc, _ = run_stub_phases(str(tmp_path))
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert rc == 0
    assert last == ('{"ok": true, "device": {"platform": "tpu", '
                    '"kind": "TPU v5 lite", "count": 1}}')
    rec = json.loads(last)
    assert set(rec) == {"ok", "device"}
    assert set(rec["device"]) == {"platform", "kind", "count"}


def _copy_of_the_script(tmp_path, with_package):
    shutil.copy(osp.join(REPO, "chip_smoke.py"), tmp_path)
    if with_package:  # enough to get past the checkout check
        os.makedirs(tmp_path / "dexiraft_tpu")
    return [sys.executable, str(tmp_path / "chip_smoke.py")]


def test_as_a_command_on_cpu_it_refuses(tmp_path):
    """The real probe child on this host: JAX finds the CPU, the run is
    refused before any phase, non-zero, no result."""
    r = subprocess.run(_copy_of_the_script(tmp_path, with_package=True),
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "refusing to run" in r.stdout
    assert '"ok"' not in r.stdout


def test_alone_in_a_directory_it_exits_nonzero(tmp_path):
    r = subprocess.run(_copy_of_the_script(tmp_path, with_package=False),
                       capture_output=True, text=True, timeout=60)
    assert r.returncode != 0
    assert r.stdout == ""


def test_no_switch_lifts_the_platform_refusal():
    for argv in (["--platform", "cpu"], ["--cpu"], ["--chips", "2"]):
        with pytest.raises(SystemExit) as e:
            cs.main(argv)
        assert e.value.code == 2


def test_a_child_on_another_platform_fails_its_phase(tmp_path):
    ctx = make_ctx(str(tmp_path))
    line = '[train] device: {"platform": "cpu", "device_kind": "cpu"}'
    with pytest.raises(cs.PhaseFailed, match="ran on 'cpu'"):
        cs.banner(f"noise\n{line}\nmore", "train", ctx)
    with pytest.raises(cs.PhaseFailed, match="no device banner"):
        cs.banner("noise", "train", ctx)


def test_a_hung_child_is_killed_and_fails_its_phase(tmp_path):
    ctx = make_ctx(str(tmp_path))
    ctx.sizes = cs.Sizes(phase_timeout_s=1.0)
    t0 = time.monotonic()
    with pytest.raises(cs.PhaseFailed, match="no exit"):
        cs.run_child(ctx, "hang", [sys.executable, "-c",
                                   "import time; time.sleep(600)"])
    assert time.monotonic() - t0 < 30
    assert all(p.poll() is not None for p in ctx.children)


def test_spawners_hold_each_child_to_its_chip(monkeypatch):
    """`router --spawn` / `serve_bench --fleet` replicas: child i gets
    chip i through the TPU runtime's environment, nothing else changed."""
    from dexiraft_tpu import router_cli

    seen = {}
    monkeypatch.setattr(router_cli.subprocess, "Popen",
                        lambda argv, env, **kw: seen.update(env=env, kw=kw))
    router_cli.spawn_replica(8103, ["--small"], chip=2, env={"KEEP": "1"})
    assert seen["env"] == {"KEEP": "1", "TPU_VISIBLE_CHIPS": "2",
                           "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                           "TPU_PROCESS_BOUNDS": "1,1,1"}


@pytest.mark.parametrize("chips,n,refused", [(4, 4, False), (4, 5, True),
                                             (1, 2, True), (0, 8, False)])
def test_more_model_processes_than_chips_is_refused(monkeypatch, chips, n,
                                                    refused):
    """On a TPU host the extra children could not get a chip; a host
    with no TPU device files (this one) is not held to a count."""
    from dexiraft_tpu import chips as chips_mod

    monkeypatch.setattr(chips_mod, "local_chip_count", lambda: chips)
    if refused:
        with pytest.raises(SystemExit, match="one process at a time"):
            chips_mod.refuse_more_than_chips(n, "serve --workers")
    else:
        chips_mod.refuse_more_than_chips(n, "serve --workers")


@pytest.mark.parametrize("env_dir,cwd", [("outside", "tmp"),
                                         (None, "tmp"), (None, "repo")])
def test_compile_cache_has_one_owner(tmp_path, monkeypatch, env_dir, cwd):
    """JAX_COMPILATION_CACHE_DIR set: no directory is set in code. Unset:
    the same absolute in-checkout path from any working directory."""
    import jax

    from dexiraft_tpu import profiling

    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    monkeypatch.chdir(tmp_path if cwd == "tmp" else REPO)
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / env_dir))
        assert profiling.enable_persistent_cache() == str(tmp_path / env_dir)
        assert "jax_compilation_cache_dir" not in updates
        assert not (tmp_path / env_dir).exists()  # JAX's to make, not ours
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = osp.join(REPO, ".jax_cache")
        assert profiling.enable_persistent_cache() == want
        assert updates["jax_compilation_cache_dir"] == want
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0


@pytest.mark.slow
def test_real_phases_tiny_on_cpu(tmp_path):
    """The rehearsal: the real train, eval and serve phases through the
    real entry points, small (v5 --small, 64x96, 2 iterations), on the
    CPU — wrong paths, arguments and control flow show here at no chip
    time. On the CPU `auto` resolves to allpairs, so the kernel checks
    are the chip run's."""
    tiny = cs.Sizes(small=True, chairs_hw=(96, 128), crop_hw=(64, 96),
                    sintel_hw=(60, 96), train_batch=4, train_steps=2,
                    train_iters=2, eval_iters=2)
    ctx = cs.make_context(str(tmp_path / "work"), str(tmp_path / "logs"),
                          tiny, platform="cpu")
    ctx.env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    ctx.env.pop("XLA_FLAGS", None)  # conftest's 8 virtual devices
    assert cs.run_phases(ctx, cs.ONE_CHIP_PHASES) == 0
