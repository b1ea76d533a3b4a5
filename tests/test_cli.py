"""CLI integration: train a few steps on a synthetic chairs tree through
the real argparse surface, checkpoint, resume, then eval-restore."""

import numpy as np
import pytest

from dexiraft_tpu.data.flow_io import write_flo


@pytest.fixture()
def chairs_env(tmp_path, monkeypatch):
    import imageio.v2 as imageio

    root = tmp_path / "FlyingChairs_release"
    data = root / "data"
    data.mkdir(parents=True)
    rng = np.random.default_rng(0)
    n = 8
    for i in range(n):
        imageio.imwrite(data / f"{i:05d}_img1.ppm",
                        rng.integers(0, 256, (96, 128, 3), dtype=np.uint8))
        imageio.imwrite(data / f"{i:05d}_img2.ppm",
                        rng.integers(0, 256, (96, 128, 3), dtype=np.uint8))
        write_flo(data / f"{i:05d}_flow.flo",
                  rng.normal(size=(96, 128, 2)).astype(np.float32))
    (root / "chairs_split.txt").write_text("\n".join(["1"] * n))
    monkeypatch.setenv("DEXIRAFT_DATA_DIR", str(tmp_path))
    monkeypatch.chdir(tmp_path)
    return tmp_path


def _train_args(tmp_path, steps, extra=()):
    return [
        "--name", "t", "--stage", "chairs", "--variant", "v1", "--small",
        "--num_steps", str(steps), "--batch_size", "2",
        "--image_size", "64", "64", "--iters", "2", "--lr", "1e-4",
        "--num_workers", "1", "--val_freq", "1000",
        "--output", str(tmp_path / "ckpts"),
        "--log_dir", str(tmp_path / "runs"),
        *extra,
    ]


def test_train_resume_eval_roundtrip(chairs_env, capsys):
    import jax

    from dexiraft_tpu.train_cli import main as train_main
    from dexiraft_tpu.train import checkpoint as ckpt

    tmp = chairs_env
    train_main(_train_args(tmp, 3))
    ckpt_dir = str(tmp / "ckpts" / "t")
    assert ckpt.latest_step(ckpt_dir) == 3
    # what JAX spent before the first step was done, by jitted program:
    # one line, at the first mark_warm and not at the later ones
    (setup,) = [line for line in capsys.readouterr().out.splitlines()
                if line.startswith("[setup] ")]
    assert "program(s)" in setup and "uncached" in setup
    assert (tmp / "runs" / "t" / "metrics.jsonl").exists()

    # resume continues the step counter (full-state restore)
    train_main(_train_args(tmp, 5, extra=["--resume"]))
    assert ckpt.latest_step(ckpt_dir) == 5

    # eval-restore path: variables load and the jitted test-mode forward runs
    from dexiraft_tpu.eval_cli import build_parser, load_variables
    from dexiraft_tpu.train.step import make_eval_step

    args = build_parser().parse_args(
        ["--model", ckpt_dir, "--variant", "v1", "--small",
         "--dataset", "chairs"])
    cfg, variables = load_variables(args)
    step = make_eval_step(cfg, iters=2)
    im = jax.numpy.zeros((1, 64, 64, 3))
    low, up = step(variables, im, im)
    assert up.shape == (1, 64, 64, 2)


def test_divergence_guard_rolls_back_then_aborts(chairs_env):
    """Elastic-recovery guard (absent in the reference, SURVEY.md §5:
    its v3 diverged and kept logging). Poison the dataset after a good
    checkpoint exists: the guard must roll back to it — never saving a
    poisoned state — retry up to --max_rollbacks, then abort loudly."""
    from dexiraft_tpu.train import checkpoint as ckpt
    from dexiraft_tpu.train_cli import main as train_main

    tmp = chairs_env
    train_main(_train_args(tmp, 2))
    ckpt_dir = str(tmp / "ckpts" / "t")
    assert ckpt.latest_step(ckpt_dir) == 2

    # poison every flow file -> every batch from here on yields nan loss
    data = tmp / "FlyingChairs_release" / "data"
    for f in data.glob("*_flow.flo"):
        write_flo(f, np.full((96, 128, 2), np.nan, np.float32))

    with pytest.raises(RuntimeError, match="diverged.*after 2 rollbacks"):
        train_main(_train_args(
            tmp, 6, extra=["--resume", "--guard_every", "1",
                           "--max_rollbacks", "2"]))
    # the poisoned steps never reached disk
    assert ckpt.latest_step(ckpt_dir) == 2


def test_guard_disabled_reproduces_reference_behavior(chairs_env):
    """--no_guard: nan losses train through to completion (what the
    reference always did) — the guard is an opt-out upgrade, not a
    behavior change for anyone who wants the old semantics."""
    from dexiraft_tpu.train import checkpoint as ckpt
    from dexiraft_tpu.train_cli import main as train_main

    tmp = chairs_env
    data = tmp / "FlyingChairs_release" / "data"
    for f in data.glob("*_flow.flo"):
        write_flo(f, np.full((96, 128, 2), np.nan, np.float32))
    train_main(_train_args(tmp, 2, extra=["--no_guard"]))
    assert ckpt.latest_step(str(tmp / "ckpts" / "t")) == 2


def test_eval_cli_edgesum_dispatch(chairs_env, capsys):
    """--dataset edgesum wires through the validator registry: the CLI
    builds the edge-pair chairs-val dataset from --edge_root and
    validate_edgesum runs the dual-pass summed validation."""
    import imageio.v2 as imageio

    tmp = chairs_env
    root = tmp / "FlyingChairs_release"
    # flip the split to validation ("2") and add a parallel edge tree
    (root / "chairs_split.txt").write_text("\n".join(["2"] * 8))
    edge_root = tmp / "edges"
    rng = np.random.default_rng(1)
    for i in range(8):
        for k in (1, 2):
            p = edge_root / "data" / f"{i:05d}_img{k}.png"
            p.parent.mkdir(parents=True, exist_ok=True)
            imageio.imwrite(p, rng.integers(0, 256, (96, 128, 3),
                                            dtype=np.uint8))

    from dexiraft_tpu.eval_cli import _edgesum_dataset
    from dexiraft_tpu.eval.validate import run_validation

    ds = _edgesum_dataset(str(edge_root / "data"))
    assert len(ds) == 8
    fake = lambda im1, im2, flow_init=None: (
        None, np.zeros(im1.shape[:3] + (2,), np.float32))
    out = run_validation("edgesum", fake, ds)
    assert "edgesum" in out and np.isfinite(out["edgesum"])

    # the guard the registry contract requires: no dataset -> clear error
    with pytest.raises(ValueError, match="edge-pair dataset"):
        run_validation("edgesum", fake)


def test_preset_resolution():
    from dexiraft_tpu.train_cli import build_parser, resolve_configs

    args = build_parser().parse_args(
        ["--stage", "sintel", "--preset", "standard", "--variant", "v5"])
    cfg, tc = resolve_configs(args)
    assert cfg.variant == "dual" and cfg.embed_dexined
    assert tc.gamma == 0.85 and tc.freeze_bn and tc.num_steps == 100_000
    assert tc.image_size == (368, 768)

    # explicit overrides win over the preset
    args = build_parser().parse_args(
        ["--stage", "sintel", "--preset", "standard", "--lr", "3e-4"])
    _, tc = resolve_configs(args)
    assert tc.lr == 3e-4
