"""Resilience layer, component level: fault-tolerant loader (retry /
skip / worker-death recovery), exact stream positioning, verified
restore with truncated-checkpoint fallback, retention GC, guard
messages, serve-input validation, actionable missing-checkpoint errors.

Named test_zz* so the file sorts AFTER the whole existing suite: the
tier-1 870s wall-clock cap kills the tail of the run, and new tests must
be the ones displaced, never the seed suite's.
"""

import json
import os

import numpy as np
import pytest

from dexiraft_tpu.data.loader import Loader, PipelineStats
from dexiraft_tpu.resilience import chaos
from dexiraft_tpu.resilience.stream import (
    StreamPosition,
    load_position,
    save_position,
)

DS = chaos.SyntheticFlowDataset(n=8, size=(8, 8))


def _take(loader_iter, n):
    out = [next(loader_iter) for _ in range(n)]
    loader_iter.close()
    return out


def _assert_batches_equal(a, b):
    for x, y in zip(a, b):
        assert x.keys() == y.keys()
        for k in x:
            np.testing.assert_array_equal(x[k], y[k])


class TestStreamPosition:
    def test_advance_wraps_epochs(self):
        p = StreamPosition(0, 0).advance(7, 4)
        assert (p.epoch, p.offset) == (1, 3)
        assert StreamPosition(2, 3).advance(1, 4) == StreamPosition(3, 0)

    def test_sidecar_roundtrip_and_missing(self, tmp_path):
        d = str(tmp_path)
        save_position(d, 500, StreamPosition(2, 7), seed=9)
        assert load_position(d, 500) == StreamPosition(2, 7)
        assert load_position(d, 123) is None  # absent -> epoch-0 resume

    def test_seed_mismatch_warns(self, tmp_path, capsys):
        d = str(tmp_path)
        save_position(d, 1, StreamPosition(0, 1), seed=1)
        assert load_position(d, 1, seed=2) == StreamPosition(0, 1)
        assert "seed" in capsys.readouterr().out


class TestLoaderExactPositioning:
    def test_start_offset_reproduces_stream(self):
        """batches(start_epoch=e, start_offset=o) must yield the EXACT
        continuation an uninterrupted stream produces — the property the
        checkpointed position relies on."""
        ref = _take(Loader(DS, 2, num_workers=1).batches(), 11)
        for consumed in (3, 4, 9):
            pos = StreamPosition().advance(consumed, 4)
            resumed = _take(
                Loader(DS, 2, num_workers=1).batches(
                    start_epoch=pos.epoch, start_offset=pos.offset),
                2)
            _assert_batches_equal(resumed, ref[consumed:consumed + 2])

    def test_offset_past_epoch_end_normalizes(self):
        ref = _take(Loader(DS, 2, num_workers=1).batches(), 7)
        resumed = _take(
            Loader(DS, 2, num_workers=1).batches(start_epoch=0,
                                                 start_offset=6), 1)
        _assert_batches_equal(resumed, ref[6:7])


class TestDecodeFaults:
    def test_permanent_corruption_skips_and_counts(self, capsys):
        bad = chaos.CorruptSampleDataset(DS, [0, 5])
        loader = Loader(bad, 2, num_workers=1, max_retries=1,
                        retry_backoff_s=0.001)
        got = _take(loader.batches(), 8)  # two epochs: both bad indices hit
        assert all(b["image1"].shape == (2, 8, 8, 3) for b in got)
        assert loader.stats.skipped_samples >= 2
        assert loader.stats.retries >= 2
        assert "skipping" in capsys.readouterr().out

    def test_transient_corruption_retries_to_bit_parity(self):
        flaky = chaos.CorruptSampleDataset(DS, [1, 6], fail_times=1)
        loader = Loader(flaky, 2, num_workers=1, max_retries=3,
                        retry_backoff_s=0.001)
        got = _take(loader.batches(), 4)
        assert loader.stats.retries >= 1
        assert loader.stats.skipped_samples == 0
        _assert_batches_equal(got, _take(Loader(DS, 2,
                                                num_workers=1).batches(), 4))

    def test_dropped_batch_never_desyncs_published_positions(self):
        """The loader publishes each yielded batch's true (epoch,
        offset); a dropped batch must NOT occupy a slot — resuming from
        the published position must reproduce the yielded stream (the
        trainer's exact-resume bookkeeping relies on this)."""
        # unshuffled, indices 0+1 corrupt -> every epoch's batch 0 dies
        # wholesale while batches 1..3 survive
        bad = chaos.CorruptSampleDataset(DS, [0, 1])
        loader = Loader(bad, 2, num_workers=1, shuffle=False,
                        max_retries=0, retry_backoff_s=0.001)
        it = loader.batches()
        got = [next(it) for _ in range(6)]
        positions = list(loader.positions)
        it.close()
        # unshuffled: indices 0,1 form batch (0,0) which drops entirely
        assert loader.stats.dropped_batches >= 1
        assert positions[0] == (0, 1)  # batch (0,0) never published
        assert len(positions) == len(got)
        # every published position replays to the exact same batch
        pos_epoch, pos_offset = positions[3]
        replay = _take(Loader(DS, 2, num_workers=1, shuffle=False).batches(
            start_epoch=pos_epoch, start_offset=pos_offset), 1)
        _assert_batches_equal(replay, got[3:4])

    def test_all_samples_failing_drops_batches_not_run(self):
        """Epoch 0 is entirely corrupt (every sample fails its single
        attempt); epoch 1 decodes fine. The stream must DROP the four
        doomed batches and keep going — the first batch that arrives is
        epoch 1's first."""
        bad = chaos.CorruptSampleDataset(DS, range(8), fail_times=1)
        loader = Loader(bad, 2, num_workers=1, max_retries=0,
                        retry_backoff_s=0.001)
        got = _take(loader.batches(), 2)
        assert loader.stats.dropped_batches == 4
        assert loader.stats.skipped_samples == 8
        ref = _take(Loader(DS, 2, num_workers=1).batches(start_epoch=1), 2)
        _assert_batches_equal(got, ref)

    def test_logger_surfaces_pipeline_counts(self, capsys):
        from dexiraft_tpu.train.logger import Logger

        stats = PipelineStats()
        stats.skipped_samples = 3
        stats.worker_restarts = 1
        stats.retries = 4
        logger = Logger(sum_freq=1, pipeline_stats=stats)
        logger.push({"loss": 1.0})
        out = capsys.readouterr().out
        assert "pipeline: 3 skipped" in out and "1 worker restarts" in out

    def test_logger_jsonl_carries_pipeline_fields(self, tmp_path):
        from dexiraft_tpu.train.logger import Logger

        stats = PipelineStats()
        stats.skipped_samples = 2
        logger = Logger(sum_freq=1, log_dir=str(tmp_path),
                        tensorboard=False, pipeline_stats=stats)
        logger.push({"loss": 1.0})
        logger.close()
        rec = json.loads((tmp_path / "metrics.jsonl").read_text().splitlines()[0])
        assert rec["pipeline/skipped_samples"] == 2


class TestWorkerDeath:
    def test_process_pool_rebuilds_and_batches_match(self):
        import tempfile

        with tempfile.TemporaryDirectory() as sentinels:
            killer = chaos.WorkerDeathDataset(DS, [1], sentinels)
            loader = Loader(killer, 2, num_workers=1, worker_mode="process",
                            mp_start_method="spawn", max_retries=3,
                            retry_backoff_s=0.01)
            got = _take(loader.batches(), 4)
        assert loader.stats.worker_restarts >= 1
        _assert_batches_equal(got, _take(Loader(DS, 2,
                                                num_workers=1).batches(), 4))


def _toy_state():
    """A real TrainState with toy leaves — checkpoint plumbing without a
    model init (keeps these tests off the 870s budget's radar)."""
    import jax.numpy as jnp

    from dexiraft_tpu.train.state import TrainState

    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params={"w": jnp.arange(512, dtype=jnp.float32).reshape(32, 16),
                "b": jnp.ones((16,), jnp.float32)},
        batch_stats={},
        opt_state={"m": jnp.zeros((32, 16), jnp.float32)},
        rng=jnp.zeros((2,), jnp.uint32),
    )


class TestVerifiedRestore:
    def test_truncated_newest_falls_back(self, tmp_path, capsys):
        from dexiraft_tpu.resilience import restore_verified
        from dexiraft_tpu.train import checkpoint as ckpt

        d = str(tmp_path / "ck")
        state = _toy_state()
        ckpt.save_checkpoint(d, state, step=1)
        ckpt.save_checkpoint(d, state.replace(
            params={"w": state.params["w"] + 1, "b": state.params["b"]}),
            step=2)
        assert chaos.truncate_checkpoint(d, 2)
        restored, got = restore_verified(d, state)
        assert got == 1
        np.testing.assert_array_equal(np.asarray(restored.params["w"]),
                                      np.asarray(state.params["w"]))
        out = capsys.readouterr().out
        assert "failed verification" in out and "restored step 1" in out
        # the damaged step must be GONE: orbax save() onto an existing
        # step dir silently no-ops, so leaving it would swallow the
        # re-save when retraining reaches step 2 again
        assert ckpt.all_steps(d) == [1]
        ckpt.save_checkpoint(d, state, step=2)
        re_restored, got = restore_verified(d, state, verbose=False)
        assert got == 2
        np.testing.assert_array_equal(np.asarray(re_restored.params["w"]),
                                      np.asarray(state.params["w"]))

    def test_nonfinite_checkpoint_rejected(self, tmp_path):
        import jax.numpy as jnp

        from dexiraft_tpu.resilience import (CheckpointIntegrityError,
                                             restore_verified, verify_state)
        from dexiraft_tpu.train import checkpoint as ckpt

        state = _toy_state()
        poisoned = state.replace(
            params={"w": jnp.full((32, 16), jnp.nan, jnp.float32),
                    "b": state.params["b"]})
        with pytest.raises(CheckpointIntegrityError, match="non-finite"):
            verify_state(poisoned, state)

        d = str(tmp_path / "ck")
        ckpt.save_checkpoint(d, state, step=1)
        ckpt.save_checkpoint(d, poisoned, step=2)
        _, got = restore_verified(d, state, verbose=False)
        assert got == 1  # the poisoned newest step was skipped
        assert ckpt.all_steps(d) == [1]  # ...and deleted (re-savable)

    def test_all_bad_raises_integrity_error(self, tmp_path):
        from dexiraft_tpu.resilience import (CheckpointIntegrityError,
                                             restore_verified)
        from dexiraft_tpu.train import checkpoint as ckpt

        d = str(tmp_path / "ck")
        state = _toy_state()
        ckpt.save_checkpoint(d, state, step=1)
        assert chaos.truncate_checkpoint(d, 1)
        with pytest.raises(CheckpointIntegrityError, match="no restorable"):
            restore_verified(d, state, verbose=False)
        # total loss: nothing is deleted (forensics beat tidiness)
        assert os.path.isdir(os.path.join(d, "1"))


class TestRetention:
    def test_keep_window_and_sidecar_gc(self, tmp_path):
        from dexiraft_tpu.resilience import RetentionPolicy
        from dexiraft_tpu.train import checkpoint as ckpt

        d = str(tmp_path / "ck")
        state = _toy_state()
        for s in (1, 2, 3, 4):
            ckpt.save_checkpoint(d, state, step=s)
            save_position(d, s, StreamPosition(0, s))
        policy = RetentionPolicy(keep=2)
        deleted = policy.apply(d)
        assert deleted == [1, 2]
        assert ckpt.all_steps(d) == [3, 4]
        assert load_position(d, 1) is None
        assert load_position(d, 4) is not None

    def test_keep_best_survives_window(self, tmp_path):
        from dexiraft_tpu.resilience import RetentionPolicy
        from dexiraft_tpu.train import checkpoint as ckpt

        d = str(tmp_path / "ck")
        state = _toy_state()
        policy = RetentionPolicy(keep=1, keep_best=True)
        for s, epe in ((1, 5.0), (2, 1.0), (3, 9.0)):
            ckpt.save_checkpoint(d, state, step=s)
            policy.note_score(s, epe)
        policy.apply(d, protect=(3,))
        assert ckpt.all_steps(d) == [2, 3]  # best (2) + newest (3)

    def test_protect_beats_window(self, tmp_path):
        from dexiraft_tpu.resilience import RetentionPolicy
        from dexiraft_tpu.train import checkpoint as ckpt

        d = str(tmp_path / "ck")
        state = _toy_state()
        for s in (1, 2, 3):
            ckpt.save_checkpoint(d, state, step=s)
        RetentionPolicy(keep=1).apply(d, protect=(1,))
        assert ckpt.all_steps(d) == [1, 3]

    def test_keep_best_scores_survive_restart(self, tmp_path):
        """--keep_best is a promise about a multi-restart run: a policy
        rebuilt after preemption (fresh process, empty memory) must
        still protect the best step recorded BEFORE the restart."""
        from dexiraft_tpu.resilience import RetentionPolicy
        from dexiraft_tpu.train import checkpoint as ckpt

        d = str(tmp_path / "ck")
        state = _toy_state()
        first = RetentionPolicy(keep=1, keep_best=True, directory=d)
        for s, epe in ((1, 5.0), (2, 1.0)):
            ckpt.save_checkpoint(d, state, step=s)
            first.note_score(s, epe)

        # simulate the relaunch: a brand-new policy over the same dir
        resumed = RetentionPolicy(keep=1, keep_best=True, directory=d)
        assert resumed.best_step() == 2
        ckpt.save_checkpoint(d, state, step=3)
        resumed.apply(d, protect=(3,))
        assert ckpt.all_steps(d) == [2, 3]  # best survived the restart

    def test_pool_not_rebuilt_after_close(self):
        """Closing the batch stream while the feeder still has
        submissions in flight must not resurrect the worker pool (a
        leak) nor count phantom worker restarts."""
        from dexiraft_tpu.data.loader import _PoolManager

        loader = Loader(DS, 2, num_workers=1)
        pools = _PoolManager(loader)
        pools.shutdown()
        pools.rebuild(0)  # the race: a post-shutdown observer
        assert loader.stats.worker_restarts == 0
        fut = pools.submit(0, 0)  # must not spin up a fresh pool either
        with pytest.raises(Exception):
            fut.result()
        assert loader.stats.worker_restarts == 0

    def test_keep_zero_is_noop(self, tmp_path):
        from dexiraft_tpu.resilience import RetentionPolicy
        from dexiraft_tpu.train import checkpoint as ckpt

        d = str(tmp_path / "ck")
        ckpt.save_checkpoint(d, _toy_state(), step=1)
        assert RetentionPolicy(keep=0).apply(d) == []
        assert ckpt.all_steps(d) == [1]


class TestGuardMessages:
    def test_rollback_message_names_dir_and_step(self):
        from dexiraft_tpu.train.guard import DivergenceGuard

        g = DivergenceGuard(max_rollbacks=2)
        msg = g.consume_rollback(float("nan"), True, "step 7", 5,
                                 ckpt_dir="ckpts/run")
        assert "ckpts/run" in msg and "step 5" in msg and "1/2" in msg

    def test_abort_message_names_last_good_checkpoint(self):
        from dexiraft_tpu.train.guard import DivergenceGuard

        g = DivergenceGuard(max_rollbacks=0)
        with pytest.raises(RuntimeError,
                           match=r"ckpts/run step 5"):
            g.consume_rollback(1e9, True, "step 7", 5, ckpt_dir="ckpts/run")


class TestServeInputValidation:
    def _engine(self, batch_size=1):
        from dexiraft_tpu.serve import InferenceEngine, ServeConfig

        def fake_eval(im1, im2, fi):
            b, h, w, _ = np.asarray(im1).shape
            return (np.zeros((b, h // 8, w // 8, 2), np.float32),
                    np.zeros((b, h, w, 2), np.float32))

        return InferenceEngine(fake_eval,
                               ServeConfig(batch_size=batch_size),
                               put=lambda x: x)

    def test_good_item_passes(self):
        eng = self._engine()
        item = {"image1": np.zeros((16, 24, 3), np.float32),
                "image2": np.zeros((16, 24, 3), np.float32)}
        out = eng.run_batch([item])
        assert out[0].flow_up.shape == (16, 24, 2)

    def test_array_like_input_normalized_not_crashed(self):
        """A nested-list frame is a valid array-like: validation
        normalizes it in place (np.asarray written back) instead of
        letting it pass the checks and crash on `.shape` downstream."""
        eng = self._engine()
        frame = np.zeros((16, 24, 3), np.float32)
        item = {"image1": frame.tolist(), "image2": frame.tolist()}
        out = eng.run_batch([item])
        assert out[0].flow_up.shape == (16, 24, 2)

    @pytest.mark.parametrize("mutate,match", [
        (lambda it: it.pop("image2"), "missing"),
        (lambda it: it.update(image1=np.zeros((16, 24), np.float32)),
         "rank-3"),
        (lambda it: it.update(image2=np.zeros((16, 24, 4), np.float32)),
         "3 channels"),
        (lambda it: it.update(image1=np.zeros((16, 24, 3), bool)),
         "dtype"),
        (lambda it: it.update(image2=np.zeros((8, 24, 3), np.float32)),
         "must agree"),
        (lambda it: it.update(flow_init=np.zeros((2, 3, 7), np.float32)),
         "flow_init"),
    ])
    def test_malformed_items_rejected_up_front(self, mutate, match):
        eng = self._engine()
        item = {"image1": np.zeros((16, 24, 3), np.float32),
                "image2": np.zeros((16, 24, 3), np.float32)}
        mutate(item)
        with pytest.raises(ValueError, match=match):
            eng.run_batch([item])
        with pytest.raises(ValueError, match=match):
            list(eng.stream([item]))


class TestMissingCheckpointErrors:
    def test_require_checkpoints_lists_candidates(self, tmp_path):
        from dexiraft_tpu.train import checkpoint as ckpt

        good = tmp_path / "raft-chairs"
        (good / "100").mkdir(parents=True)
        with pytest.raises(FileNotFoundError) as ei:
            ckpt.require_checkpoints(str(tmp_path / "raft-chair"))
        msg = str(ei.value)
        assert "raft-chair" in msg and "raft-chairs" in msg
        assert "\n" not in msg  # ONE line, not a traceback wall
        # probing must not have created the missing dir
        assert not (tmp_path / "raft-chair").exists()

    def test_eval_cli_missing_model_exits_cleanly(self, tmp_path):
        from dexiraft_tpu.eval_cli import build_parser, load_variables

        args = build_parser().parse_args(
            ["--model", str(tmp_path / "nope"), "--dataset", "chairs"])
        with pytest.raises(SystemExit, match="no checkpoints under"):
            load_variables(args)


# --- pod-grade additions: async saves, consensus, watchdog ----------------


class TestAsyncCheckpoint:
    def test_async_save_returns_before_flush_commits(self, tmp_path):
        import threading

        from dexiraft_tpu.train import checkpoint as ckpt

        d = str(tmp_path / "ck")
        state = _toy_state()
        hold = threading.Event()
        ckpt.flush_hold = hold
        try:
            ckpt.save_checkpoint(d, state, step=1, block=False)
            # the flush is provably in flight (held), yet save returned
            assert ckpt.pending_step(d) == 1
            threading.Timer(0.05, hold.set).start()
            info = ckpt.wait_pending(d)
        finally:
            ckpt.flush_hold = None
        assert info["step"] == 1 and info["error"] is None
        # two threads' clocks: each ran, neither is compared with the other
        assert info["flush_s"] > 0 and info["blocked_s"] > 0
        stats = ckpt.save_stats(d)
        assert stats["saves"] == 1 and stats["failed"] == 0
        assert ckpt.all_steps(d) == [1]

    def test_poisoned_verdict_during_inflight_flush(self, tmp_path):
        """The guard+save interleaving contract: a poisoned loss arriving
        while a previous (guard-checked, good) flush is still in flight
        neither commits the poisoned state nor orphans the in-flight
        save — the rollback barrier commits it, then restores it."""
        import threading

        import jax.numpy as jnp

        from dexiraft_tpu.resilience import restore_verified
        from dexiraft_tpu.train import checkpoint as ckpt
        from dexiraft_tpu.train.guard import DivergenceGuard

        d = str(tmp_path / "ck")
        good1 = _toy_state()
        good2 = good1.replace(
            step=jnp.int32(2),
            params={"w": good1.params["w"] + 1, "b": good1.params["b"]})
        ckpt.save_checkpoint(d, good1, step=1)  # committed baseline

        hold = threading.Event()
        ckpt.flush_hold = hold
        try:
            # step 2's guard verdict was taken BEFORE this handoff
            ckpt.save_checkpoint(d, good2, step=2, block=False)
            last_saved = 2
            # ... two steps later the loss explodes: train_cli's rollback
            # discipline — guard verdict, then barrier, then restore
            guard = DivergenceGuard(threshold=1e4)
            assert guard.poisoned(float("nan"), True)
            assert ckpt.pending_step(d) == 2  # flush genuinely in flight
            threading.Timer(0.05, hold.set).start()
            state, restored = restore_verified(d, good1, step=last_saved,
                                               verbose=False)
        finally:
            ckpt.flush_hold = None
        # the in-flight save was NOT orphaned: the barrier inside the
        # restore path committed it, and the rollback landed on it
        assert restored == 2
        np.testing.assert_array_equal(np.asarray(state.params["w"]),
                                      np.asarray(good2.params["w"]))
        # and the poisoned state never reached disk at all
        assert ckpt.all_steps(d) == [1, 2]

    def test_crash_mid_flush_debris_cleaned_and_prior_step_restores(
            self, tmp_path, capsys):
        from dexiraft_tpu.resilience import (
            restore_verified,
            uncommitted_flushes,
        )
        from dexiraft_tpu.train import checkpoint as ckpt

        d = str(tmp_path / "ck")
        state = _toy_state()
        ckpt.save_checkpoint(d, state, step=3)
        # what a kill mid-flush leaves behind: an uncommitted orbax tmp
        # dir for the NEXT step (the rename-commit never happened)
        debris = tmp_path / "ck" / "4.orbax-checkpoint-tmp-123456"
        debris.mkdir()
        (debris / "partial").write_bytes(b"x" * 64)
        assert uncommitted_flushes(d) == [debris.name]
        # a READER (serve/eval) reports the debris but must never
        # delete it — it may be another process's live in-flight flush
        restored, got = restore_verified(d, state)
        assert got == 3
        assert uncommitted_flushes(d) == [debris.name]
        assert "left in place" in capsys.readouterr().out
        # the WRITER recovering its own directory sweeps it
        restored, got = restore_verified(d, state, clean_debris=True)
        assert got == 3  # the prior committed step is the latest
        assert uncommitted_flushes(d) == []  # debris reported + removed
        assert "uncommitted flush" in capsys.readouterr().out
        assert ckpt.all_steps(d) == [3]

    def test_failed_flush_reports_and_never_raises(self, tmp_path,
                                                   monkeypatch, capsys):
        from dexiraft_tpu.train import checkpoint as ckpt

        d = str(tmp_path / "ck")

        def boom(key, step, host_state, t0):
            raise OSError("disk on fire")

        monkeypatch.setattr(ckpt, "_flush", boom)
        ckpt.save_checkpoint(d, _toy_state(), step=5, block=False)
        info = ckpt.wait_pending(d)
        assert info["error"] and "disk on fire" in info["error"]
        assert "FAILED" in capsys.readouterr().out
        assert ckpt.save_stats(d)["failed"] == 1
        # a BLOCKING save keeps the historical contract: it raises at
        # the call site, so callers never bookkeep an uncommitted step
        with pytest.raises(OSError, match="disk on fire"):
            ckpt.save_checkpoint(d, _toy_state(), step=6, block=True)
        # the directory stays usable: nothing committed, reads work
        monkeypatch.undo()
        assert ckpt.latest_step(d) is None

    def test_typed_prng_key_roundtrips_dtype_preserving(self, tmp_path):
        import jax

        from dexiraft_tpu.train import checkpoint as ckpt

        d = str(tmp_path / "ck")
        state = _toy_state().replace(rng=jax.random.key(3))
        ckpt.save_checkpoint(d, state, step=1)
        template = _toy_state().replace(rng=jax.random.key(0))
        restored = ckpt.restore_checkpoint(d, template)
        assert restored.rng.dtype == state.rng.dtype  # key<fry>, not u32
        np.testing.assert_array_equal(
            np.asarray(jax.random.key_data(restored.rng)),
            np.asarray(jax.random.key_data(state.rng)))
        # and the old-style uint32 key path is untouched
        ckpt.save_checkpoint(d, _toy_state(), step=2)
        old = ckpt.restore_checkpoint(d, _toy_state(), step=2)
        assert old.rng.dtype == np.uint32

    def test_chaos_kill_mid_flush_spec_arms_once(self):
        from dexiraft_tpu.resilience import chaos as chaos_lib
        from dexiraft_tpu.train import checkpoint as ckpt

        fire = chaos_lib.parse_spec("kill_mid_flush@3")
        try:
            fire(2)
            assert not ckpt._chaos_kill_next_flush
            fire(3)
            assert ckpt._chaos_kill_next_flush
        finally:
            ckpt._chaos_kill_next_flush = False  # never kill this pytest


class TestDeleteStepLogging:
    def test_manager_refusal_names_step_and_dir(self, tmp_path, capsys):
        from dexiraft_tpu.train import checkpoint as ckpt

        d = str(tmp_path / "ck")
        ckpt.save_checkpoint(d, _toy_state(), step=1)
        ckpt.delete_step(d, 999)  # the manager has no step 999
        out = capsys.readouterr().out
        assert "999" in out and str(d) in out and "failed" in out


class TestPartialRestoreSkipReport:
    def test_full_skip_list_lands_in_sidecar(self, tmp_path, capsys):
        from dexiraft_tpu.train import checkpoint as ckpt

        params = {f"fresh_{i}": np.zeros((2,)) for i in range(12)}
        restored = {f"old_{i}": np.zeros((2,)) for i in range(3)}
        merged, skipped = ckpt.restore_params_into(
            params, restored, verbose=True,
            skipped_report_dir=str(tmp_path))
        assert len(skipped) == 15
        out = capsys.readouterr().out
        assert "15 leaves" in out
        report = tmp_path / "partial_restore_skipped.txt"
        assert str(report) in out
        lines = report.read_text().strip().splitlines()
        assert len(lines) == 15
        assert set(lines) == set(skipped)

    def test_small_skip_list_stays_inline(self, tmp_path, capsys):
        from dexiraft_tpu.train import checkpoint as ckpt

        params = {"a": np.zeros((2,)), "b": np.zeros((3,))}
        merged, skipped = ckpt.restore_params_into(
            params, {"a": np.zeros((5,))}, verbose=True,
            skipped_report_dir=str(tmp_path))
        # 'a' (shape mismatch) and 'b' (missing) both count, inline only
        assert "2 leaves" in capsys.readouterr().out
        assert not (tmp_path / "partial_restore_skipped.txt").exists()


class TestHangWatchdog:
    def _wd(self, tmp_path, timeout=10.0, **kw):
        import io

        from dexiraft_tpu.resilience import HangWatchdog

        clk = [0.0]
        exits = []
        out = open(tmp_path / "wd.log", "w+")
        wd = HangWatchdog(timeout, clock=lambda: clk[0],
                          exit_fn=exits.append, stream=out, **kw)
        return wd, clk, exits, out

    def test_stall_dumps_stacks_and_exits_nonzero(self, tmp_path):
        from dexiraft_tpu.resilience import STALL_EXIT_CODE

        wd, clk, exits, out = self._wd(tmp_path, timeout=10.0)
        wd.arm(42, "step+data")
        clk[0] = 9.0
        assert wd.check_once() is None
        clk[0] = 10.5
        assert wd.check_once() == "stall"
        assert exits == [STALL_EXIT_CODE] and wd.fired
        out.seek(0)
        dump = out.read()
        out.close()
        assert "step 42" in dump and "step+data" in dump
        assert "Thread" in dump  # faulthandler live-stack dump

    def test_straggler_warns_once_on_ewma(self, tmp_path):
        wd, clk, exits, out = self._wd(tmp_path, timeout=100.0,
                                       straggler_factor=10.0)
        # four 1s steps -> EWMA 1s
        for step in range(4):
            wd.arm(step)
            clk[0] += 1.0
            wd.disarm()
        assert wd.ewma_s == pytest.approx(1.0)
        wd.arm(5)
        clk[0] += 11.0  # > 10x EWMA, < timeout
        assert wd.check_once() == "straggler"
        assert wd.check_once() is None  # once per armed region
        assert wd.straggler_warnings == 1 and not exits
        out.seek(0)
        assert "straggler" in out.read()
        out.close()

    def test_sanctioned_windows_stay_out_of_ewma_and_straggler(
            self, tmp_path):
        wd, clk, exits, out = self._wd(tmp_path, timeout=100.0)
        # seed the EWMA with fast steady steps
        for step in range(3):
            wd.arm(step)
            clk[0] += 0.5
            wd.disarm()
        # a sanctioned slow region: no EWMA feed, no straggler warning,
        # and the stall bound is scaled by slow_region_factor (10x) —
        # a legitimate 2-minute validation sweep must not be killed by
        # a step-sized timeout
        wd.arm(9, "checkpoint+validation", steady=False)
        clk[0] += 500.0  # 1000x the EWMA, 5x timeout, < 10x timeout
        assert wd.check_once() is None
        assert wd.straggler_warnings == 0
        assert wd.disarm() is not None
        assert wd.ewma_s == pytest.approx(0.5)
        wd.arm(10, "checkpoint+validation", steady=False)
        clk[0] += 1001.0  # past 10x the timeout: still fires
        assert wd.check_once() == "stall"
        assert exits  # the stall bound is scaled, never waived
        out.close()

    def test_timeout_zero_is_inert(self, tmp_path):
        wd, clk, exits, out = self._wd(tmp_path, timeout=0.0)
        assert not wd.enabled
        wd.arm(1)
        clk[0] = 1e9
        assert wd.check_once() is None and not exits
        assert wd.start()._thread is None  # no monitor thread either
        out.close()


class TestCoordinator:
    def test_single_process_is_identity(self):
        from dexiraft_tpu.resilience import Coordinator

        calls = []
        coord = Coordinator(size=1, index=0,
                            allgather_fn=lambda v: calls.append(v))
        assert coord.any_flag(True) is True
        assert coord.any_flag(False) is False
        assert coord.min_int(7) == 7
        state, step = coord.agree_step(
            lambda b: (("state", b), 4), None)
        assert (state, step) == (("state", None), 4)
        coord.warmup()
        assert calls == []  # never a collective

    def test_any_flag_and_min_over_hosts(self):
        from dexiraft_tpu.resilience import Coordinator

        peers = {"flags": [False, True], "steps": [40, 20]}

        def fake_allgather(v):
            import numpy as _np

            if v.dtype == bool:
                return _np.asarray([[f] for f in peers["flags"]])
            return _np.asarray([[s] for s in peers["steps"]])

        coord = Coordinator(size=2, index=0, allgather_fn=fake_allgather)
        assert coord.any_flag(False) is True  # the PEER's verdict wins
        assert coord.min_int(40) == 20

    def test_agree_step_converges_to_global_min(self):
        from dexiraft_tpu.resilience import Coordinator

        # this host restored 4, the peer only has 2: round 1 agrees on
        # 2, round 2 this host re-restores at 2 and everyone matches
        script = iter([
            np.asarray([[4], [2]]),          # min_int round 1 -> 2
            np.asarray([[True], [False]]),   # any_flag: mismatch
            np.asarray([[2], [2]]),          # min_int round 2 -> 2
            np.asarray([[False], [False]]),  # any_flag: agreed
        ])
        coord = Coordinator(size=2, index=0,
                            allgather_fn=lambda v: next(script))
        restores = []

        def restore_fn(bound):
            restores.append(bound)
            step = 4 if bound is None else min(4, bound)
            return f"state@{step}", step

        state, step = coord.agree_step(restore_fn, None)
        assert (state, step) == ("state@2", 2)
        assert restores == [None, 2]  # re-restored at the agreed min

    def test_agree_step_gives_up_after_max_rounds(self):
        from dexiraft_tpu.resilience import Coordinator

        coord = Coordinator(
            size=2, index=1,
            allgather_fn=lambda v: (np.asarray([[True], [True]])
                                    if v.dtype == bool
                                    else np.asarray([[0], [1]])))
        with pytest.raises(RuntimeError, match="no checkpoint step"):
            coord.agree_step(lambda b: ("s", 1), None, max_rounds=2)
