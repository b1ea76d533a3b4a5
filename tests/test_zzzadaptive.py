"""Adaptive-iteration inference (models/raft._adaptive_refine +
serve scheduler budgets): convergence-gated early exit in the
refinement loop, SLO-driven iteration budgets in the serve tier.

Layers covered, cheapest first:
  * model — converge_tol=0 + full budget is BIT-EXACT vs the fixed
    nn.scan driver (the gate strictly `dn < tol` never fires at 0);
    budget clamp; per-item freeze independence in a mixed batch (the
    damped contraction fixture, docs/perf.md);
  * engine/scheduler/service — numpy stub eval_fn (no jax): Result
    plumbing, budget refusal on fixed engines, the SLO/pressure budget
    policy on a fake clock, conditional stats keys, wire headers;
  * compile discipline — a second dispatch at a different budget rides
    the SAME executable (the traced-int32-scalar contract), proven via
    the engine's RecompileWatch;
  * record schemas — serve_bench ADAPTIVE_* and eval_cli FRONTIER_*
    pins, plus the watchdog stderr filter (bench.make_stderr_filter).

Real-model tests share one module-scoped fixture (v1-small, 40x56,
iters=4 — a handful of tiny CPU compiles). Named test_zzz* to sort
with the tail tests (tier-1 870 s budget convention).
"""

import dataclasses
import json
import os.path as osp
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

REPO = osp.dirname(osp.dirname(osp.abspath(__file__)))

from dexiraft_tpu.serve import (FlowService, InferenceEngine, Scheduler,
                                ServeConfig)
from dexiraft_tpu.serve.server import encode_request

H, W = 40, 56
ITERS = 4


# ---- module fixture: one tiny real model, shared compiles ---------------


@pytest.fixture(scope="module")
def setup():
    import jax

    from dexiraft_tpu.config import TrainConfig, raft_v1
    from dexiraft_tpu.train.state import create_state
    from dexiraft_tpu.train.step import make_eval_step

    cfg = raft_v1(small=True)
    state = create_state(jax.random.PRNGKey(0), cfg, TrainConfig())
    variables = {"params": state.params,
                 "batch_stats": state.batch_stats}

    # the contraction fixture (docs/perf.md): random-init refinement
    # updates do not contract, so the convergence gate never fires;
    # damping the flow head's params x0.01 gives the converging plateau
    # a trained model has, without shipping a checkpoint
    from jax.tree_util import tree_map_with_path

    def _damp(path, leaf):
        keys = [getattr(p, "key", getattr(p, "name", None)) for p in path]
        return leaf * 0.01 if "FlowHead_0" in keys else leaf

    damped = {"params": tree_map_with_path(_damp, variables["params"]),
              "batch_stats": variables["batch_stats"]}

    fixed = make_eval_step(cfg, iters=ITERS)
    adapt0 = make_eval_step(
        dataclasses.replace(cfg, converge_tol=0.0), iters=ITERS,
        adaptive=True)
    adapt = make_eval_step(cfg, iters=ITERS, adaptive=True)  # tol 0.02

    rng = np.random.default_rng(0)

    def frame(seed):
        r = np.random.default_rng(seed)
        return r.uniform(0, 255, (H, W, 3)).astype(np.float32)

    del rng
    return dict(cfg=cfg, variables=variables, damped=damped,
                fixed=fixed, adapt0=adapt0, adapt=adapt, frame=frame)


def _get(x):
    import jax

    return jax.device_get(x)


# ---- model: parity, clamp, freeze ---------------------------------------


class TestAdaptiveRefine:
    def test_tol_zero_full_budget_bit_exact_vs_scan(self, setup):
        a, b = setup["frame"](1)[None], setup["frame"](2)[None]
        low_f, up_f = setup["fixed"](setup["variables"], a, b)
        low_a, up_a, iu, fd = setup["adapt0"](
            setup["variables"], a, b, iter_budget=np.int32(ITERS))
        # strict `dn < tol` with tol=0 NEVER fires: every item runs the
        # full budget and the while_loop must reproduce the scan's
        # arithmetic exactly — parity is the correctness anchor the
        # whole perf win hangs off
        assert np.array_equal(_get(up_f), _get(up_a))
        assert np.array_equal(_get(low_f), _get(low_a))
        assert _get(iu).tolist() == [ITERS]
        assert float(_get(fd)[0]) > 0.0

    def test_budget_clamped_to_configured_iters(self, setup):
        a, b = setup["frame"](1)[None], setup["frame"](2)[None]
        _, up_full, iu_full, _ = setup["adapt0"](
            setup["variables"], a, b, iter_budget=np.int32(ITERS))
        _, up_hi, iu_hi, _ = setup["adapt0"](
            setup["variables"], a, b, iter_budget=np.int32(100))
        assert _get(iu_hi).tolist() == [ITERS]   # clamped, not overrun
        assert np.array_equal(_get(up_full), _get(up_hi))

    def test_partial_budget_runs_exactly_budget_iters(self, setup):
        a, b = setup["frame"](1)[None], setup["frame"](2)[None]
        _, up2, iu, _ = setup["adapt0"](
            setup["variables"], a, b, iter_budget=np.int32(2))
        assert _get(iu).tolist() == [2]
        _, up4, _, _ = setup["adapt0"](
            setup["variables"], a, b, iter_budget=np.int32(ITERS))
        # fewer refinement steps = a genuinely different flow
        assert not np.array_equal(_get(up2), _get(up4))

    def test_converged_item_freezes_early(self, setup):
        # damped params converge below tol=0.02 after one update (the
        # measured plateau is ~4e-5) — the gate must stop the loop and
        # leave the flow exactly where iteration 1 put it
        a, b = setup["frame"](1)[None], setup["frame"](2)[None]
        _, up, iu, fd = setup["adapt"](
            setup["damped"], a, b, iter_budget=np.int32(ITERS))
        used = int(_get(iu)[0])
        assert used < ITERS, "early exit never fired"
        assert float(_get(fd)[0]) < setup["cfg"].converge_tol
        _, up_ref, _, _ = setup["adapt0"](
            setup["damped"], a, b, iter_budget=np.int32(used))
        np.testing.assert_allclose(_get(up), _get(up_ref),
                                   rtol=0, atol=1e-6)

    def test_mixed_batch_rows_freeze_independently(self, setup):
        # per-row done mask: batching two items must reproduce each
        # item's solo convergence (iterations applied AND flow) — a
        # leaked freeze mask would let a done row keep integrating or
        # stop its neighbor
        f1, f2 = setup["frame"](1), setup["frame"](2)
        f3, f4 = setup["frame"](3), setup["frame"](4)
        solo = [setup["adapt"](setup["damped"], x[None], y[None],
                               iter_budget=np.int32(ITERS))
                for x, y in ((f1, f2), (f3, f4))]
        _, up_b, iu_b, fd_b = setup["adapt"](
            setup["damped"], np.stack([f1, f3]), np.stack([f2, f4]),
            iter_budget=np.int32(ITERS))
        for row in range(2):
            _, up_s, iu_s, fd_s = solo[row]
            assert int(_get(iu_b)[row]) == int(_get(iu_s)[0])
            np.testing.assert_allclose(float(_get(fd_b)[row]),
                                       float(_get(fd_s)[0]), atol=1e-6)
            np.testing.assert_allclose(_get(up_b)[row], _get(up_s)[0],
                                       rtol=0, atol=1e-4)

    def test_flash_kernel_under_the_while_loop(self, setup, monkeypatch):
        """The adaptive driver reads `coords1` differences and freezes
        carry rows outside `RAFTStep`, on a carry the step holds as
        planes down to the kernel's coordinate operand (ISSUE 36): with
        flash + fused (what `auto` serves on a TPU; interpreted here) a
        full budget at tol 0 is the scan bit for bit, the all-pairs path
        to rounding, and rows still freeze on their own."""
        from dexiraft_tpu.train.step import make_eval_step

        monkeypatch.setenv("DEXIRAFT_PALLAS_INTERPRET", "1")
        cfg = dataclasses.replace(setup["cfg"], corr_impl="flash",
                                  fused_update=True)
        a = np.stack([setup["frame"](1), setup["frame"](3)])
        b = np.stack([setup["frame"](2), setup["frame"](4)])
        low_f, up_f = make_eval_step(cfg, iters=ITERS)(
            setup["variables"], a, b)
        low_a, up_a, iu, _ = make_eval_step(
            dataclasses.replace(cfg, converge_tol=0.0), iters=ITERS,
            adaptive=True)(setup["variables"], a, b,
                           iter_budget=np.int32(ITERS))
        assert np.array_equal(_get(up_f), _get(up_a))
        assert np.array_equal(_get(low_f), _get(low_a))
        assert _get(iu).tolist() == [ITERS, ITERS]
        _, up_plain = setup["fixed"](setup["variables"], a, b)
        np.testing.assert_allclose(_get(up_f), _get(up_plain), rtol=0,
                                   atol=1e-3)
        # the gate: the damped model's rows stop early, each where its
        # solo all-pairs run stops
        _, up_d, iu_d, _ = make_eval_step(cfg, iters=ITERS, adaptive=True)(
            setup["damped"], a, b, iter_budget=np.int32(ITERS))
        assert int(_get(iu_d).max()) < ITERS
        for row in range(2):
            _, up_s, iu_s, _ = setup["adapt"](
                setup["damped"], a[row][None], b[row][None],
                iter_budget=np.int32(ITERS))
            assert int(_get(iu_d)[row]) == int(_get(iu_s)[0])
            np.testing.assert_allclose(_get(up_d)[row], _get(up_s)[0],
                                       rtol=0, atol=1e-4)

    def test_config_rejects_negative_tol(self):
        from dataclasses import replace

        from dexiraft_tpu.config import raft_v1

        with pytest.raises(ValueError):
            replace(raft_v1(small=True), converge_tol=-0.1)


# ---- engine/scheduler/service: numpy stub, no jax -----------------------


_FULL = 8


def _stub_fixed(im1, im2, flow_init=None):
    b, h, w = im1.shape[:3]
    up = np.broadcast_to(np.float32([2.0, -1.0]), (b, h, w, 2)).copy()
    low = np.zeros((b, h // 8, w // 8, 2), np.float32)
    return low, up


def _stub_adaptive(im1, im2, flow_init=None, iter_budget=None):
    low, up = _stub_fixed(im1, im2, flow_init)
    b = im1.shape[0]
    n = _FULL if iter_budget is None else int(iter_budget)
    return (low, up, np.full((b,), n, np.int32),
            np.full((b,), 1e-4, np.float32))


def _item(seed=0):
    rng = np.random.default_rng(seed)
    return {"image1": rng.uniform(0, 255, (H, W, 3)).astype(np.float32),
            "image2": rng.uniform(0, 255, (H, W, 3)).astype(np.float32)}


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class TestAdaptiveEngine:
    def test_results_carry_convergence_evidence(self):
        eng = InferenceEngine(_stub_adaptive,
                              ServeConfig(batch_size=2, adaptive=True))
        r1, r2 = eng.run_batch([_item(), _item(1)])
        assert r1.iters_used == _FULL and r2.iters_used == _FULL
        assert abs(r1.final_delta - 1e-4) < 1e-9
        (r3,) = eng.run_batch([_item()], iter_budget=3)
        assert r3.iters_used == 3
        rec = eng.stats_record()
        assert rec["adaptive"] is True
        assert rec["iters_used_mean"] > 0
        assert {"iters_used_p50", "iters_used_p99", "final_delta_p50",
                "final_delta_p99"} <= set(rec)

    def test_fixed_engine_refuses_budget_and_stays_schema_clean(self):
        eng = InferenceEngine(_stub_fixed, ServeConfig(batch_size=1))
        with pytest.raises(ValueError):
            eng.run_batch([_item()], iter_budget=4)
        (r,) = eng.run_batch([_item()])
        assert r.iters_used is None and r.final_delta is None
        # fixed-path stats are byte-identical to pre-adaptive records
        assert "adaptive" not in eng.stats_record()

    def test_stream_threads_budget_through(self):
        eng = InferenceEngine(_stub_adaptive,
                              ServeConfig(batch_size=2, adaptive=True))
        out = list(eng.stream([_item(i) for i in range(4)], iter_budget=5))
        assert [r.iters_used for r in out] == [5] * 4


class TestBudgetPolicy:
    def _sched(self, clock, calls, **kw):
        def timed(im1, im2, flow_init=None, iter_budget=None):
            calls.append(None if iter_budget is None else int(iter_budget))
            clock.advance(0.07)   # measured service time, fake-clock
            return _stub_adaptive(im1, im2, flow_init, iter_budget)

        eng = InferenceEngine(timed,
                              ServeConfig(batch_size=1, adaptive=True))
        kw.setdefault("slo_ms", 100.0)
        kw.setdefault("max_queue", 8)
        return Scheduler(eng, adaptive=True, max_iters=_FULL, min_iters=2,
                         clock=clock, **kw)

    def test_unlearned_bucket_runs_full_depth(self):
        clock, calls = FakeClock(), []
        s = self._sched(clock, calls)
        s.submit_async(_item())
        assert s.poll_once()
        # no per-iteration estimate yet: degrading on a guess would
        # teach the EWMA a degraded cost forever
        assert calls == [_FULL]

    def test_slo_exhausted_head_floors_at_min_iters(self):
        clock, calls = FakeClock(), []
        s = self._sched(clock, calls)
        s.submit_async(_item())
        assert s.poll_once()                  # learn ~8.75 ms/iter
        s.submit_async(_item())
        clock.advance(0.095)                  # 95 of the 100 ms burned
        assert s.poll_once()
        assert calls[-1] == 2                 # the min_iters floor holds

    def test_queue_pressure_degrades_smoothly(self):
        clock, calls = FakeClock(), []
        s = self._sched(clock, calls)
        s.submit_async(_item())
        assert s.poll_once()                  # learn the estimate
        for i in range(4):                    # pending=4 of max_queue=8
            s.submit_async(_item(i))
        assert s.poll_once()
        # between the floor and full depth: the soft valve, not a cliff
        assert 2 < calls[-1] < _FULL
        rec = s.stats_record()
        assert rec["adaptive"] is True
        assert rec["min_iters"] == 2 and rec["max_iters"] == _FULL
        assert {"iter_budget_p50", "iter_budget_p99",
                "iter_est_ms"} <= set(rec)

    def test_adaptive_scheduler_needs_adaptive_engine(self):
        eng = InferenceEngine(_stub_fixed, ServeConfig(batch_size=1))
        with pytest.raises(ValueError):
            Scheduler(eng, adaptive=True, clock=FakeClock())
        eng_a = InferenceEngine(_stub_adaptive,
                                ServeConfig(batch_size=1, adaptive=True))
        with pytest.raises(ValueError):
            Scheduler(eng_a, adaptive=True, max_iters=4, min_iters=9,
                      clock=FakeClock())

    def test_fixed_scheduler_schema_unchanged(self):
        eng = InferenceEngine(_stub_fixed, ServeConfig(batch_size=1))
        rec = Scheduler(eng, clock=FakeClock()).stats_record()
        assert "adaptive" not in rec and "iter_budget_p50" not in rec


class TestServiceWire:
    def test_headers_and_stats_expose_convergence(self):
        svc = FlowService(
            InferenceEngine(_stub_adaptive,
                            ServeConfig(batch_size=1, adaptive=True)),
            port=0, slo_ms=50.0, max_queue=8, session_ttl_s=0.0,
            max_iters=_FULL, min_iters=2).start()
        try:
            body = encode_request(**_item())
            req = urllib.request.Request(
                svc.url + "/v1/flow", data=body,
                headers={"Content-Type": "application/x-npz"})
            with urllib.request.urlopen(req, timeout=10.0) as r:
                assert r.status == 200
                hdr = dict(r.headers)
                r.read()
            assert int(hdr["X-Iters-Used"]) == _FULL
            assert abs(float(hdr["X-Final-Delta"]) - 1e-4) < 1e-6
            with urllib.request.urlopen(svc.url + "/stats",
                                        timeout=10.0) as r:
                stats = json.load(r)
            assert stats["service"]["adaptive"] is True
            assert stats["engine"]["adaptive"] is True
            assert stats["engine"]["iters_used_mean"] == float(_FULL)
            assert stats["scheduler"]["adaptive"] is True
            assert stats["scheduler"]["max_iters"] == _FULL
        finally:
            svc.drain_and_stop(timeout=10.0)


# ---- compile discipline: one executable serves every budget -------------


class TestCompileFlat:
    def test_budget_change_is_not_a_recompile(self, setup):
        # the serve_cli --warmup contract (satellite): after the warmup
        # dispatch, a dispatch at a DIFFERENT budget must ride the same
        # executable — the budget is a traced int32 scalar, so a --strict
        # boot would fail loudly if it ever re-specialized
        import jax

        step = setup["adapt"]
        variables = setup["damped"]

        def eval_fn(a, b, fi, ib=None):
            put = jax.device_put
            return step(variables, put(a), put(b),
                        flow_init=None if fi is None else put(fi),
                        iter_budget=np.int32(ITERS if ib is None else ib))

        eng = InferenceEngine(
            eval_fn, ServeConfig(batch_size=1, bucket_multiple=8,
                                 adaptive=True))
        (r1,) = eng.run_batch([_item()])            # warmup, baseline set
        (r2,) = eng.run_batch([_item()], iter_budget=1)
        (r3,) = eng.run_batch([_item()], iter_budget=3)
        eng.watch.check()                           # raises on drift
        assert eng.registry.compiles == 1
        assert r1.iters_used is not None
        assert r2.iters_used is not None and r2.iters_used <= 1


# ---- record schemas + watchdog stderr hygiene ---------------------------


def test_adaptive_bench_record_schema_pinned():
    sys.path.insert(0, osp.join(REPO, "scripts"))
    try:
        from serve_bench import (ADAPTIVE_OVERLOAD_KEYS,
                                 ADAPTIVE_RECORD_KEYS, OVERLOAD_KEYS)
    finally:
        sys.path.pop(0)
    assert {"metric", "converge_tol", "min_iters", "epe_vs_fixed_px",
            "mean_iters_used", "p99_iters_used", "iters_drop_pct",
            "mean_final_delta", "fixed_ms_per_pair",
            "adaptive_ms_per_pair", "overload_fixed", "overload_adaptive",
            "overload_goodput_ratio"} <= ADAPTIVE_RECORD_KEYS
    assert OVERLOAD_KEYS < ADAPTIVE_OVERLOAD_KEYS
    assert {"iter_budget_p50", "iter_budget_p99",
            "iters_used_mean"} <= ADAPTIVE_OVERLOAD_KEYS


def test_frontier_record_schema_pinned():
    from dexiraft_tpu.eval_cli import (FRONTIER_LEG_KEYS,
                                       FRONTIER_RECORD_KEYS)

    assert FRONTIER_RECORD_KEYS == {"record", "dataset", "iters",
                                    "converge_tol", "fixed", "sweep"}
    assert {"budget", "wall_s", "mean_iters_used", "p99_iters_used",
            "mean_final_delta"} <= FRONTIER_LEG_KEYS


def test_stderr_filter_diverts_xla_host_warning(tmp_path):
    sys.path.insert(0, REPO)
    try:
        from bench import XLA_HOST_WARNING_MARKER, make_stderr_filter
    finally:
        sys.path.pop(0)
    log = tmp_path / "xla_warn.log"
    filt = make_stderr_filter(log_path=str(log), tag="t")
    assert filt(b"ordinary progress line\n") == b"ordinary progress line\n"
    warn = b"W000 cpu_client.cc] " + XLA_HOST_WARNING_MARKER + b".\n"
    note = filt(warn)
    assert note is not None and b"suppressed" in note
    assert XLA_HOST_WARNING_MARKER not in note     # tail stays clean
    assert filt(warn) is None                      # repeats vanish
    assert warn in log.read_bytes()                # full text preserved
    # the record line the driver greps must always pass through
    rec = b'{"metric": "serve_adaptive"}\n'
    assert filt(rec) == rec
