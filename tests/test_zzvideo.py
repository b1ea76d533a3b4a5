"""Streaming video tier (PR 14): DeviceSessionStore byte-budget LRU
accounting, VideoEngine chunk/carry semantics (numpy stubs — no model),
the /v1/flow/stream endpoint, the engine's device-carry flow_init
assembly, and the split-model parity pin (encode_frame +
step_from_features == monolithic __call__ on the same params).

Named test_zz* to sort after the long-standing tail tests (tier-1 870 s
budget convention, see test_zpipeline_async.py); the jax-model parity
tests sit at the end of the file and use the small config at tiny
geometry.
"""

import json
import os.path as osp
import sys
import urllib.request

import numpy as np
import pytest

from dexiraft_tpu.serve import (DeviceSessionStore, FlowService,
                                InferenceEngine, ServeConfig, VideoEngine)
from dexiraft_tpu.serve.server import (decode_stream_response,
                                       encode_stream_request)
from dexiraft_tpu.serve.sessions import carry_nbytes


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _feats(kb: int) -> dict:
    """A feature-dict stand-in of `kb` KiB (float32)."""
    return {"fmap": np.zeros((kb * 256,), np.float32)}


_FI = np.zeros((4, 4, 2), np.float32)  # 128 B flow seed


# ---- DeviceSessionStore: byte-budget LRU accounting ----------------------


class TestDeviceSessionStore:
    def test_byte_budget_evicts_oldest_and_counters_move(self):
        clock = FakeClock()
        st = DeviceSessionStore(budget_bytes=2 * 1024 + 512, ttl_s=60,
                                clock=clock)
        st.put("a", (32, 32), _feats(1), _FI)
        clock.advance(1)
        st.put("b", (32, 32), _feats(1), _FI)
        assert len(st) == 2
        used = st.bytes_in_use
        assert used == 2 * carry_nbytes(_feats(1), _FI)
        clock.advance(1)
        # admitting c busts the budget -> the OLDEST stream (a) goes
        st.put("c", (32, 32), _feats(1), _FI)
        assert st.get("a", (32, 32)) is None       # evicted
        assert st.get("b", (32, 32)) is not None   # LRU survivor
        assert st.get("c", (32, 32)) is not None
        rec = st.stats_record()
        assert rec["budget_evicted"] == 1
        assert rec["active"] == 2
        assert st.bytes_in_use == used  # back under budget

    def test_touch_order_protects_hot_streams(self):
        clock = FakeClock()
        st = DeviceSessionStore(budget_bytes=2 * 1024 + 512, ttl_s=60,
                                clock=clock)
        st.put("a", (32, 32), _feats(1), _FI)
        clock.advance(1)
        st.put("b", (32, 32), _feats(1), _FI)
        clock.advance(1)
        st.get("a", (32, 32))   # a is now most-recent
        st.put("c", (32, 32), _feats(1), _FI)
        assert st.get("b", (32, 32)) is None   # b was LRU, not a
        assert st.get("a", (32, 32)) is not None

    def test_single_over_budget_stream_kept_and_counted(self):
        st = DeviceSessionStore(budget_bytes=1024, ttl_s=60,
                                clock=FakeClock())
        st.put("big", (64, 64), _feats(4), _FI)   # 4 KiB > 1 KiB budget
        assert st.get("big", (64, 64)) is not None
        assert st.stats_record()["over_budget"] == 1
        assert st.stats_record()["budget_evicted"] == 0

    def test_bucket_change_resets_exactly_one_stream(self):
        st = DeviceSessionStore(budget_bytes=1 << 20, ttl_s=60,
                                clock=FakeClock())
        st.put("a", (32, 32), _feats(1), _FI)
        st.put("b", (32, 32), _feats(1), _FI)
        # a's camera changed geometry into a new bucket: cold restart
        # for a ONLY, counted once
        assert st.get("a", (64, 64)) is None
        rec = st.stats_record()
        assert rec["bucket_resets"] == 1
        assert rec["active"] == 1
        assert st.get("b", (32, 32)) is not None  # untouched

    def test_ttl_expiry_and_update_accounting(self):
        clock = FakeClock()
        st = DeviceSessionStore(budget_bytes=1 << 20, ttl_s=10,
                                clock=clock)
        st.put("a", (32, 32), _feats(1), _FI)
        clock.advance(11)
        assert st.get("a", (32, 32)) is None
        assert st.stats_record()["expired"] == 1
        # replacing a carry re-accounts bytes instead of double-counting
        st.put("b", (32, 32), _feats(1), _FI)
        st.put("b", (32, 32), _feats(2), _FI)
        assert st.bytes_in_use == carry_nbytes(_feats(2), _FI)
        assert len(st) == 1

    def test_counter_reset_keeps_state(self):
        st = DeviceSessionStore(budget_bytes=1 << 20, ttl_s=60,
                                clock=FakeClock())
        st.put("a", (32, 32), _feats(1), _FI)
        st.get("a", (32, 32))
        st.reset_counters()
        rec = st.stats_record()
        assert rec["hits"] == 0 and rec["active"] == 1
        assert rec["bytes_in_use_mb"] > 0
        assert set(rec) == {
            "active", "ttl_s", "max_sessions", "budget_mb",
            "bytes_in_use_mb", "peak_mb", "hits", "misses", "expired",
            "lru_evicted", "budget_evicted", "bucket_resets",
            "over_budget"}


# ---- VideoEngine: chunk/carry semantics over numpy stubs ----------------


def _stub_encode(frame):
    return {"fmap": np.asarray(frame)[..., :1].copy()}


def _stub_refine(f1, f2, fi):
    """flow_low = flow_init + 1 (chaining visible); flow_up broadcasts
    its mean so the test can read the chain depth off the response."""
    b, h, w = f1["fmap"].shape[:3]
    low = np.asarray(fi) + 1.0
    up = np.full((b, h, w, 2), float(np.mean(low)), np.float32)
    return low, up


def _video(**kw):
    kw.setdefault("sessions", DeviceSessionStore(budget_bytes=1 << 20,
                                                 ttl_s=60,
                                                 clock=FakeClock()))
    return VideoEngine(_stub_encode, _stub_refine, bucket_multiple=16,
                       **kw)


def _chunk(t=3, h=40, w=56, seed=0):
    rng = np.random.default_rng(seed)
    return rng.uniform(0, 255, (t, h, w, 3)).astype(np.float32)


class TestVideoEngine:
    def test_cold_chunk_yields_t_minus_1_flows(self):
        v = _video()
        res = v.process_chunk("cam", _chunk(3))
        assert not res.warm
        assert res.frames_in == 3 and len(res.flows) == 2
        assert res.flows[0].shape == (40, 56, 2)
        # consecutive pairs chain: seed 0 -> low 1 -> low 2
        assert float(res.flows[0].mean()) == pytest.approx(1.0)
        assert float(res.flows[1].mean()) == pytest.approx(2.0)

    def test_warm_chunk_pairs_carry_with_first_frame(self):
        v = _video()
        v.process_chunk("cam", _chunk(3))
        res = v.process_chunk("cam", _chunk(3, seed=1))
        # warm: (carry, f0) + 2 in-chunk pairs, chain continues 3, 4, 5
        assert res.warm and len(res.flows) == 3
        assert [float(f.mean()) for f in res.flows] == [
            pytest.approx(3.0), pytest.approx(4.0), pytest.approx(5.0)]

    def test_cold_single_frame_primes_carry_only(self):
        v = _video()
        res = v.process_chunk("cam", _chunk(1))
        assert res.frames_in == 1 and len(res.flows) == 0
        res = v.process_chunk("cam", _chunk(1, seed=1))
        assert res.warm and len(res.flows) == 1

    def test_no_session_id_is_standalone(self):
        v = _video()
        v.process_chunk(None, _chunk(3))
        assert len(v.sessions) == 0
        res = v.process_chunk(None, _chunk(3))
        assert not res.warm    # nothing carried

    def test_blank_session_id_is_standalone(self):
        # "" as a real key would share one carry across every client
        # that sends a blank X-Session-Id header (pair endpoint parity)
        v = _video()
        v.process_chunk("", _chunk(3))
        assert len(v.sessions) == 0
        res = v.process_chunk("", _chunk(3))
        assert not res.warm

    def test_chunk_cap_rejects_oversize(self):
        v = _video(max_chunk_frames=4)
        with pytest.raises(ValueError, match="caps chunks at 4"):
            v.process_chunk("cam", _chunk(5))
        assert v.process_chunk("cam", _chunk(4)).frames_in == 4
        with pytest.raises(ValueError):
            _video(max_chunk_frames=0)

    def test_inflight_zero_at_rest_and_after_traffic(self):
        v = _video()
        assert v.inflight() == 0
        v.process_chunk("cam", _chunk(3))
        assert v.inflight() == 0

    def test_admission_sheds_past_max_pending_chunks(self):
        from dexiraft_tpu.serve.video import StreamOverloaded

        v = _video(max_pending_chunks=2)
        with v._inflight_lock:
            v._inflight = 2   # two chunks already queued on the lock
        try:
            with pytest.raises(StreamOverloaded, match="retry"):
                v.process_chunk("cam", _chunk(2))
        finally:
            with v._inflight_lock:
                v._inflight = 0
        assert v.process_chunk("cam", _chunk(2)).frames_in == 2
        with pytest.raises(ValueError):
            _video(max_pending_chunks=0)

    def test_stats_scrape_never_blocks_behind_a_live_chunk(self):
        # _lock is held for a whole chunk's frame loop; stats_record
        # takes only the stats lock, so a /stats scrape (router
        # aggregation, monitoring) returns immediately
        import threading

        v = _video()
        v.process_chunk("cam", _chunk(3))
        out = {}
        with v._lock:   # a chunk is "mid-flight"
            t = threading.Thread(
                target=lambda: out.update(rec=v.stats_record()))
            t.start()
            t.join(timeout=5)
            assert not t.is_alive(), "stats_record blocked on _lock"
        assert out["rec"]["chunks"] == 1

    def test_bucket_change_restarts_cold(self):
        v = _video()
        v.process_chunk("cam", _chunk(3))
        res = v.process_chunk("cam", _chunk(3, h=72, w=88))
        assert not res.warm and len(res.flows) == 2
        assert v.sessions.stats_record()["bucket_resets"] == 1

    def test_validation_rejects_malformed(self):
        v = _video()
        with pytest.raises(ValueError):
            v.process_chunk("cam", np.zeros((40, 56, 3), np.float32))
        with pytest.raises(ValueError):
            v.process_chunk("cam", np.zeros((0, 40, 56, 3), np.float32))
        with pytest.raises(ValueError):
            v.process_chunk("cam", np.zeros((2, 40, 56, 4), np.float32))

    def test_stats_record_and_reset(self):
        v = _video()
        v.process_chunk("cam", _chunk(3))
        v.process_chunk("cam", _chunk(3))
        rec = v.stats_record()
        assert rec["chunks"] == 2 and rec["frames_in"] == 6
        assert rec["flows_out"] == 5
        assert rec["warm_chunks"] == 1 and rec["cold_chunks"] == 1
        assert rec["compiled_buckets"] == ["48x64"]
        assert rec["sessions"]["active"] == 1
        v.reset_stats()
        rec = v.stats_record()
        assert rec["chunks"] == 0
        assert rec["compiled_buckets"] == ["48x64"]   # state survives
        assert rec["sessions"]["active"] == 1


# ---- the /v1/flow/stream endpoint over the stub video engine ------------


def _stub_eval(im1, im2, flow_init=None):
    b, h, w = im1.shape[:3]
    return (np.zeros((b, h // 8, w // 8, 2), np.float32),
            np.zeros((b, h, w, 2), np.float32))


class TestStreamEndpoint:
    @pytest.fixture()
    def service(self):
        svc = FlowService(
            InferenceEngine(_stub_eval, ServeConfig(batch_size=1)),
            port=0, video=_video()).start()
        yield svc
        svc.drain_and_stop(timeout=10)

    def _post(self, svc, frames, sid=None):
        headers = {"X-Session-Id": sid} if sid else {}
        req = urllib.request.Request(svc.url + "/v1/flow/stream",
                                     data=encode_stream_request(frames),
                                     headers=headers)
        resp = urllib.request.urlopen(req, timeout=30)
        return resp, decode_stream_response(resp.read())

    def test_chunked_stream_carries_across_requests(self, service):
        resp, flows = self._post(service, _chunk(3), "vid")
        assert resp.headers["X-Warm-Start"] == "0"
        assert resp.headers["X-Flows-Out"] == "2"
        assert flows.shape == (2, 40, 56, 2)
        resp, flows = self._post(service, _chunk(3, seed=1), "vid")
        assert resp.headers["X-Warm-Start"] == "1"
        assert flows.shape == (3, 40, 56, 2)
        assert resp.headers["X-Bucket"] == "48x64"

    def test_stream_stats_on_endpoint(self, service):
        self._post(service, _chunk(2), "vid")
        stats = json.loads(urllib.request.urlopen(
            service.url + "/stats", timeout=30).read())
        assert stats["video"]["chunks"] == 1
        assert stats["video"]["sessions"]["active"] == 1

    def test_healthz_inflight_counts_streaming_chunks(self, service):
        # streaming bypasses the scheduler; the router's zero-drop
        # drain polls healthz inflight, so live chunks must count there
        assert service.health_record()["inflight"] == 0
        with service.video._inflight_lock:
            service.video._inflight += 1
        try:
            assert service.health_record()["inflight"] == 1
        finally:
            with service.video._inflight_lock:
                service.video._inflight -= 1

    def test_overloaded_stream_is_503_with_retry_after(self, service):
        with service.video._inflight_lock:
            service.video._inflight = service.video.max_pending_chunks
        try:
            req = urllib.request.Request(
                service.url + "/v1/flow/stream",
                data=encode_stream_request(_chunk(2)))
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(req, timeout=30)
            assert e.value.code == 503
            assert e.value.headers["Retry-After"] == "1"
        finally:
            with service.video._inflight_lock:
                service.video._inflight = 0

    def test_oversize_chunk_is_400(self, service):
        service.video.max_chunk_frames = 2
        req = urllib.request.Request(
            service.url + "/v1/flow/stream",
            data=encode_stream_request(_chunk(3)))
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req, timeout=30)
        assert e.value.code == 400
        assert b"caps chunks" in e.value.read()

    def test_malformed_chunk_is_400(self, service):
        req = urllib.request.Request(
            service.url + "/v1/flow/stream",
            data=encode_stream_request(np.zeros((40, 56, 3))))
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req, timeout=30)
        assert e.value.code == 400

    def test_streaming_disabled_is_404_with_hint(self):
        svc = FlowService(
            InferenceEngine(_stub_eval, ServeConfig(batch_size=1)),
            port=0).start()
        try:
            req = urllib.request.Request(
                svc.url + "/v1/flow/stream",
                data=encode_stream_request(_chunk(2)))
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(req, timeout=30)
            assert e.value.code == 404
            assert b"stream_sessions_mb" in e.value.read()
        finally:
            svc.drain_and_stop(timeout=10)


# ---- engine device-carry flow_init assembly ------------------------------


class TestEngineDeviceCarry:
    def test_host_path_counts_carry_bytes(self):
        eng = InferenceEngine(_stub_eval,
                              ServeConfig(batch_size=2, warm_start=True))
        fi = np.ones((5, 7, 2), np.float32)
        eng.run_batch([
            {"image1": np.zeros((40, 56, 3), np.float32),
             "image2": np.zeros((40, 56, 3), np.float32),
             "flow_init": fi},
            {"image1": np.zeros((40, 56, 3), np.float32),
             "image2": np.zeros((40, 56, 3), np.float32)}])
        assert eng.stats.carry_h2d_bytes == fi.nbytes  # warm row only
        assert eng.stats.carry_d2h_bytes == 0          # stub: no fetch

    def test_device_carry_strict_compile_flat_on_multi_row_batches(self):
        """The per-row carry slice (low[row]) is one executable per
        STATIC row index: a one-item warmup batch only ever slices row
        0, so rows 1.. must be pre-compiled inside the fresh-dispatch
        sanctioned window or the first real multi-warm batch trips the
        --strict check."""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def eval_fn(im1, im2, fi):
            return fi, jnp.zeros(im1.shape[:3] + (2,), jnp.float32)

        eng = InferenceEngine(
            eval_fn, ServeConfig(batch_size=3, warm_start=True,
                                 device_carry=True, strict=True))
        item = lambda: {"image1": np.zeros((40, 56, 3), np.float32),
                        "image2": np.zeros((40, 56, 3), np.float32)}
        eng.run_batch([item()])                      # warmup-like, fresh
        eng.run_batch([item(), item(), item()])      # slices rows 1, 2
        eng.run_batch([item(), item(), item()])      # strict: stays flat

    def test_device_path_assembles_on_device_with_zero_bytes(self):
        import jax

        eng = InferenceEngine(
            _stub_eval, ServeConfig(batch_size=2, warm_start=True,
                                    device_carry=True))
        row = jax.device_put(np.full((5, 7, 2), 2.0, np.float32))
        fi = eng._assemble_fi((40, 56), [row, None])
        assert fi.shape == (2, 5, 7, 2)
        got = jax.device_get(fi)
        np.testing.assert_array_equal(got[0], 2.0)
        np.testing.assert_array_equal(got[1], 0.0)
        assert eng.stats.carry_h2d_bytes == 0
        # device flow_init into a host-carry engine is refused loudly
        host_eng = InferenceEngine(_stub_eval,
                                   ServeConfig(batch_size=2,
                                               warm_start=True))
        with pytest.raises(ValueError, match="device_carry"):
            host_eng._assemble_fi((40, 56), [row, None])


# ---- video_bench record schema ------------------------------------------


def test_video_bench_record_schema_pins():
    sys.path.insert(0, osp.join(osp.dirname(osp.dirname(
        osp.abspath(__file__))), "scripts"))
    try:
        from video_bench import (CARRY_KEYS, FOOTPRINT_KEYS, LEG_KEYS,
                                 VIDEO_RECORD_KEYS, validate_record)
    finally:
        sys.path.pop(0)
    leg = {k: 0 for k in LEG_KEYS}
    rec = {k: None for k in VIDEO_RECORD_KEYS}
    rec.update(pairwise=dict(leg), streamed=dict(leg),
               footprint={k: [] for k in FOOTPRINT_KEYS},
               carry={k: 0 for k in CARRY_KEYS})
    validate_record(rec)   # complete record passes
    bad = dict(rec)
    del bad["corr_impl_resolved"]
    with pytest.raises(ValueError):
        validate_record(bad)
    rec["streamed"] = {**leg, "extra": 1}
    with pytest.raises(ValueError):
        validate_record(rec)


# ---- split-model parity pin (jax; small model, tiny frames) -------------


@pytest.mark.parametrize("variant", ["v1", "v5"])
def test_split_encoder_parity_with_monolithic(variant):
    """encode_frame + step_from_features == monolithic __call__ on the
    SAME params (the streaming tier's correctness contract): cold and
    warm-start forwards agree to <= 1e-4, and the split path never
    forks the param tree (the same init serves both)."""
    import jax
    import jax.numpy as jnp

    from _models import init_raft, jit_apply
    from dexiraft_tpu.config import VARIANTS

    model, variables = init_raft(VARIANTS[variant](small=True), 48, 64)
    forward = jit_apply(model)
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    im1 = jax.random.uniform(k1, (1, 48, 64, 3), jnp.float32, 0, 255)
    im2 = jax.random.uniform(k2, (1, 48, 64, 3), jnp.float32, 0, 255)

    low_m, up_m = forward(variables, im1, im2, iters=2, test_mode=True)
    f1 = forward(variables, im1, mode="encode")
    f2 = forward(variables, im2, mode="encode")
    low_s, up_s = forward(variables, None, iters=2, test_mode=True,
                          mode="step", features1=f1, features2=f2)
    assert float(jnp.max(jnp.abs(low_m - low_s))) <= 1e-4
    assert float(jnp.max(jnp.abs(up_m - up_s))) <= 1e-4

    # warm start rides the same contract (flow_init enters in "step")
    fi = jax.random.uniform(jax.random.PRNGKey(3), (1, 6, 8, 2),
                            jnp.float32, -1, 1)
    _, up_mw = forward(variables, im1, im2, iters=2, test_mode=True,
                       flow_init=fi)
    _, up_sw = forward(variables, None, iters=2, test_mode=True,
                       flow_init=fi, mode="step", features1=f1, features2=f2)
    assert float(jnp.max(jnp.abs(up_mw - up_sw))) <= 1e-4

    # a forgotten frame fails loudly, not as a NoneType deep crash
    # (images became Optional for the split modes)
    with pytest.raises(ValueError, match="mode='pair' needs"):
        forward(variables, im1, iters=1, test_mode=True)


def test_streaming_feature_reuse_matches_chained_pairs():
    """The cross-frame reuse claim itself: driving frames f0, f1, f2 as
    (encode-once, refine) streaming steps equals the chained monolithic
    pairs (f0,f1), (f1,f2) — frame 1 is encoded ONCE in the streamed
    path yet serves as frame 2 of the first pair and frame 1 of the
    second."""
    import jax
    import jax.numpy as jnp

    from _models import init_raft, jit_apply
    from dexiraft_tpu.config import raft_v1

    model, variables = init_raft(raft_v1(small=True), 48, 64)
    forward = jit_apply(model)
    key = jax.random.PRNGKey(5)
    frames = [jax.random.uniform(jax.random.fold_in(key, i),
                                 (1, 48, 64, 3), jnp.float32, 0, 255)
              for i in range(3)]

    # chained monolithic pairs with flow carry
    low, up_a1 = forward(variables, frames[0], frames[1], iters=2,
                         test_mode=True)
    _, up_a2 = forward(variables, frames[1], frames[2], iters=2,
                       test_mode=True, flow_init=low)

    # streamed: each frame encoded once
    feats = [forward(variables, f, mode="encode") for f in frames]
    low_s, up_b1 = forward(variables, None, iters=2, test_mode=True,
                           mode="step", features1=feats[0],
                           features2=feats[1])
    _, up_b2 = forward(variables, None, iters=2, test_mode=True,
                       mode="step", features1=feats[1], features2=feats[2],
                       flow_init=low_s)
    assert float(jnp.max(jnp.abs(up_a1 - up_b1))) <= 1e-4
    assert float(jnp.max(jnp.abs(up_a2 - up_b2))) <= 1e-4


def test_warm_start_through_the_flash_kernel(monkeypatch):
    """`flow_init` enters the carry above the loop, and the loop holds
    its coordinates as planes down to the kernel's `(B, 2, Np)` operand
    (ISSUE 36): a streamed warm start through flash + fused (what `auto`
    serves on a TPU; interpreted here) is the all-pairs path's on the
    same parameters, and differs from the cold start."""
    import jax
    import jax.numpy as jnp

    from _models import init_raft, jit_apply
    from dexiraft_tpu.config import raft_v1
    from dexiraft_tpu.models.raft import RAFT

    monkeypatch.setenv("DEXIRAFT_PALLAS_INTERPRET", "1")
    k1, k2 = jax.random.split(jax.random.PRNGKey(11))
    im1 = jax.random.uniform(k1, (2, 48, 64, 3), jnp.float32, 0, 255)
    im2 = jax.random.uniform(k2, (2, 48, 64, 3), jnp.float32, 0, 255)
    plain, variables = init_raft(raft_v1(small=True), 48, 64)
    plain = jit_apply(plain)
    flash = jit_apply(
        RAFT(raft_v1(small=True, corr_impl="flash", fused_update=True)))
    # per-item warm starts: a warm row beside a cold (zero) row
    fi = jnp.stack([jax.random.uniform(jax.random.PRNGKey(3), (6, 8, 2),
                                       jnp.float32, -2, 2),
                    jnp.zeros((6, 8, 2))])

    def streamed(forward, flow_init):
        f1 = forward(variables, im1, mode="encode")
        f2 = forward(variables, im2, mode="encode")
        return forward(variables, None, iters=3, test_mode=True, mode="step",
                       features1=f1, features2=f2, flow_init=flow_init)

    low_p, up_p = streamed(plain, fi)
    low_f, up_f = streamed(flash, fi)
    assert float(jnp.max(jnp.abs(low_p - low_f))) <= 1e-4
    assert float(jnp.max(jnp.abs(up_p - up_f))) <= 1e-4
    low_c, _ = streamed(flash, None)
    assert float(jnp.max(jnp.abs(low_c[0] - low_f[0]))) > 1e-2  # warm row
    assert float(jnp.max(jnp.abs(low_c[1] - low_f[1]))) <= 1e-6  # cold row
