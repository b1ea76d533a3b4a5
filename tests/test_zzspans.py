"""The program's host spans (profiling.span/add/reset/snapshot) and the
layers that write them: the inference engine, the loader, the device
prefetcher and the compile listener.

Named to sort last in collection (the test_zpipeline_async.py
convention). The table is process-wide, so every case resets the prefix
it reads before it writes.
"""

import sys
import threading
import time

import numpy as np
import pytest

from dexiraft_tpu import profiling
from dexiraft_tpu.profiling import span


def _counts(prefix):
    return {k: v["count"] for k, v in profiling.snapshot(prefix).items()}


# ---- the table ------------------------------------------------------------


def test_nested_spans_each_record_their_own_time():
    profiling.reset("t:")
    with span("t:outer") as outer:
        with span("t:inner") as inner:
            time.sleep(0.01)
    got = profiling.snapshot("t:")
    assert set(got) == {"t:outer", "t:inner"}
    assert got["t:inner"]["seconds"] == inner.seconds >= 0.01
    assert got["t:outer"]["seconds"] == outer.seconds >= inner.seconds
    assert got["t:outer"]["durations"] == [outer.seconds]


def test_add_snapshot_and_reset_go_by_prefix():
    profiling.reset("t:")
    profiling.reset("u:")
    profiling.add("t:a", 0.25)
    profiling.add("t:a", 0.5)
    profiling.add("u:a", 1.0)
    assert profiling.snapshot("t:") == {
        "t:a": {"seconds": 0.75, "count": 2, "durations": [0.25, 0.5]}}
    profiling.reset("t:")
    assert profiling.snapshot("t:") == {}
    assert _counts("u:") == {"u:a": 1}
    with span("t:raises"):
        pass
    with pytest.raises(KeyError):
        with span("t:raises"):
            raise KeyError("the span still closes")
    assert _counts("t:") == {"t:raises": 2}


def test_single_durations_are_bounded_and_the_count_is_not():
    profiling.reset("t:")
    n = profiling.SPAN_WINDOW + 10
    for i in range(n):
        profiling.add("t:many", float(i))
    rec = profiling.snapshot("t:")["t:many"]
    assert rec["count"] == n
    assert len(rec["durations"]) == profiling.SPAN_WINDOW
    assert rec["durations"][-1] == float(n - 1)


def test_threads_on_one_name_lose_no_update():
    profiling.reset("t:")
    threads, each = 16, 2000
    go = threading.Event()

    def work():
        go.wait(10)
        for _ in range(each):
            profiling.add("t:shared", 1.0)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        pool = [threading.Thread(target=work) for _ in range(threads)]
        for t in pool:
            t.start()
        go.set()
        for t in pool:
            t.join(60)
        assert not any(t.is_alive() for t in pool)
    finally:
        sys.setswitchinterval(interval)
    rec = profiling.snapshot("t:")["t:shared"]
    assert rec["count"] == threads * each
    assert rec["seconds"] == float(threads * each)


# ---- the engine -------------------------------------------------------------


def _stub_eval(im1, im2, flow_init=None):
    b, h, w = im1.shape[:3]
    return (np.zeros((b, h // 8, w // 8, 2), np.float32),
            np.ones((b, h, w, 2), np.float32))


def _items(n, hw=(30, 41)):
    rng = np.random.default_rng(0)
    return [{"image1": rng.uniform(0, 255, hw + (3,)).astype(np.float32),
             "image2": rng.uniform(0, 255, hw + (3,)).astype(np.float32)}
            for _ in range(n)]


ENGINE_SPANS = {"engine:assemble", "engine:put", "engine:enqueue",
                "engine:wait", "engine:copy_out", "engine:deliver",
                "engine:caller"}


def test_engine_spans_split_dispatch_and_fetch():
    from dexiraft_tpu.serve import InferenceEngine, ServeConfig

    engine = InferenceEngine(_stub_eval, ServeConfig(batch_size=2, inflight=2))
    list(engine.stream(_items(2)))  # the bucket's first dispatch
    # a fresh signature's call goes to compile_s and not to the table
    assert set(profiling.snapshot("engine:")) == ENGINE_SPANS - {
        "engine:enqueue"}
    assert engine.compile_s > 0.0 and engine.stats.dispatch_s > 0.0

    engine.reset_stats()
    assert profiling.snapshot("engine:") == {}
    for r in engine.stream(_items(8)):
        time.sleep(0.002)  # the caller's work, on the engine's thread
    got = profiling.snapshot("engine:")
    assert set(got) == ENGINE_SPANS
    # one duration a batch for every span, rows summed
    assert {v["count"] for v in got.values()} == {engine.stats.batches} == {4}
    s = {k: v["seconds"] for k, v in got.items()}
    assert (s["engine:assemble"] + s["engine:put"] + s["engine:enqueue"]
            == pytest.approx(engine.stats.dispatch_s, abs=1e-6))
    assert (s["engine:wait"] + s["engine:copy_out"]
            == pytest.approx(engine.stats.fetch_s, abs=1e-6))
    assert s["engine:caller"] >= 8 * 0.002
    assert engine.stats.frames == 8 and engine.compile_s == 0.0


def test_engine_waits_for_a_device_array_before_it_copies_it_out():
    import jax.numpy as jnp

    from dexiraft_tpu.serve import InferenceEngine, ServeConfig

    def device_eval(im1, im2, flow_init=None):
        low, up = _stub_eval(im1, im2)
        return jnp.asarray(low), jnp.asarray(up) * 3.0

    engine = InferenceEngine(device_eval, ServeConfig(batch_size=2))
    out = engine.run_batch(_items(2))
    assert isinstance(out[0].flow_up, np.ndarray)
    np.testing.assert_array_equal(out[0].flow_up, np.full((30, 41, 2), 3.0))
    got = _counts("engine:")
    assert got["engine:wait"] == got["engine:copy_out"] == 1


def test_profiler_trace_holds_engine_spans_on_the_calling_threads_line(tmp_path):
    import jax

    from benchmarks import trace_reduce
    from dexiraft_tpu.serve import InferenceEngine, ServeConfig

    engine = InferenceEngine(_stub_eval, ServeConfig(batch_size=2))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        # the reducer keeps the lines that hold a `bench:` event: the
        # runner's own thread
        with jax.profiler.TraceAnnotation("bench:tail"):
            list(engine.stream(_items(4)))
    finally:
        jax.profiler.stop_trace()
    host = trace_reduce.load_xplane(
        trace_reduce.find_xplane(str(tmp_path)))["host"]
    names = {name for name, _, _ in host}
    # two dispatches: the bucket's first (compile), then a warm one
    assert ENGINE_SPANS <= names
    tail = next(e for e in host if e[0] == "bench:tail")
    inside = [e for e in host if e[0] == "engine:assemble"]
    assert len(inside) == 2
    assert all(tail[1] <= s and s + d <= tail[1] + tail[2]
               for _, s, d in inside)


# ---- the loader and the prefetcher ---------------------------------------------


class _TinyDS:
    def __len__(self):
        return 8

    def sample(self, index, rng):
        img = rng.normal(loc=index, size=(16, 24, 3)).astype(np.float32)
        return {"image1": img, "image2": img + 1.0}


def test_loader_spans_sit_inside_the_prefetchers_host_next():
    from dexiraft_tpu.data.loader import Loader
    from dexiraft_tpu.data.prefetch import DevicePrefetcher

    loader = Loader(_TinyDS(), batch_size=2, seed=3, num_workers=2)
    pf = DevicePrefetcher(loader.batches(), put=lambda b: b, depth=2)
    try:
        for _ in range(5):
            next(pf)
        got = profiling.snapshot("")
        s = {k: v["seconds"] for k, v in got.items()}
        assert (s["prefetch:host_next"] == pytest.approx(
            pf.stats.stall_s + pf.stats.warm_fill_s, abs=1e-6))
        assert (s["loader:wait"] + s["loader:stack"]
                <= s["prefetch:host_next"])
        # warm fill pulls depth + 1, then one pull a yield
        pulls = 3 + 4
        for name in ("prefetch:host_next", "prefetch:put", "loader:wait",
                     "loader:stack"):
            assert got[name]["count"] == pulls, name
        # workers run ahead: every sample the consumer took was decoded
        assert got["loader:decode"]["count"] >= pulls * 2

        pf.stats.reset()
        assert profiling.snapshot("prefetch:") == {}
        assert _counts("loader:")["loader:stack"] == pulls
        next(pf)
        assert _counts("prefetch:") == {"prefetch:host_next": 1,
                                        "prefetch:put": 1}
    finally:
        pf.close()

    # a new stream of the same Loader starts a new account
    again = loader.batches()
    try:
        next(again)
        assert _counts("loader:")["loader:stack"] == 1
    finally:
        again.close()


def test_the_call_that_finds_the_end_is_a_span_and_not_a_stall():
    from dexiraft_tpu.data.prefetch import DevicePrefetcher

    def two_then_a_slow_end():
        yield {"x": np.zeros(2)}
        yield {"x": np.zeros(2)}
        time.sleep(0.02)

    pf = DevicePrefetcher(two_then_a_slow_end(), put=lambda b: b, depth=1)
    assert len(list(pf)) == 2
    got = profiling.snapshot("prefetch:")
    assert got["prefetch:host_next"]["count"] == 3  # two batches, one end
    assert got["prefetch:put"]["count"] == 2
    *batches, end = got["prefetch:host_next"]["durations"]
    assert end >= 0.02
    assert sum(batches) == pytest.approx(
        pf.stats.stall_s + pf.stats.warm_fill_s, abs=1e-6)
    assert pf.stats.stalls == 0


# ---- the compile listener ---------------------------------------------------------


def test_listener_keeps_what_a_fresh_jit_costs_and_mark_warm_freezes_it():
    import jax
    import jax.numpy as jnp

    from dexiraft_tpu.analysis import guards

    watch = guards.RecompileWatch("spans-test")
    profiling.reset("jax:")
    before = guards.compile_count()

    @jax.jit
    def inner(x):
        return jnp.sin(x) * 2.0

    @jax.jit
    def outer(x):
        return inner(x) + inner(x * 2.0)

    t0 = time.perf_counter()
    jax.block_until_ready(outer(jnp.ones((7, 3))))
    wall = time.perf_counter() - t0
    got = profiling.snapshot("jax:")
    assert {"jax:trace", "jax:lower", "jax:backend_compile"} <= set(got)
    assert guards.compile_count() > before  # the drift count still counts
    # self times: the inner jit's trace is not counted twice, nor is a
    # cache read inside backend_compile, so the phases fit in the wall
    phases = sum(got[k]["seconds"] for k in (
        "jax:trace", "jax:lower", "jax:backend_compile", "jax:cache_load")
        if k in got)
    assert 0.0 < phases <= wall

    watch.mark_warm()
    frozen = guards.jax_at_warm()
    assert frozen == got
    jax.block_until_ready(outer(jnp.ones((5, 2))))  # a second signature
    assert watch.drift >= 1
    assert (profiling.snapshot("jax:")["jax:lower"]["count"]
            > got["jax:lower"]["count"])
    assert guards.jax_at_warm() == frozen == got


def test_self_seconds_takes_out_what_ran_inside():
    from dexiraft_tpu.analysis import guards

    # own thread: the listener's record of what it has seen is per thread
    out = {}

    def run():
        time.sleep(0.02)
        a = guards._self_seconds(0.01)    # a child: the last 10 ms
        time.sleep(0.02)
        b = guards._self_seconds(0.015)   # a second child
        c = guards._self_seconds(0.045)   # their parent, just ended
        d = guards._self_seconds(0.001)   # a sibling after it
        out.update(a=a, b=b, c=c, d=d)

    t = threading.Thread(target=run)
    t.start()
    t.join(30)
    assert not t.is_alive()
    assert out["a"] == 0.01 and out["b"] == 0.015 and out["d"] == 0.001
    assert out["c"] == pytest.approx(0.045 - 0.01 - 0.015)
