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


TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE = "/jax/compilation_cache/cache_retrieval_time_sec"


def _play(script):
    """JAX's begin ("b") and end ("e", with its seconds) events as the
    listeners are handed them, on this thread's stack."""
    from dexiraft_tpu.analysis import guards

    for kind, event, fun_name, *seconds in script:
        kw = {"fun_name": fun_name} if fun_name else {}
        if kind == "b":
            guards._on_begin(event, time.time(), **kw)
        else:
            guards._on_end(event, seconds[0], **kw)


def _played(*scripts):
    """Each script on a thread of its own (the stack is per thread), all
    at once; the `jax:` table afterwards as {name: (seconds, count)}."""
    profiling.reset("jax:")
    go = threading.Barrier(len(scripts))

    def run(script):
        go.wait(10)
        _play(script)

    pool = [threading.Thread(target=run, args=(s,)) for s in scripts]
    for t in pool:
        t.start()
    for t in pool:
        t.join(30)
    assert not any(t.is_alive() for t in pool)
    return {k: (pytest.approx(v["seconds"]), v["count"])
            for k, v in profiling.snapshot("jax:").items()}


STACK_CASES = {
    # an inner jit's trace inside a trace, inside the outer's lowering:
    # each event's seconds leave out what ended inside it, and all of
    # it is the root's
    "nested": (
        [[("b", LOWER, "jit(step)"), ("b", TRACE, "f"), ("b", TRACE, "g"),
          ("e", TRACE, "g", 0.1), ("e", TRACE, "f", 0.5),
          ("e", LOWER, "jit(step)", 2.0)]],
        {"jax:trace": (0.5, 1), "jax:trace/step": (0.5, 1),
         "jax:lower": (1.5, 1), "jax:lower/step": (1.5, 1)}),
    # one call's trace, lowering and compile are three roots of one
    # name; another program's are its own
    "siblings": (
        [[("b", TRACE, "step"), ("b", TRACE, "inner"),
          ("e", TRACE, "inner", 0.25), ("b", TRACE, "inner"),
          ("e", TRACE, "inner", 0.25), ("e", TRACE, "step", 1.0),
          ("b", LOWER, "jit(step)"), ("e", LOWER, "jit(step)", 0.5),
          ("b", TRACE, "loss"), ("e", TRACE, "loss", 2.0),
          ("b", LOWER, "pmap(loss)"), ("e", LOWER, "pmap(loss)", 0.125)]],
        {"jax:trace": (3.0, 2), "jax:trace/step": (1.0, 1),
         "jax:trace/loss": (2.0, 1), "jax:lower": (0.625, 2),
         "jax:lower/step": (0.5, 1), "jax:lower/loss": (0.125, 1)}),
    # the cache read has no begin: a leaf of the compile that is open,
    # which is then no miss; a compile with no read inside is one
    "cache_read": (
        [[("b", COMPILE, "jit(step)"), ("e", CACHE, "", 4.0),
          ("e", COMPILE, "jit(step)", 5.0),
          ("b", COMPILE, "jit(init)"), ("e", COMPILE, "jit(init)", 2.0)]],
        {"jax:backend_compile": (3.0, 2), "jax:cache_load": (4.0, 1),
         "jax:backend_compile/step": (1.0, 1),
         "jax:cache_load/step": (4.0, 1),
         "jax:backend_compile/init": (2.0, 1),
         "jax:uncached/init": (2.0, 1)}),
    # a begin whose end JAX skipped goes when its parent ends, and what
    # ended inside it ended inside the parent; an end with no begin and
    # nothing open is a root of its own; the stack is whole afterwards
    "unmatched": (
        [[("b", TRACE, "step"), ("b", TRACE, "lost"), ("b", TRACE, "inner"),
          ("e", TRACE, "inner", 0.125), ("e", TRACE, "step", 1.0),
          ("e", LOWER, "jit(ghost)", 0.5), ("e", CACHE, "", 0.25),
          ("b", TRACE, "after"), ("e", TRACE, "after", 0.75)]],
        {"jax:trace": (1.75, 2), "jax:trace/step": (1.0, 1),
         "jax:trace/after": (0.75, 1), "jax:lower": (0.5, 1),
         "jax:lower/ghost": (0.5, 1), "jax:cache_load": (0.25, 1),
         "jax:cache_load/?": (0.25, 1)}),
    # a thread's open frames are its own
    "two_threads": (
        [[("b", TRACE, "a"), ("b", TRACE, "inner"),
          ("e", TRACE, "inner", 0.5), ("e", TRACE, "a", 1.0)],
         [("b", LOWER, "jit(b)"), ("b", TRACE, "inner"),
          ("e", TRACE, "inner", 0.25), ("e", LOWER, "jit(b)", 2.0)]],
        {"jax:trace": (1.25, 2), "jax:trace/a": (1.0, 1),
         "jax:trace/b": (0.25, 1), "jax:lower": (1.75, 1),
         "jax:lower/b": (1.75, 1)}),
}


@pytest.mark.parametrize("case", sorted(STACK_CASES))
def test_stack_books_self_seconds_to_the_root_that_caused_them(case):
    scripts, want = STACK_CASES[case]
    assert _played(*scripts) == want


def _fresh_programs():
    """A jitted function that calls an inner jit, under names no other
    test compiles."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def spans_inner(x):
        return jnp.sin(x) * 2.0

    @jax.jit
    def spans_outer(x):
        return spans_inner(x) + spans_inner(x * 2.0)

    return spans_outer


PHASES = ("jax:trace", "jax:lower", "jax:backend_compile", "jax:cache_load")


def test_a_fresh_jit_books_one_root_with_its_inner_jit_inside():
    import jax
    import jax.numpy as jnp

    outer = _fresh_programs()
    x = jax.block_until_ready(jnp.ones((7, 3)))  # eager programs: before
    profiling.reset("jax:")
    jax.block_until_ready(outer(x))
    got = profiling.snapshot("jax:")
    roots = {name.partition("/")[2] for name in got} - {""}
    assert roots == {"spans_outer"}     # the inner's seconds are inside
    for phase in ("jax:trace", "jax:lower", "jax:backend_compile"):
        # one add a root frame, not one an event
        assert got[phase + "/spans_outer"]["count"] == 1
    jax.block_until_ready(outer(jnp.ones((2, 2))))  # eager roots beside it
    got = profiling.snapshot("jax:")
    assert got["jax:lower/spans_outer"]["count"] == 2
    for phase in PHASES:
        under = [v for k, v in got.items() if k.startswith(phase + "/")]
        if phase in got:
            assert got[phase]["seconds"] == pytest.approx(
                sum(v["seconds"] for v in under))
            assert got[phase]["count"] == sum(v["count"] for v in under)
        else:
            assert not under


def test_drift_warning_names_the_function_that_recompiled():
    import io

    import jax
    import jax.numpy as jnp

    from dexiraft_tpu.analysis import guards

    outer = _fresh_programs()
    a, b = jnp.ones((7, 3)), jnp.ones((5, 2))
    jax.block_until_ready(outer(a))
    watch = guards.RecompileWatch("spans-test")
    watch.mark_warm()
    assert watch.recompiled() == []
    jax.block_until_ready(outer(b))   # a second signature: drift
    with watch.sanctioned():          # a planned compile keeps no name
        jax.block_until_ready(outer(jnp.ones((4, 4))))
    jax.block_until_ready(outer(b * 1.0))
    jax.block_until_ready(outer(jnp.ones((3, 9))))
    assert watch.recompiled().count("spans_outer") == 2 <= watch.drift
    buf = io.StringIO()
    assert watch.warn_if_drifted(file=buf)
    assert "recompile(s) after warmup: " in buf.getvalue()
    assert "spans_outer x2" in buf.getvalue()
    with pytest.raises(guards.RecompileBudgetExceeded, match="spans_outer x2"):
        watch.check()


@pytest.fixture
def persistent_cache(tmp_path):
    """The persistent compilation cache in a directory of the test's."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_enable_compilation_cache",
        "jax_persistent_cache_min_entry_size_bytes",
        "jax_persistent_cache_min_compile_time_secs")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compilation_cache.reset_cache()
    yield tmp_path
    for k, v in was.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_a_root_compiled_with_the_cache_off_counts_as_uncached():
    import jax
    import jax.numpy as jnp

    assert not jax.config.jax_compilation_cache_dir
    outer = _fresh_programs()
    x = jax.block_until_ready(jnp.ones((7, 3)))
    profiling.reset("jax:")
    jax.block_until_ready(outer(x))
    got = profiling.snapshot("jax:")
    assert got["jax:uncached/spans_outer"]["count"] == 1
    assert "jax:cache_load" not in got


def test_a_root_that_read_its_cache_entry_is_not_uncached(persistent_cache):
    import jax
    import jax.numpy as jnp

    from dexiraft_tpu.analysis import guards

    x = jax.block_until_ready(jnp.ones((7, 3)))
    profiling.reset("jax:")
    jax.block_until_ready(_fresh_programs()(x))   # a miss: written
    cold = profiling.snapshot("jax:")
    assert cold["jax:uncached/spans_outer"]["count"] == 1
    assert "jax:cache_load/spans_outer" not in cold

    profiling.reset("jax:")
    jax.block_until_ready(_fresh_programs()(x))   # a new jit: read back
    guards.RecompileWatch("spans-test").mark_warm()
    warm = guards.jax_at_warm()
    assert "jax:uncached/spans_outer" not in warm
    assert warm["jax:cache_load/spans_outer"]["count"] == 1
    assert warm["jax:cache_load/spans_outer"]["seconds"] > 0.0
    (row,) = guards.setup_report(floor_s=0.0)
    assert row["root"] == "spans_outer"
    assert (row["programs"], row["uncached"]) == (1, 0)
    assert row["seconds"] == pytest.approx(
        row["trace_s"] + row["lower_s"] + row["backend_compile_s"]
        + row["cache_load_s"])
    assert row["seconds"] == pytest.approx(sum(
        warm[p]["seconds"] for p in PHASES if p in warm))
    assert guards.setup_line().startswith("[setup] jax spent ")
    (folded,) = guards.setup_report(floor_s=1e9)
    assert folded == {**row, "root": "other"}
