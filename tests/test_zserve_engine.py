"""Throughput-mode inference engine (dexiraft_tpu.serve): bucket
pad/unpad round-trips, partial-batch tail masking, eval-forward batch
invariance, engine-vs-per-image metric parity, per-item warm-start
carry, and the empty-valid-mask sparse-metrics fix.

Named to sort LAST in collection (the test_zpipeline_async.py
convention): the tier-1 suite runs under a hard 870 s wall-clock cap
(ROADMAP.md), and inserting new files mid-order would displace the
long-standing tail tests out of the budget window.
"""

import numpy as np
import pytest

from dexiraft_tpu.data.padder import InputPadder
from dexiraft_tpu.serve import InferenceEngine, ServeConfig, bucket_shape


def _stub_eval(im1, im2, flow_init=None):
    """Constant (2, -1) prediction at any batch/geometry; warm-start
    rows add their (upsampled-by-repeat) flow_init so per-item carry is
    observable. flow_low is a PER-ITEM constant derived from the input
    (sub-pixel, so forward_interpolate round-trips it) — a zero
    flow_low would make every warm-start carry vanish and leave the
    carry ROUTING (which row feeds which sequence) unpinned."""
    b, h, w = im1.shape[:3]
    up = np.broadcast_to(np.float32([2.0, -1.0]), (b, h, w, 2)).copy()
    if flow_init is not None:
        up = up + np.repeat(np.repeat(np.asarray(flow_init), 8, 1), 8, 2)
    means = np.asarray(im1).reshape(b, -1).mean(axis=1) / 255.0  # (0, 1)
    low = np.zeros((b, h // 8, w // 8, 2), np.float32)
    low[..., 0] = means[:, None, None] * 0.4
    low[..., 1] = -0.2 * means[:, None, None]
    return low, up


def _items(geoms, seed=0):
    rng = np.random.default_rng(seed)
    return [{"image1": rng.uniform(0, 255, (h, w, 3)).astype(np.float32),
             "image2": rng.uniform(0, 255, (h, w, 3)).astype(np.float32)}
            for h, w in geoms]


class TestBuckets:
    def test_bucket_shape_quantizes_up(self):
        assert bucket_shape(30, 41) == (32, 48)          # stride default
        assert bucket_shape(32, 48) == (32, 48)          # aligned unchanged
        assert bucket_shape(33, 49, multiple=16) == (48, 64)
        with pytest.raises(ValueError):
            bucket_shape(30, 41, multiple=12)            # not stride-aligned

    def test_padder_target_roundtrip_both_modes(self):
        rng = np.random.default_rng(0)
        x = rng.normal(size=(37, 53, 2)).astype(np.float32)
        for mode in ("sintel", "kitti"):
            p = InputPadder(x.shape, mode=mode, target=(48, 64))
            (px,) = p.pad(x)
            assert px.shape == (48, 64, 2) and p.padded_shape == (48, 64)
            np.testing.assert_array_equal(p.unpad(px), x)

    def test_padder_target_matches_reference_when_stride_aligned(self):
        # target = next stride multiple reproduces the reference pad
        # placement bit for bit (the metric-parity configuration)
        x = np.arange(30 * 41 * 3, dtype=np.float32).reshape(30, 41, 3)
        ref = InputPadder(x.shape, mode="sintel")
        gen = InputPadder(x.shape, mode="sintel", target=(32, 48))
        np.testing.assert_array_equal(ref.pad(x)[0], gen.pad(x)[0])

    def test_padder_rejects_bad_targets(self):
        with pytest.raises(ValueError):
            InputPadder((40, 56, 3), target=(32, 56))    # smaller than input
        with pytest.raises(ValueError):
            InputPadder((40, 56, 3), target=(44, 56))    # not stride-aligned


def _edge_pad_reference(x, padder):
    """What the engine fed the model before `pad_into`: the frame as
    float32, then numpy's own edge pad at the padder's widths."""
    l, r, t, b = padder._pad
    return np.pad(np.asarray(x, np.float32), [(t, b), (l, r), (0, 0)],
                  mode="edge")


# (frame, target): no pad; the stride pad; a bucket larger than the next
# stride multiple, pads on all four sides in sintel mode; a pad one pixel
# wide (right column and bottom row alone)
_PAD_CASES = {
    "no_pad": ((40, 56), None),
    "stride_pad": ((37, 53), None),
    "bucket_target": ((37, 53), (48, 72)),
    "one_pixel": ((39, 55), None),
}


class TestPadInto:
    @pytest.mark.parametrize("dtype", [np.uint8, np.int32, np.float32,
                                       np.float64])
    @pytest.mark.parametrize("case", sorted(_PAD_CASES))
    @pytest.mark.parametrize("mode", ["sintel", "kitti"])
    def test_matches_numpy_edge_pad_bit_for_bit(self, mode, case, dtype):
        hw, target = _PAD_CASES[case]
        rng = np.random.default_rng(7)
        x = rng.uniform(0, 255, hw + (3,))
        if dtype is np.int32:
            x = x * 1e5  # past float32's 24 bits: the cast must round alike
        x = x.astype(dtype)
        p = InputPadder(x.shape, mode=mode, target=target)
        ref = _edge_pad_reference(x, p)
        out = np.full(p.padded_shape + (3,), np.nan, np.float32)
        p.pad_into(out, x)
        assert out.shape == ref.shape
        assert out.tobytes() == ref.tobytes()
        if case == "bucket_target" and mode == "sintel":
            assert all(p._pad)  # the case covers all four sides

    def test_leading_axes_and_other_dtypes(self):
        # a batch of frames at once, into a float64 buffer: the
        # assignment casts to whatever `out` holds
        x = np.random.default_rng(8).integers(0, 255, (2, 37, 53, 3),
                                              dtype=np.uint8)
        p = InputPadder(x.shape, mode="sintel", target=(48, 64))
        out = np.full((2, 48, 64, 3), np.nan, np.float64)
        p.pad_into(out, x)
        np.testing.assert_array_equal(out, p.pad(x.astype(np.float64))[0])

    def test_rejects_a_buffer_of_another_shape(self):
        p = InputPadder((37, 53, 3), target=(48, 64))
        with pytest.raises(ValueError, match="padded shape"):
            p.pad_into(np.empty((40, 56, 3), np.float32),
                       np.zeros((37, 53, 3), np.uint8))


class _Keeps:
    """An eval_fn that records the batches it is handed and answers
    with a flow made of its inputs (channel 0 of each frame), so a
    Result says whose frames were in its row."""

    def __init__(self):
        self.seen = []

    def flow(self, im1, im2):
        up = np.stack([im1[..., 0], im2[..., 0]], axis=-1)
        return up[:, ::8, ::8].copy(), up

    def __call__(self, im1, im2, flow_init=None):
        self.seen.append((im1, im2))
        return self.flow(im1, im2)


class _AtFetch:
    """A device future's stand-in: nothing is read before the fetch."""

    def __init__(self, read):
        self.read = read

    def __array__(self, dtype=None, copy=None):
        return self.read()


class _KeepsUntilFetch(_Keeps):
    """Keeps its inputs and reads them only when the engine fetches the
    ticket: what a device does whose host-to-device copy is still
    reading the buffer after `device_put` returned. A buffer written to
    between the hand-over and the fetch fails the read."""

    def __call__(self, im1, im2, flow_init=None):
        self.seen.append((im1, im2))
        handed = im1.tobytes(), im2.tobytes()

        def read(part):
            assert (im1.tobytes(), im2.tobytes()) == handed
            return self.flow(im1, im2)[part]

        return _AtFetch(lambda: read(0)), _AtFetch(lambda: read(1))


def _own_flow(item):
    return np.stack([np.asarray(item["image1"], np.float32)[..., 0],
                     np.asarray(item["image2"], np.float32)[..., 0]], -1)


class TestAssemble:
    @pytest.mark.parametrize("n", [1, 3])
    def test_group_shorter_than_the_batch(self, n):
        # tail rows hold the last item, and count as pad frames
        items = _items([(37, 53)] * n, seed=3)
        fn = _Keeps()
        eng = InferenceEngine(fn, ServeConfig(batch_size=4),
                              put=lambda batch: batch)
        out = eng.run_batch([dict(it) for it in items])
        assert len(out) == n and eng.stats.pad_frames == 4 - n
        (im1, im2), = fn.seen
        assert im1.shape == im2.shape == (4, 40, 56, 3)
        assert im1.dtype == im2.dtype == np.float32
        p = InputPadder((37, 53, 3), mode="sintel", target=(40, 56))
        for row in range(4):
            it = items[min(row, n - 1)]
            assert (im1[row].tobytes()
                    == _edge_pad_reference(it["image1"], p).tobytes())
            assert (im2[row].tobytes()
                    == _edge_pad_reference(it["image2"], p).tobytes())

    @pytest.mark.parametrize("mode", ["sintel", "kitti"])
    def test_mixed_frame_sizes_and_dtypes_in_one_bucket(self, mode):
        geoms = [(40, 56), (44, 60), (36, 52), (48, 64)]
        items = _items(geoms, seed=4)
        for it, dt in zip(items, (np.uint8, np.float64, np.int32,
                                  np.float32)):
            it["image1"] = it["image1"].astype(dt)
            it["image2"] = it["image2"].astype(dt)
        fn = _Keeps()
        eng = InferenceEngine(fn, ServeConfig(
            batch_size=4, bucket_multiple=16, mode=mode),
            put=lambda batch: batch)
        out = eng.run_batch([dict(it) for it in items])
        (im1, im2), = fn.seen
        assert eng.stats.pad_frames == 0
        for row, it in enumerate(items):
            p = InputPadder(it["image1"].shape, mode=mode, target=(48, 64))
            assert (im1[row].tobytes()
                    == _edge_pad_reference(it["image1"], p).tobytes())
            assert (im2[row].tobytes()
                    == _edge_pad_reference(it["image2"], p).tobytes())
            np.testing.assert_array_equal(out[row].flow_up, _own_flow(it))

    # what the rings may pin: nothing (every batch gets fresh buffers),
    # one of the two buckets' rings (they evict each other), everything
    @pytest.mark.parametrize("ring_bytes", [0, 4 * 2 * 2 * 40 * 56 * 12,
                                            None])
    @pytest.mark.parametrize("inflight", [1, 2, 3])
    def test_a_batch_keeps_its_frames_until_it_is_fetched(
            self, inflight, ring_bytes, monkeypatch):
        # the buffer-reuse hazard: batch k is read at its fetch, after
        # the batches behind it in the window were assembled into the
        # bucket's other buffers. `put` hands the engine's own buffers
        # through, as a backend that aliases host memory would
        from dexiraft_tpu.serve import engine as engine_module

        if ring_bytes is not None:
            monkeypatch.setattr(engine_module, "_RING_BYTES", ring_bytes)
        geoms = [(37, 53), (30, 41)] * 9  # two buckets, interleaved
        items = _items(geoms, seed=5)
        fn = _KeepsUntilFetch()
        eng = InferenceEngine(fn, ServeConfig(batch_size=2,
                                              inflight=inflight),
                              put=lambda batch: batch)
        got = {r.index: r.flow_up
               for r in eng.stream(dict(it) for it in items)}
        assert sorted(got) == list(range(len(items)))
        assert eng.stats.peak_inflight == inflight
        for i, it in enumerate(items):
            np.testing.assert_array_equal(got[i], _own_flow(it))
        assert len(fn.seen) == eng.stats.batches == 10
        held = sum(a.nbytes + b.nbytes for ring in eng._rings.values()
                   for a, b in ring)
        assert held <= engine_module._RING_BYTES
        buffers = {id(im1) for im1, _ in fn.seen}
        if ring_bytes is None:
            # five batches a bucket through inflight + 1 buffers each
            assert len(buffers) == 2 * min(5, inflight + 1)
        elif ring_bytes == 0:
            assert len(buffers) == 10 and not eng._rings

    def test_assembly_allocates_no_second_batch(self):
        # one pass: the two batch buffers and nothing else of their size
        import tracemalloc

        b, hw = 4, (250, 317)
        rng = np.random.default_rng(6)
        items = [{"image1": rng.integers(0, 255, hw + (3,), dtype=np.uint8),
                  "image2": rng.integers(0, 255, hw + (3,), dtype=np.uint8)}
                 for _ in range(b)]

        def fn(im1, im2, flow_init=None):  # answers without allocating
            bh, bw = im1.shape[1:3]
            return (np.broadcast_to(np.float32(0), (b, bh // 8, bw // 8, 2)),
                    np.broadcast_to(np.float32(0), (b, bh, bw, 2)))

        eng = InferenceEngine(fn, ServeConfig(batch_size=b),
                              put=lambda batch: batch)
        buffers = 2 * b * 256 * 320 * 3 * 4
        tracemalloc.start()
        try:
            eng.run_batch(items)
            peak = tracemalloc.get_traced_memory()[1]
        finally:
            tracemalloc.stop()
        assert buffers <= peak < 1.25 * buffers


class TestEngineStream:
    def test_partial_batch_tail_masked(self):
        # 5 frames over 2 buckets at batch 2: tails pad up to the batch
        # shape on device but yield EXACTLY the dataset back
        items = _items([(30, 41), (30, 41), (30, 41), (62, 70), (62, 70)])
        eng = InferenceEngine(_stub_eval, ServeConfig(batch_size=2))
        got = sorted(eng.stream(items), key=lambda r: r.index)
        assert [r.index for r in got] == [0, 1, 2, 3, 4]
        for r, it in zip(got, items):
            assert r.flow_up.shape == it["image1"].shape[:2] + (2,)
            np.testing.assert_allclose(r.flow_up, np.float32([2.0, -1.0])
                                       * np.ones_like(r.flow_up))
        assert eng.stats.frames == 5
        assert eng.stats.pad_frames == 1                 # the 30x41 tail
        assert eng.registry.stats()["bucket_count"] == 2
        assert eng.registry.compiles == 2                # one per bucket

    def test_bucket_multiple_bounds_executables(self):
        # three geometries collapse into one bucket at multiple=16
        items = _items([(40, 56), (44, 60), (36, 52), (40, 56)])
        eng = InferenceEngine(
            _stub_eval, ServeConfig(batch_size=2, bucket_multiple=16))
        got = list(eng.stream(items))
        assert len(got) == 4
        assert eng.registry.stats()["buckets"] == {"48x64": 4}
        assert eng.registry.compiles == 1

    def test_inflight_window_respected(self):
        items = _items([(30, 41)] * 7)
        eng = InferenceEngine(
            _stub_eval, ServeConfig(batch_size=1, inflight=3))
        assert len(list(eng.stream(items))) == 7
        assert eng.stats.peak_inflight == 3

    def test_run_batch_rejects_leftover_inflight(self):
        # silently fetching (and discarding) an unfinished stream()'s
        # tickets would lose frames — the engine must refuse instead
        items = _items([(30, 41)] * 4)
        eng = InferenceEngine(_stub_eval,
                              ServeConfig(batch_size=1, inflight=2))
        it = eng.stream(items)
        next(it)  # leaves dispatched tickets behind
        with pytest.raises(RuntimeError, match="in flight"):
            eng.run_batch([items[0]])

    def test_per_item_flow_init_rows(self):
        # one warm row + one cold row ride the same batch; zeros == cold
        items = _items([(32, 48), (32, 48)])
        items[0]["flow_init"] = np.full((4, 6, 2), 0.5, np.float32)
        eng = InferenceEngine(
            _stub_eval, ServeConfig(batch_size=2, warm_start=True))
        out = eng.run_batch(items)
        np.testing.assert_allclose(out[0].flow_up[0, 0], [2.5, -0.5])
        np.testing.assert_allclose(out[1].flow_up[0, 0], [2.0, -1.0])
        assert eng.registry.compiles == 1                # one signature


@pytest.fixture(scope="module")
def small_eval():
    """Real small-RAFT eval step + variables (one init, many tests)."""
    import jax

    from dexiraft_tpu.config import TrainConfig, raft_v1
    from dexiraft_tpu.train.state import create_state
    from dexiraft_tpu.train.step import make_eval_step

    cfg = raft_v1(small=True)
    tc = TrainConfig(num_steps=10, batch_size=2, image_size=(40, 56), iters=2)
    state = create_state(jax.random.PRNGKey(0), cfg, tc)
    variables = {"params": state.params, "batch_stats": state.batch_stats}
    step = make_eval_step(cfg, iters=2)
    return dict(
        cfg=cfg,
        variables=variables,
        step=step,
        fn=lambda a, b, flow_init=None: step(variables, a, b,
                                             flow_init=flow_init),
    )


class TestRealModel:
    def test_eval_forward_batch_invariant(self, small_eval):
        # batch of 3 == 3 batches of 1: eval-mode BN normalizes with
        # running stats, so no cross-item coupling survives
        rng = np.random.default_rng(1)
        im1 = rng.uniform(0, 255, (3, 40, 56, 3)).astype(np.float32)
        im2 = rng.uniform(0, 255, (3, 40, 56, 3)).astype(np.float32)
        _, up_batched = small_eval["fn"](im1, im2)
        for i in range(3):
            _, up_one = small_eval["fn"](im1[i:i + 1], im2[i:i + 1])
            np.testing.assert_allclose(np.asarray(up_batched)[i],
                                       np.asarray(up_one)[0], atol=1e-4)

    def test_engine_matches_per_image_metrics(self, small_eval):
        # the acceptance pin: --batch_size N metrics == batch-size-1
        # metrics (fp32 tolerance) on a tiny synthetic dataset
        from dexiraft_tpu.eval.validate import validate_chairs

        class DS:
            def __len__(self):
                return 3

            def sample(self, i, rng=None):
                r = np.random.default_rng(i)
                return {
                    "image1": r.uniform(0, 255, (37, 53, 3)).astype(np.float32),
                    "image2": r.uniform(0, 255, (37, 53, 3)).astype(np.float32),
                    "flow": np.broadcast_to(np.float32([2.0, -1.0]),
                                            (37, 53, 2)).copy(),
                    "valid": np.ones((37, 53), np.float32),
                }

        ref = validate_chairs(small_eval["fn"], DS())
        batched = validate_chairs(small_eval["fn"], DS(), batch_size=2)
        np.testing.assert_allclose(batched["chairs"], ref["chairs"],
                                   rtol=1e-5, atol=1e-5)

    def test_data_parallel_engine_matches_single_chip(self, small_eval):
        # the first multi-chip eval path: batch sharded over 'data',
        # pinned in_shardings, per-item results identical
        from dexiraft_tpu.parallel.mesh import make_serve_mesh
        from dexiraft_tpu.train.step import make_eval_step

        mesh = make_serve_mesh(2)
        stepm = make_eval_step(small_eval["cfg"], iters=2, mesh=mesh)
        variables = small_eval["variables"]
        items = _items([(37, 53)] * 3, seed=2)
        single = InferenceEngine(
            lambda a, b, fi: small_eval["step"](variables, a, b,
                                                flow_init=fi),
            ServeConfig(batch_size=2))
        sharded = InferenceEngine(
            lambda a, b, fi: stepm(variables, a, b, None, None, fi),
            ServeConfig(batch_size=2), mesh=mesh)
        ref = {r.index: r.flow_up
               for r in single.stream(dict(it) for it in items)}
        got = {r.index: r.flow_up
               for r in sharded.stream(dict(it) for it in items)}
        for i in ref:
            np.testing.assert_allclose(got[i], ref[i], atol=1e-4)

    def test_engine_rejects_indivisible_mesh_batch(self, small_eval):
        from dexiraft_tpu.parallel.mesh import make_serve_mesh

        with pytest.raises(ValueError, match="divisible"):
            InferenceEngine(small_eval["fn"], ServeConfig(batch_size=3),
                            mesh=make_serve_mesh(2))


class TestSparseMetricsFix:
    def _ds(self, empty_frames=()):
        class DS:
            def __len__(self):
                return 3

            def sample(self, i, rng=None):
                r = np.random.default_rng(i)
                s = {
                    "image1": r.uniform(0, 255, (32, 48, 3)).astype(np.float32),
                    "image2": r.uniform(0, 255, (32, 48, 3)).astype(np.float32),
                    "flow": np.broadcast_to(np.float32([2.0, -1.0]),
                                            (32, 48, 2)).copy(),
                    "valid": np.zeros((32, 48), np.float32)
                    if i in empty_frames
                    else np.ones((32, 48), np.float32),
                }
                return s

        return DS()

    def test_empty_mask_frame_skipped_not_nan(self, capsys):
        from dexiraft_tpu.eval.validate import validate_kitti

        res = validate_kitti(_stub_eval, self._ds(empty_frames=(1,)))
        assert np.isfinite(res["kitti-epe"])             # NaN before the fix
        np.testing.assert_allclose(res["kitti-epe"], 0.0, atol=1e-5)
        assert "1 empty-mask frames skipped" in capsys.readouterr().out

    def test_all_empty_raises(self):
        from dexiraft_tpu.eval.validate import _sparse_metrics

        with pytest.raises(ValueError, match="empty valid mask"):
            _sparse_metrics(_stub_eval, self._ds(empty_frames=(0, 1, 2)),
                            "kitti")

    def test_batched_sparse_matches_per_image(self):
        from dexiraft_tpu.eval.validate import validate_kitti

        ref = validate_kitti(_stub_eval, self._ds(empty_frames=(2,)))
        got = validate_kitti(_stub_eval, self._ds(empty_frames=(2,)),
                             batch_size=2)
        np.testing.assert_allclose(got["kitti-epe"], ref["kitti-epe"],
                                   atol=1e-6)
        np.testing.assert_allclose(got["kitti-f1"], ref["kitti-f1"],
                                   atol=1e-6)


class TestBatchedSubmission:
    def test_sintel_batched_equals_per_frame(self, tmp_path):
        """Two sequences abreast with per-item warm-start carry write
        byte-identical .flo trees to the reference per-frame loop."""
        from dexiraft_tpu.data.flow_io import read_flo
        from dexiraft_tpu.eval.submission import create_sintel_submission

        class SintelStub:
            def __init__(self, lens=(3, 2)):
                self.extra_info = [(f"seq_{s}", j)
                                   for s, n in enumerate(lens)
                                   for j in range(n)]

            def __len__(self):
                return len(self.extra_info)

            def sample(self, i, rng=None):
                r = np.random.default_rng(i)
                return {"image1": r.uniform(0, 255, (36, 48, 3))
                        .astype(np.float32),
                        "image2": r.uniform(0, 255, (36, 48, 3))
                        .astype(np.float32),
                        "extra_info": self.extra_info[i]}

        for warm in (True, False):  # False = the pipelined stream() path
            outs = {}
            for bs in (1, 2):
                out = tmp_path / f"sub_w{warm}_b{bs}"
                create_sintel_submission(
                    _stub_eval, output_path=str(out), warm_start=warm,
                    datasets={"clean": SintelStub()}, batch_size=bs)
                outs[bs] = {p.relative_to(out): read_flo(p)
                            for p in sorted(out.rglob("*.flo"))}
            assert set(outs[1]) == set(outs[2]) and len(outs[1]) == 5
            for name in outs[1]:
                np.testing.assert_allclose(outs[2][name], outs[1][name],
                                           atol=1e-5, err_msg=str(name))

    def test_kitti_batched_equals_per_frame(self, tmp_path):
        from dexiraft_tpu.data.flow_io import read_flow_kitti
        from dexiraft_tpu.eval.submission import create_kitti_submission

        class KittiStub:
            def __len__(self):
                return 3

            def sample(self, i, rng=None):
                r = np.random.default_rng(i)
                return {"image1": r.uniform(0, 255, (30, 41, 3))
                        .astype(np.float32),
                        "image2": r.uniform(0, 255, (30, 41, 3))
                        .astype(np.float32),
                        "extra_info": [f"{i:06d}_10.png"]}

        for bs in (1, 2):
            create_kitti_submission(_stub_eval,
                                    output_path=str(tmp_path / f"k{bs}"),
                                    dataset=KittiStub(), batch_size=bs)
        for i in range(3):
            a, _ = read_flow_kitti(tmp_path / "k1" / f"{i:06d}_10.png")
            b, _ = read_flow_kitti(tmp_path / "k2" / f"{i:06d}_10.png")
            np.testing.assert_allclose(b, a, atol=1e-6)
