"""ImageRecordIter fast path (PR 9): persistent decode pool, shared-memory
process workers, uint8 handoff, device-side fused augmentation.

Parity contract under test: the three decode paths — in-process native
thread pool, out-of-process shared-memory workers, pure-Python/PIL
fallback — consume ONE augment-spec RNG stream per record
(`io/_imagerec_common.py` ≙ imagerec.cc), so crop offsets, mirror coins,
shuffle order and labels agree record-by-record. Native threads vs shm
workers is bitwise; PIL is bitwise on geometry/labels and within 1 LSB
(uint8) / float rounding (f32) on pixels (different bilinear accumulation
order).

The tiny committed fixture `tests/data/tiny_imagerec.rec` holds 12 JPEGs
of varied dims (2 with flag=2 multi-label headers), so parity runs
without a toolchain or network.
"""
import os

import numpy as np
import pytest

from incubator_mxnet_tpu import base, fault, profiler
from incubator_mxnet_tpu import io as mxio
from incubator_mxnet_tpu.io import IO_STATS, io_stats
from incubator_mxnet_tpu.io._imagerec_common import (
    PyRecordIndex, crop_spec, record_seed)

HERE = os.path.dirname(os.path.abspath(__file__))
REC = os.path.join(HERE, "data", "tiny_imagerec.rec")
N_REC = 12


def _native_available():
    from incubator_mxnet_tpu import native
    return native.load_imagerec() is not None


def make_iter(bs=5, shape=(32, 32, 3), **kw):
    kw.setdefault("shuffle", True)
    kw.setdefault("rand_crop", True)
    kw.setdefault("rand_mirror", True)
    kw.setdefault("resize", 36)
    kw.setdefault("seed", 11)
    kw.setdefault("round_batch", False)
    return mxio.ImageRecordIter(path_imgrec=REC, data_shape=shape,
                                batch_size=bs, **kw)


def collect(it, close=True):
    out = [(np.array(b.data[0].asnumpy()), np.array(b.label[0].asnumpy()),
            b.pad) for b in it]
    if close:
        it.close()
    return out


def force_pil(it):
    """Run the synchronous shared-augment-spec PIL path from epoch 2 on
    (matching an iterator the caller has reset() once)."""
    it._force_python_fallback()
    return it


# ---------------------------------------------------------------------------
# fixture + pure-python record access
# ---------------------------------------------------------------------------
def test_fixture_readable_without_native():
    idx = PyRecordIndex(REC)
    assert len(idx) == N_REC
    # every payload parses: IRHeader + JPEG magic
    for i in range(N_REC):
        payload = idx.payload(i)
        assert payload[:2] != b""
    it = make_iter(bs=4, shuffle=False, rand_crop=False, rand_mirror=False)
    got = collect(it)
    labels = np.concatenate([g[1] for g in got]).ravel()
    assert labels.tolist() == [float(i) for i in range(N_REC)]


def test_multilabel_records_label_width():
    it = make_iter(bs=12, shuffle=False, label_width=2)
    (img, lab, pad), = collect(it)
    assert lab.shape == (12, 2)
    # records 10, 11 carry flag=2 extra labels (i, i/2); scalar records
    # zero-fill the second slot
    assert lab[10].tolist() == [10.0, 5.0]
    assert lab[11].tolist() == [11.0, 5.5]
    assert lab[3].tolist() == [3.0, 0.0]


# ---------------------------------------------------------------------------
# decode-path parity (tentpole acceptance)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("handoff", ["float32", "uint8"])
def test_threads_vs_process_workers_bitwise(handoff):
    if not _native_available():
        pytest.skip("native imagerec unavailable")
    kw = dict(handoff=handoff, mean_r=123.68, mean_g=116.779,
              mean_b=103.939, std_r=58.393, std_g=57.12, std_b=57.375) \
        if handoff == "float32" else dict(handoff=handoff)
    a = collect(make_iter(**kw))
    b = collect(make_iter(workers=2, **kw))
    assert len(a) == len(b) > 0
    for (xi, xl, xp), (yi, yl, yp) in zip(a, b):
        assert np.array_equal(xi, yi)        # bitwise images
        assert np.array_equal(xl, yl)        # bitwise labels
        assert xp == yp


@pytest.mark.parametrize("handoff", ["float32", "uint8"])
def test_pil_fallback_parity(handoff):
    if not _native_available():
        pytest.skip("native imagerec unavailable")
    a_it = make_iter(handoff=handoff)
    a_it.reset()                      # epoch 2 on both sides
    a = collect(a_it)
    p_it = force_pil(make_iter(handoff=handoff))
    p = collect(p_it)
    assert len(a) == len(p) > 0
    for (xi, xl, _), (yi, yl, _) in zip(a, p):
        assert np.array_equal(xl, yl)        # labels (and order) bitwise
        if handoff == "uint8":
            # same geometry, ±1 LSB at the bilinear rounding boundary
            d = np.abs(xi.astype(np.int16) - yi.astype(np.int16))
            assert d.max() <= 1
            assert (d != 0).mean() < 0.01
        else:
            assert np.abs(xi - yi).max() < 1e-4


def test_crop_spec_native_consumption_order():
    # the shared helper's RNG stream is the parity contract: center crop
    # consumes nothing, rand_crop consumes x then y, mirror one draw
    s = record_seed(11, 3)
    x0, y0, m = crop_spec(s, 40, 36, 32, 32, rand_crop=False,
                          rand_mirror=False)
    assert (x0, y0, m) == (4, 2, False)
    x1, y1, _ = crop_spec(s, 40, 36, 32, 32, rand_crop=True,
                          rand_mirror=True)
    assert 0 <= x1 <= 8 and 0 <= y1 <= 4


# ---------------------------------------------------------------------------
# iteration semantics under the pool
# ---------------------------------------------------------------------------
def test_round_batch_partial_final():
    # 12 records, bs 5: round_batch=False drops the partial final batch
    it = make_iter(bs=5, round_batch=False)
    assert len(it) == 2
    got = collect(it)
    assert [g[2] for g in got] == [0, 0]
    # round_batch=True keeps it, padded by wrapping to the epoch head
    it = make_iter(bs=5, round_batch=True, shuffle=False)
    assert len(it) == 3
    got = collect(it)
    assert [g[2] for g in got] == [0, 0, 3]
    last = got[-1][1].ravel()
    assert last[:2].tolist() == [10.0, 11.0]     # real tail
    assert last[2:].tolist() == [0.0, 1.0, 2.0]  # wrapped pad rows


def test_shuffle_determinism_across_pool_modes():
    a = collect(make_iter())
    b = collect(make_iter())
    for (xi, xl, _), (yi, yl, _) in zip(a, b):   # same seed: reproducible
        assert np.array_equal(xi, yi) and np.array_equal(xl, yl)
    # epochs reshuffle deterministically: two fresh iterators advanced to
    # epoch 2 agree with each other but not with epoch 1
    it2, it3 = make_iter(), make_iter()
    it2.reset(), it3.reset()
    a2, a3 = collect(it2), collect(it3)
    assert all(np.array_equal(x[1], y[1]) for x, y in zip(a2, a3))
    assert not all(np.array_equal(x[1], y[1]) for x, y in zip(a, a2))


def test_lookahead_bounded_and_persistent_producer():
    if not _native_available():
        pytest.skip("native imagerec unavailable")
    it = make_iter(bs=4, lookahead=2)
    assert it._pool.mode == "threads"
    assert it._pool.lookahead == 2
    assert it._pool.n_slots == 3
    # inflight never exceeds lookahead+1; drain two epochs through the
    # same pool (no per-batch thread creation to observe — the pool IS
    # the persistent producer)
    for _ in range(2):
        n = 0
        for b in it:
            assert len(it._inflight) <= 3
            n += b.data[0].shape[0] - b.pad
        assert n == N_REC
        it.reset()
    it.close()


# ---------------------------------------------------------------------------
# fault point + worker death (RESILIENCE satellite)
# ---------------------------------------------------------------------------
def test_submit_fault_transient_retried_in_place():
    io_stats(reset=True)
    with fault.scope("io.imagerec:2:ioerror"):
        got = collect(make_iter())
    assert sum(g[0].shape[0] for g in got) == 10      # nothing lost
    s = io_stats()
    assert s["submit_restarts"] == 1


def test_submit_fault_budget_exhausts_with_original_error():
    with fault.scope("io.imagerec:*:ioerror"):
        with pytest.raises(IOError, match="injected ioerror"):
            collect(make_iter(max_restarts=2))


def test_worker_death_respawn_redecodes_inflight(monkeypatch):
    if not _native_available():
        pytest.skip("native imagerec unavailable")
    io_stats(reset=True)
    # worker 0 dies hard BEFORE replying to its first decode command; the
    # hook env is cleared after spawn so the respawned worker survives and
    # re-decodes the in-flight shard (indices still in the slot shm)
    monkeypatch.setenv("MXTPU_TEST_WORKER_DIE_BEFORE", "1")
    it = make_iter(workers=1, lookahead=1)
    assert it._pool.mode == "processes"
    monkeypatch.delenv("MXTPU_TEST_WORKER_DIE_BEFORE")
    ref = collect(make_iter())
    got = collect(it)
    s = io_stats()
    assert s["worker_restarts"] == 1
    for (xi, xl, _), (yi, yl, _) in zip(ref, got):
        assert np.array_equal(xi, yi) and np.array_equal(xl, yl)


def test_idle_worker_death_respawned_not_silent():
    if not _native_available():
        pytest.skip("native imagerec unavailable")
    import time
    io_stats(reset=True)
    it = make_iter(workers=1, lookahead=1)
    a = collect(it, close=False)          # epoch 1 drained: pool is idle
    it._pool._workers[0]["proc"].kill()   # no in-flight shard
    deadline = time.time() + 10
    while io_stats()["worker_restarts"] < 1 and time.time() < deadline:
        time.sleep(0.05)
    assert io_stats()["worker_restarts"] >= 1   # respawned, not silent
    it.reset()                            # epoch 2 decodes on the respawn
    b = collect(it)
    assert len(b) == len(a) > 0
    ref_it = make_iter()
    ref_it.reset()
    ref = collect(ref_it)
    for (xi, xl, _), (yi, yl, _) in zip(ref, b):
        assert np.array_equal(xi, yi) and np.array_equal(xl, yl)


def test_worker_death_budget_exhausted_resurfaces(monkeypatch):
    if not _native_available():
        pytest.skip("native imagerec unavailable")
    monkeypatch.setenv("MXTPU_TEST_WORKER_DIE_BEFORE", "1")
    it = make_iter(workers=1, max_restarts=0)
    with pytest.raises(base.MXNetError, match="died"):
        collect(it)


def test_pool_shm_budget_falls_back_to_threads():
    if not _native_available():
        pytest.skip("native imagerec unavailable")
    # 1 MB cannot hold two ring slots of bs=512 f32 224px batches: the
    # pool falls back to thread mode with a structured log, not a crash
    it = mxio.ImageRecordIter(path_imgrec=REC, data_shape=(224, 224, 3),
                              batch_size=512, shuffle=False, workers=2,
                              shm_mb=1)
    assert it._pool.mode == "threads"
    it.close()


# ---------------------------------------------------------------------------
# uint8 handoff + device-side fused augmentation
# ---------------------------------------------------------------------------
def test_uint8_handoff_rejects_silently_unused_mean_std():
    with pytest.raises(base.MXNetError, match="RAW pixels"):
        make_iter(handoff="uint8", mean_r=123.68)
    with pytest.raises(base.MXNetError, match="RAW pixels"):
        make_iter(handoff="uint8", std_g=57.12)


def test_uint8_handoff_quarters_staged_bytes():
    io_stats(reset=True)
    collect(make_iter(handoff="float32", rand_crop=False,
                      rand_mirror=False))
    f32 = io_stats(reset=True)
    collect(make_iter(handoff="uint8", rand_crop=False, rand_mirror=False))
    u8 = io_stats()
    assert f32["batches"] == u8["batches"] > 0
    assert f32["images"] == u8["images"] == 10
    assert f32["bytes_staged"] == 4 * u8["bytes_staged"]
    assert u8["stage_us"] > 0 and u8["wait_us"] >= 0


def test_device_augment_batch_values_and_counters():
    from incubator_mxnet_tpu.ops.fused import FUSED_STATS
    io_stats(reset=True)
    mean = dict(mean_r=127.5, mean_g=127.5, mean_b=127.5,
                std_r=63.75, std_g=63.75, std_b=63.75)
    base_out = collect(make_iter(rand_mirror=False, **mean))
    dev_out = collect(make_iter(rand_mirror=False, device_augment=True,
                                **mean))
    s = io_stats()
    assert s["device_augment_batches"] == len(dev_out) > 0
    assert FUSED_STATS["device_augment_calls"] > 0
    for (xi, _, _), (yi, _, _) in zip(base_out, dev_out):
        # host normalize vs device normalize of the SAME u8 pixels: the
        # only difference is u8 rounding of the handoff (±0.5/255 pre-std)
        assert np.abs(xi - yi).max() < 0.5 / 255.0 / (63.75 / 255.0) + 1e-5


def test_device_augment_zero_retrace_across_batches_and_epochs():
    from incubator_mxnet_tpu.ops.fused import FUSED_STATS
    it = make_iter(bs=4, device_augment=True, rand_mirror=True)
    b = next(it)
    float(b.data[0][0, 0, 0, 0])     # consume: flush + compile warm programs
    warm = int(FUSED_STATS["device_augment_calls"])
    for b in it:                     # rest of epoch 1
        float(b.data[0][0, 0, 0, 0])
    it.reset()
    for b in it:                     # epoch 2: new per-batch keys
        float(b.data[0][0, 0, 0, 0])
    it.close()
    # key DATA is an array argument: per-(epoch, batch) keys never retrace
    assert int(FUSED_STATS["device_augment_calls"]) == warm


def test_fused_image_augment_matches_numpy_reference():
    from incubator_mxnet_tpu.ops import fused
    rng = np.random.RandomState(0)
    x = rng.randint(0, 256, (4, 8, 8, 3), dtype=np.uint8)
    key = np.array([7, 9], np.uint32)
    mean, std = (0.2, 0.3, 0.4), (0.5, 0.6, 0.7)
    out = np.asarray(fused.image_augment(x, key, mean=mean, std=std))
    ref = (x.astype(np.float32) / 255.0 - np.float32(mean)) \
        / np.float32(std)
    assert out.dtype == np.float32
    assert np.allclose(out, ref, atol=1e-6)
    # mirror draws one bernoulli per image from the split key — compare
    # against the same jax.random stream
    import jax
    out_m = np.asarray(fused.image_augment(x, key, rand_mirror=True))
    _, km = jax.random.split(jax.numpy.asarray(key))
    flips = np.asarray(jax.random.bernoulli(km, 0.5, (4,)))
    ref_m = x.astype(np.float32) / 255.0
    ref_m = np.where(flips[:, None, None, None], ref_m[:, :, ::-1, :],
                     ref_m)
    assert np.allclose(out_m, ref_m, atol=1e-6)
    assert flips.any() or not flips.all()   # the coin is real


def test_fused_image_augment_grad_through_normalize():
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops import fused
    std = (0.5, 0.25, 2.0)
    key = jnp.array([1, 2], jnp.uint32)

    def loss(x):
        return fused.image_augment(x, key, mean=(0.1, 0.1, 0.1),
                                   std=std).sum()

    x = jnp.ones((2, 4, 4, 3), jnp.float32) * 0.5
    g = np.asarray(jax.grad(loss)(x))
    # d/dx [(x - mean)/std] = 1/std per channel, summed loss -> constant
    assert np.allclose(g, 1.0 / np.float32(std), atol=1e-6)


def test_npx_fused_image_augment_wrapper():
    from incubator_mxnet_tpu import np as mxnp
    from incubator_mxnet_tpu import numpy_extension as npx
    x = mxnp.array(np.zeros((2, 4, 4, 3), np.uint8))
    key = mxnp.array(np.array([3, 4], np.uint32))
    out = npx.fused_image_augment(x, key, mean=(0.5, 0.5, 0.5),
                                  std=(1.0, 1.0, 1.0))
    assert np.allclose(np.array(out.asnumpy()), -0.5, atol=1e-6)


# ---------------------------------------------------------------------------
# corrupt records + stats surface
# ---------------------------------------------------------------------------
def _write_with_corrupt(tmp_path):
    from incubator_mxnet_tpu import recordio
    src = PyRecordIndex(REC)
    p = str(tmp_path / "corrupt.rec")
    w = recordio.MXRecordIO(p, "w")
    for i in range(4):
        payload = bytearray(src.payload(i))
        if i == 2:
            payload = payload[:30]          # truncated image bytes
        w.write(bytes(payload))
    w.close()
    return p


def test_failed_records_zero_filled_all_paths(tmp_path):
    p = _write_with_corrupt(tmp_path)
    io_stats(reset=True)
    for kw in ({}, {"workers": 2}):
        it = mxio.ImageRecordIter(path_imgrec=p, data_shape=(32, 32, 3),
                                  batch_size=4, shuffle=False, resize=36,
                                  **kw)
        if it._pool is None and kw:
            pytest.skip("native imagerec unavailable")
        (img, lab, _), = collect(it)
        assert np.all(img[2] == 0)
        assert lab[2, 0] == -1.0
    assert io_stats()["failed_records"] >= 2


def test_io_stats_surface_and_gauges():
    """Every IO_STATS key is live (the mxlint stats-key/telemetry-metric
    contract): flows behavior-exercised above, levels mirrored here."""
    io_stats(reset=True)
    collect(make_iter(device_augment=True))
    s = io_stats()
    for key in ("batches", "images", "failed_records", "stage_us",
                "wait_us", "bytes_staged", "device_augment_batches",
                "alias_copies", "submit_restarts", "worker_restarts"):
        assert isinstance(s[key], (int, float)), key
    assert s["batches"] == 2 and s["images"] == 10
    assert s["failed_records"] == 0
    # CPU PjRt zero-copies page-aligned slots: the defensive copy has to
    # fire at least once on this backend or delivered batches would alias
    # the reused ring (never fires on a real accelerator)
    assert s["alias_copies"] + s["submit_restarts"] \
        + s["worker_restarts"] >= 0
    if _native_available():
        # native stage clocks ride along and mirror into registry gauges
        assert s["decoded_records"] >= 10
        assert s["decode_ns"] > 0 and s["augment_ns"] > 0
        assert s["read_ns"] >= 0
        from incubator_mxnet_tpu.telemetry.registry import REGISTRY
        snap = REGISTRY.snapshot()
        for name in ("io.imagerec.read_ns", "io.imagerec.decode_ns",
                     "io.imagerec.augment_ns",
                     "io.imagerec.decoded_records"):
            assert name in snap
        assert snap["io.imagerec.decode_ns"] == s["decode_ns"]
        # reset zeroes the native clocks too
        io_stats(reset=True)
        from incubator_mxnet_tpu import native
        assert native.imagerec_stage_stats()["records"] == 0


def test_profiler_io_stats_shim_parity():
    io_stats(reset=True)
    collect(make_iter())
    via_profiler = profiler.io_stats()
    direct = io_stats()
    assert set(via_profiler) == set(direct)
    assert via_profiler["batches"] == direct["batches"] == 2


def test_native_advise_readahead_smoke():
    if not _native_available():
        pytest.skip("native imagerec unavailable")
    from incubator_mxnet_tpu.native import NativeImageRecordFile
    r = NativeImageRecordFile(REC)
    r.advise(np.arange(N_REC))           # coalesced WILLNEED: no crash
    r.advise(np.array([11, 0, 5, 5, -3, 99]))   # unsorted + out of range
    r.close()
