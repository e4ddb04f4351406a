"""mx.fault.elastic + mx.optimizer.sharded (ISSUE 12 acceptance): ZeRO
optimizer-state sharding over the dp mesh axis, bucketed reduce-scatter /
all-gather through the kvstore timeline, manifest-committed per-shard
checkpoints, bit-exact resume onto the same AND a smaller dp mesh under
fault injection, straggler attribution, and graceful mesh shrink."""
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import checkpoint as ckpt
from incubator_mxnet_tpu import fault
from incubator_mxnet_tpu import kvstore as kv
from incubator_mxnet_tpu import telemetry
from incubator_mxnet_tpu.fault import elastic
from incubator_mxnet_tpu.optimizer import sharded as shz

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_faults():
    fault.clear()
    yield
    fault.clear()


def _need8():
    import jax
    if len(jax.devices()) < 8:
        pytest.skip("needs the forced 8-device mesh")
    return jax.devices()


# ---------------------------------------------------------------------------
# shard math
# ---------------------------------------------------------------------------
def test_shard_math_roundtrip_and_uneven_repartition():
    a = np.arange(10, dtype=np.float32).reshape(2, 5)
    v3 = shz.to_shards(a, 3)                 # numel 10 -> (3, 4), padded
    assert v3.shape == (3, 4)
    np.testing.assert_array_equal(
        shz.from_shards(v3, 10, (2, 5)), a)
    v2 = shz.repartition(v3, 10, 2)          # uneven 3 -> 2
    assert v2.shape == (2, 5)
    np.testing.assert_array_equal(shz.from_shards(v2, 10, (2, 5)), a)
    assert v2.dtype == np.float32


def test_shard_math_preserves_dtype_and_scalars():
    for dt in (np.float16, np.float64, np.int32):
        a = (np.arange(7) + 1).astype(dt)
        v = shz.repartition(shz.to_shards(a, 4), 7, 5)
        assert v.dtype == dt
        np.testing.assert_array_equal(shz.from_shards(v, 7), a)
    s = shz.to_shards(np.float32(3.5), 4)    # 0-d: one real element
    assert s.shape == (4, 1)
    assert shz.from_shards(s, 1, ()) == np.float32(3.5)


# ---------------------------------------------------------------------------
# bucketed collectives (the kvstore ZeRO data path)
# ---------------------------------------------------------------------------
def _mesh(dp):
    import jax
    devs = _need8()
    return jax.sharding.Mesh(np.array(devs[:dp]), ("dp",))


def _stack(mesh, per_replica):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    spec = P("dp", *([None] * (per_replica.ndim - 1)))
    return jax.device_put(per_replica, NamedSharding(mesh, spec))


def test_reduce_scatter_buckets_values_and_stats():
    mesh = _mesh(8)
    rng = np.random.RandomState(0)
    grads = [rng.randn(8, 10).astype(np.float32),
             rng.randn(8, 3, 3).astype(np.float32),
             # second dtype bucket (f16 — jax would demote a f64 to f32)
             rng.randn(8, 5).astype(np.float16)]
    base = kv.KV_STATS.snapshot()
    outs = kv.reduce_scatter_buckets([_stack(mesh, g) for g in grads],
                                     mesh, scale=1.0 / 8)
    for g, o in zip(grads, outs):
        n = int(np.prod(g.shape[1:]))
        L = -(-n // 8)
        assert o.shape == (8, L)
        assert np.asarray(o).dtype == g.dtype
        got = np.asarray(o).reshape(-1)[:n]
        np.testing.assert_allclose(
            got, g.reshape(8, -1).astype(np.float64).mean(axis=0)
            .astype(g.dtype), rtol=5e-3 if g.dtype == np.float16
            else 1e-5)
        # padding rows are exact zeros (moment shards stay clean)
        np.testing.assert_array_equal(np.asarray(o).reshape(-1)[n:], 0)
    snap = kv.KV_STATS.snapshot()
    assert snap["reduce_scatter_buckets"] >= base["reduce_scatter_buckets"] + 2
    assert snap["reduce_scatter_us"] > base["reduce_scatter_us"]
    assert snap["reduce_scatter_bytes"] >= base["reduce_scatter_bytes"] + (
        10 * 4 + 9 * 4 + 5 * 2)


def test_allgather_buckets_values_and_stats():
    mesh = _mesh(8)
    a = np.arange(20, dtype=np.float32).reshape(4, 5)
    shard = _stack(mesh, shz.to_shards(a, 8))
    base = kv.KV_STATS.snapshot()
    outs = kv.allgather_buckets([shard], [(20, (4, 5))], mesh)
    np.testing.assert_array_equal(np.asarray(outs[0]), a)
    snap = kv.KV_STATS.snapshot()
    assert snap["allgather_buckets"] > base["allgather_buckets"]
    assert snap["allgather_us"] > base["allgather_us"]
    assert snap["allgather_bytes"] >= base["allgather_bytes"] + 20 * 4


def test_collective_fault_points_fire():
    mesh = _mesh(4)
    g = _stack(mesh, np.ones((4, 6), np.float32))
    with fault.scope("kvstore.reduce_scatter:1:ioerror"):
        with pytest.raises(IOError):
            kv.reduce_scatter_buckets([g], mesh)
    s = _stack(mesh, shz.to_shards(np.ones(6, np.float32), 4))
    with fault.scope("kvstore.allgather:1:timeout"):
        with pytest.raises(TimeoutError):
            kv.allgather_buckets([s], [(6, (6,))], mesh)


# ---------------------------------------------------------------------------
# ShardedOptimizer: memory + parity against the dense rules
# ---------------------------------------------------------------------------
def _mlp_problem(dim=12, batch=32):
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    params = {"w1": rng.randn(dim, 8).astype(np.float32) / 3,
              "b1": np.zeros(8, np.float32),
              "w2": rng.randn(8, 1).astype(np.float32) / 3}

    def loss_fn(p, b):
        h = jnp.tanh(b["x"] @ p["w1"] + p["b1"])
        return jnp.mean((h @ p["w2"] - b["y"]) ** 2)

    def batch_fn(step):
        r = np.random.RandomState(1000 + step)
        return {"x": r.randn(batch, dim).astype(np.float32),
                "y": r.randn(batch, 1).astype(np.float32)}
    return params, loss_fn, batch_fn


def _dense_reference(params, loss_fn, batch_fn, optimizer, steps,
                     **opt_kwargs):
    """The unsharded trajectory: full-gradient + plain Optimizer.update."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu import optimizer as opt_mod
    from incubator_mxnet_tpu.ndarray import array as nd_array
    o = opt_mod.create(optimizer, **opt_kwargs)
    ref = {k: v.copy() for k, v in params.items()}
    states = {}
    names = sorted(ref)
    for s in range(steps):
        b = {k: jnp.asarray(v) for k, v in batch_fn(s).items()}
        g = jax.grad(lambda pl: loss_fn(dict(zip(names, pl)), b))(
            [jnp.asarray(ref[n]) for n in names])
        for n, gi in zip(names, g):
            wnd, gnd = nd_array(ref[n]), nd_array(np.asarray(gi))
            if n not in states:
                states[n] = o.create_state(n, wnd)
            o.update(n, wnd, gnd, states[n])
            ref[n] = wnd.asnumpy()
    return ref


@pytest.mark.parametrize("opt_name,opt_kwargs", [
    ("sgd", {"momentum": 0.9, "learning_rate": 0.05}),
    ("adam", {"learning_rate": 0.01}),      # exercises the traced-t path
])
def test_sharded_trainer_matches_dense_optimizer(opt_name, opt_kwargs):
    _need8()
    params, loss_fn, batch_fn = _mlp_problem()
    tr = elastic.ElasticTrainer(loss_fn, params, optimizer=opt_name,
                                dp=8, **opt_kwargs)
    for s in range(5):
        tr.step(batch_fn(s))
    ref = _dense_reference(params, loss_fn, batch_fn, opt_name, 5,
                           **opt_kwargs)
    got = tr.state_arrays()
    for n in ref:
        np.testing.assert_allclose(got[n], ref[n], rtol=2e-5, atol=2e-6)


def test_state_memory_per_replica_drops_linearly_with_dp():
    _need8()
    params, loss_fn, _ = _mlp_problem(dim=64)
    mems = {}
    for dp in (2, 8):
        tr = elastic.ElasticTrainer(loss_fn, params, optimizer="sgd",
                                    dp=dp, momentum=0.9)
        mems[dp] = tr.mem_per_replica_bytes()
    # ZeRO acceptance: per-replica state scales ~1/dp (exact here —
    # shard padding is the only slack and these shapes divide evenly)
    assert mems[2] / mems[8] == pytest.approx(4.0, rel=0.05)
    assert telemetry.snapshot()["elastic.mem_per_replica_bytes"] == mems[8]


def test_sharded_optimizer_rejects_unshardable_rules():
    mesh = _mesh(2)
    from incubator_mxnet_tpu.optimizer.sharded import ShardedOptimizer
    with pytest.raises(mx.MXNetError, match="fused_safe"):
        ShardedOptimizer("nadam", mesh)   # per-step host state (m_schedule)


# ---------------------------------------------------------------------------
# collective retry / straggler watchdog
# ---------------------------------------------------------------------------
def test_transient_collective_error_is_retried_and_counted():
    _need8()
    params, loss_fn, batch_fn = _mlp_problem()
    tr = elastic.ElasticTrainer(loss_fn, params, optimizer="sgd", dp=4,
                                momentum=0.9, collective_retries=2)
    base = telemetry.snapshot().get("elastic.collective_retries", 0)
    # transient: the FIRST bucket dispatch fails once, the retry clears
    fault.install("kvstore.reduce_scatter", "ioerror", at=1)
    tr.step(batch_fn(0))
    assert telemetry.snapshot()["elastic.collective_retries"] == base + 1


def test_persistent_collective_error_exhausts_retry_budget():
    _need8()
    params, loss_fn, batch_fn = _mlp_problem()
    tr = elastic.ElasticTrainer(loss_fn, params, optimizer="sgd", dp=4,
                                momentum=0.9, collective_retries=1)
    fault.install("kvstore.reduce_scatter", "ioerror", at=1,
                  persistent=True)
    with pytest.raises(IOError):
        tr.step(batch_fn(0))


def test_straggler_report_healthy_and_stalled():
    mesh = _mesh(4)
    rep = elastic.straggler_report(mesh, probe_timeout=10.0)
    assert [r["rank"] for r in rep] == [0, 1, 2, 3]
    assert all(r["ok"] for r in rep)

    def wedged(rank, device):
        if rank == 2:
            time.sleep(60)
    rep = elastic.straggler_report(mesh, probe_timeout=0.3,
                                   probe_fn=wedged)
    assert [r["rank"] for r in rep if not r["ok"]] == [2]


def test_collective_stall_raises_straggler_timeout_naming_rank():
    _need8()
    params, loss_fn, batch_fn = _mlp_problem()

    def wedged(rank, device):
        if rank == 1:
            time.sleep(60)
    tr = elastic.ElasticTrainer(loss_fn, params, optimizer="sgd", dp=4,
                                momentum=0.9, collective_timeout=0.4,
                                collective_retries=0, probe_fn=wedged)
    fault.install("kvstore.reduce_scatter", "stall", at=1, arg=5)
    with pytest.raises(elastic.StragglerTimeout) as ei:
        tr.step(batch_fn(0))
    assert ei.value.stalled_ranks == [1]
    assert "rank" in str(ei.value)
    assert any(r["rank"] == 1 and not r["ok"] for r in ei.value.report)


# ---------------------------------------------------------------------------
# run_elastic: crash -> bit-exact resume (same mesh, quadratic model)
# ---------------------------------------------------------------------------
def _run(params, loss_fn, batch_fn, d, dp, steps, **kw):
    kw.setdefault("momentum", 1.0)
    kw.setdefault("learning_rate", 0.25)
    return elastic.run_elastic(loss_fn, params, batch_fn, d, steps,
                               optimizer="sgd", dp=dp, ckpt_every=3, **kw)


def _lattice_problem():
    """Linear-in-w loss with integer data on an exact f32 lattice: every
    reduction order (dp=8 vs dp=4 group sums) yields IDENTICAL bits, so
    cross-mesh parity tests the checkpoint/repartition protocol, not
    float summation order (same trick as tools/crashtest.py --elastic)."""
    import jax.numpy as jnp

    def loss_fn(p, batch):
        return jnp.mean(batch["c"] @ p["w"]) + 0.0 * jnp.sum(p["v"])

    def batch_fn(step):
        r = np.random.RandomState(7 + step)
        return {"c": r.randint(-8, 9, (64, 12)).astype(np.float32)}

    params = {"w": (np.arange(12, dtype=np.float32) - 6) / 4.0,
              "v": np.ones((3, 5), np.float32)}
    return params, loss_fn, batch_fn


def _assert_state_parity(ref_run, got_run):
    rp, gp = ref_run.params(), got_run.params()
    ro, go = ref_run.opt_state(), got_run.opt_state()
    for n in rp:
        np.testing.assert_array_equal(rp[n], gp[n])
        np.testing.assert_array_equal(ro[n], go[n])


def test_crash_resume_same_mesh_bit_exact_params_and_opt(tmp_path):
    _need8()
    params, loss_fn, batch_fn = _mlp_problem()
    kw = dict(momentum=0.9, learning_rate=0.05)
    ref = _run(params, loss_fn, batch_fn, str(tmp_path / "ref"), 8, 10,
               **kw)
    d = str(tmp_path / "crash")
    # ioerror (NOT InjectedFault): a plain crash, not simulated worker
    # loss — the run must die, not shrink
    fault.install("elastic.step", "ioerror", at=6)
    with pytest.raises(IOError):
        _run(params, loss_fn, batch_fn, d, 8, 10, **kw)
    fault.clear()
    assert ckpt.latest_step(d) == 3    # last committed before the crash
    res = _run(params, loss_fn, batch_fn, d, 8, 10, **kw)
    assert res.resumed_from == 3
    assert res.resumed_dp == 8
    _assert_state_parity(ref, res)


def test_crash_resume_smaller_mesh_bit_exact(tmp_path):
    _need8()
    params, loss_fn, batch_fn = _lattice_problem()
    ref = _run(params, loss_fn, batch_fn, str(tmp_path / "ref"), 8, 10)
    d = str(tmp_path / "crash")
    fault.install("elastic.step", "ioerror", at=6)
    with pytest.raises(IOError):
        _run(params, loss_fn, batch_fn, d, 8, 10)
    fault.clear()
    base_resumes = telemetry.snapshot().get("elastic.resumes", 0)
    res = _run(params, loss_fn, batch_fn, d, 4, 10)   # ELASTIC restart
    assert res.resumed_from == 3
    assert res.resumed_dp == 4
    assert res.trainer.dp == 4
    _assert_state_parity(ref, res)
    snap = telemetry.snapshot()
    assert snap["elastic.resumes"] == base_resumes + 1
    assert snap["elastic.resume_latency_us"] > 0
    assert snap["elastic.dp"] == 4


def test_elastic_resume_fault_point_retries(tmp_path):
    _need8()
    params, loss_fn, batch_fn = _lattice_problem()
    d = str(tmp_path / "ck")
    _run(params, loss_fn, batch_fn, d, 8, 6)
    fault.install("elastic.resume", "ioerror", at=1)   # transient
    res = _run(params, loss_fn, batch_fn, d, 8, 6)
    assert res.resumed_from == 6


def test_graceful_shrink_on_worker_loss_preserves_parity(tmp_path):
    _need8()
    params, loss_fn, batch_fn = _lattice_problem()
    ref = _run(params, loss_fn, batch_fn, str(tmp_path / "ref"), 8, 10)
    base = telemetry.snapshot().get("elastic.mesh_shrinks", 0)
    # InjectedFault mid-run = simulated unrecoverable worker loss: the
    # run must shrink the mesh and finish, not die
    fault.install("kvstore.allgather", "error", at=9)
    res = _run(params, loss_fn, batch_fn, str(tmp_path / "shrink"), 8, 10)
    fault.clear()
    assert res.shrinks == 1
    assert res.dp_history == [8, 4]
    assert res.trainer.dp == 4
    _assert_state_parity(ref, res)
    assert telemetry.snapshot()["elastic.mesh_shrinks"] == base + 1


def test_recurring_worker_loss_keeps_degrading_to_min_dp(tmp_path):
    """A worker that STAYS dead fails the shrunk trainer's own first
    allgather too: the recovery must keep shrinking toward min_dp and
    only then re-raise — not die on the first failed shrink."""
    _need8()
    params, loss_fn, batch_fn = _lattice_problem()
    fault.install("kvstore.allgather", "error", at=9, persistent=True)
    with pytest.raises(fault.InjectedFault):
        _run(params, loss_fn, batch_fn, str(tmp_path / "d"), 8, 10,
             min_dp=2)
    fault.clear()
    # every allowed size was attempted before giving up: 8 -> 4 -> 2
    # (the log records the attempts; dp 1 < min_dp stops the loop)


def test_worker_loss_below_min_dp_reraises(tmp_path):
    _need8()
    params, loss_fn, batch_fn = _lattice_problem()
    fault.install("kvstore.allgather", "error", at=3)
    with pytest.raises(fault.InjectedFault):
        _run(params, loss_fn, batch_fn, str(tmp_path / "d"), 8, 6,
             min_dp=8)


def test_skip_nonfinite_is_crash_consistent(tmp_path, caplog):
    _need8()
    import logging
    params, loss_fn, batch_fn = _lattice_problem()
    d = str(tmp_path / "skip")
    # poison the loss at step 2 (nan), then crash at step hit 5
    fault.install("elastic.loss", "nan", at=2)
    fault.install("elastic.step", "ioerror", at=5)
    with pytest.raises(IOError):
        _run(params, loss_fn, batch_fn, d, 8, 10)
    fault.clear()
    entry = ckpt.latest_entry(d)
    assert entry["extra"]["elastic_run"]["skipped_nonfinite"] == 1
    with caplog.at_level(logging.INFO, logger="mxnet.fault"):
        res = _run(params, loss_fn, batch_fn, d, 8, 10)
    # the resumed run CONTINUES the count instead of resetting it ...
    assert res.skipped_nonfinite == 1
    # ... and the event log shows the restored accounting
    assert any("elastic.resumed" in r.getMessage()
               for r in caplog.records)
    # the skipped step never advanced the state: one fewer update than
    # steps (momentum=1.0 makes each update's delta distinct)
    ref_skip = _run(params, loss_fn, batch_fn, str(tmp_path / "r2"), 8, 10)
    # reference run had no skip: trajectories must DIFFER
    assert not np.array_equal(ref_skip.params()["w"], res.params()["w"])


# ---------------------------------------------------------------------------
# telemetry surface
# ---------------------------------------------------------------------------
def test_elastic_metric_names_registered_and_live():
    _need8()
    params, loss_fn, batch_fn = _mlp_problem()
    tr = elastic.ElasticTrainer(loss_fn, params, optimizer="sgd", dp=4,
                                momentum=0.9)
    base_steps = telemetry.snapshot().get("elastic.steps", 0)
    tr.step(batch_fn(0))
    snap = telemetry.snapshot()
    for name in ("elastic.steps", "elastic.resumes",
                 "elastic.mesh_shrinks", "elastic.skipped_nonfinite",
                 "elastic.collective_retries",
                 "elastic.resume_latency_us",
                 "elastic.mem_per_replica_bytes", "elastic.dp"):
        assert name in snap, name
    assert snap["elastic.steps"] == base_steps + 1
    assert snap["elastic.dp"] == 4
    # span lanes: kv.reduce_scatter / kv.allgather / elastic.step all
    # recorded through the span histogram
    assert snap.get('span.count{name="kv.reduce_scatter"}', 0) > 0
    assert snap.get('span.count{name="kv.allgather"}', 0) > 0
    assert snap.get('span.count{name="elastic.step"}', 0) > 0


def test_step_timeline_gains_zero_collective_lanes():
    _need8()
    params, loss_fn, batch_fn = _mlp_problem()
    tr = elastic.ElasticTrainer(loss_fn, params, optimizer="sgd", dp=4,
                                momentum=0.9)
    tl = telemetry.StepTimeline(name="elastic.tl")
    for s in range(2):
        with tl.step():
            tr.step(batch_fn(s))
    rep = tl.report()
    assert rep["reduce_scatter_us"] > 0
    assert rep["allgather_us"] > 0
    assert rep["reduce_scatter_buckets"] > 0
    assert rep["allgather_buckets"] > 0
    # compute is the remainder AFTER the new lanes
    assert rep["compute_us"] <= rep["total_us"] - rep["reduce_scatter_us"] \
        - rep["allgather_us"] + 1.0


# ---------------------------------------------------------------------------
# kvstore barrier timeout (unit wiring; the 2-process end-to-end run is
# tests/test_multiprocess_dist.py::test_two_process_barrier_timeout_...)
# ---------------------------------------------------------------------------
def test_barrier_timeout_typed_error_names_missing_ranks(monkeypatch):
    store = kv.create("dist_sync")
    monkeypatch.setattr(store, "_dist_active", lambda: True)
    monkeypatch.setattr(store, "_barrier_announce", lambda seq: None)
    monkeypatch.setattr(store, "_barrier_sync",
                        lambda seq: time.sleep(30))
    monkeypatch.setattr(store, "_barrier_missing_ranks", lambda seq: [2])
    monkeypatch.setenv("MXNET_KVSTORE_BARRIER_TIMEOUT", "0.3")
    t0 = time.time()
    with pytest.raises(kv.BarrierTimeout) as ei:
        store.barrier()
    assert time.time() - t0 < 5.0
    assert ei.value.missing_ranks == [2]
    assert "rank(s) 2 never arrived" in str(ei.value)


def test_barrier_legacy_timeout_alias_still_works(monkeypatch):
    store = kv.create("dist_sync")
    monkeypatch.setattr(store, "_dist_active", lambda: True)
    monkeypatch.setattr(store, "_barrier_announce", lambda seq: None)
    monkeypatch.setattr(store, "_barrier_sync",
                        lambda seq: time.sleep(30))
    monkeypatch.setattr(store, "_barrier_missing_ranks", lambda seq: [])
    monkeypatch.delenv("MXNET_KVSTORE_BARRIER_TIMEOUT", raising=False)
    monkeypatch.setenv("MXNET_KV_BARRIER_TIMEOUT", "0.3")
    with pytest.raises(kv.BarrierTimeout, match="unknown"):
        store.barrier()


def test_barrier_without_timeout_or_dist_is_noop():
    store = kv.create("local")
    store.barrier()    # single process: local waitall only, no timeout


# ---------------------------------------------------------------------------
# overlap accounting + crashtest harness
# ---------------------------------------------------------------------------
def test_overlap_fraction_counts_steps_not_time():
    """`overlap_fraction()` is an event count (steps whose reduce-scatter
    was dispatched while the backward was still in flight, over steps):
    None before any step, a share afterwards, one sample a step. How large
    it is on a chip is not asserted: a CPU timing proves nothing."""
    _need8()
    params, loss_fn, batch_fn = _mlp_problem()
    tr = elastic.ElasticTrainer(loss_fn, params, optimizer="sgd", dp=8,
                                momentum=0.9)
    assert tr.overlap_fraction() is None
    for step in range(4):
        tr.step(batch_fn(step))
    share = tr.overlap_fraction()
    assert 0.0 <= share <= 1.0
    assert (share * 4) % 1 == 0              # four steps, four samples


@pytest.mark.slow
def test_crashtest_elastic_sigkill_parity_same_mesh(tmp_path):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "crashtest.py"),
         "--elastic", "--steps", "12", "--ckpt-every", "3",
         "--kill-at", "8", "--dir", str(tmp_path)],
        capture_output=True, text=True, timeout=570,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "elastic parity OK" in proc.stdout


@pytest.mark.slow
def test_crashtest_elastic_sigkill_parity_smaller_mesh(tmp_path):
    """The full ISSUE-12 acceptance: real SIGKILL mid-epoch, restart onto
    HALF the dp mesh, params + optimizer-state shards bit-exact."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "crashtest.py"),
         "--elastic", "--steps", "12", "--ckpt-every", "3",
         "--kill-at", "8", "--resume-dp", "4", "--dir", str(tmp_path)],
        capture_output=True, text=True, timeout=570,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "elastic parity OK" in proc.stdout
    assert "dp 8 -> 4" in proc.stdout
