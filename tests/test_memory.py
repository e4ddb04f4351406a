"""mx.inspect.memory — device-memory observability (ISSUE 15).

Covers: memory plans on every live compiled surface (FusedTrainStep,
FusedInferStep, ExportedModel, ContinuousEngine prefill+decode, elastic
bucketed collectives) + the PR-7-style degradation contract; the
donation proof flipping on a donate=off A/B; the attributed live-buffer
census (tag/register, weakref lifecycle, census_diff) and leakcheck
(planted per-round leak caught, real train loop clean); the
StepTimeline peak_hbm_bytes lane; the MemoryMonitor host_rss fallback
(satellite 1); the device_memory_info typed sentinel (satellite 2); the
kvpool.slab_bytes gauge vs census parity (satellite 3); OOM forensics
(on_oom dump contents, enable/disable knob, crashtest --oom
SIGKILL-parity-pattern slow run); the memscope CLI.

Metric-literal census (mxlint telemetry-metric-untested): `mem.plans`,
`mem.census_runs`, `mem.tagged_bytes`, `mem.untagged_bytes`,
`mem.peak_hbm_bytes`, `mem.oom_dumps`, `kvpool.slab_bytes` are asserted
by name below.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, telemetry
from incubator_mxnet_tpu import inspect as mxinspect
from incubator_mxnet_tpu import optimizer as opt_mod
from incubator_mxnet_tpu.base import MXNetError
from incubator_mxnet_tpu.gluon.contrib import FusedInferStep, FusedTrainStep
from incubator_mxnet_tpu.inspect import memory as mem

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny_train_step(bs=4, donate=True):
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu", in_units=8),
            gluon.nn.Dense(4, in_units=16))
    net.initialize()
    net.hybridize()
    x = mx.np.array(np.random.RandomState(0).randn(bs, 8).astype(np.float32))
    y = mx.np.array(np.random.RandomState(1).randn(bs, 4).astype(np.float32))
    loss = gluon.loss.L2Loss()
    opt = opt_mod.create("sgd", learning_rate=0.1, momentum=0.9)
    step = FusedTrainStep(net, lambda n, a, b: loss(n(a), b).mean(), opt,
                          donate=donate)
    donated = sum(p.data()._arr.nbytes
                  for p in net.collect_params().values()
                  if p.grad_req != "null")
    return step, x, y, donated


# ---------------------------------------------------------------------------
# memory plans: surfaces + degradation + donation proof
# ---------------------------------------------------------------------------
def test_memory_plan_fused_train_step_and_metric():
    before = telemetry.REGISTRY.snapshot().get("mem.plans", 0)
    step, x, y, donated = _tiny_train_step()
    plan = mxinspect.memory_plan(step, x, y, name="tiny_train")
    assert plan["name"] == "tiny_train"
    assert plan["source"] == "memory_analysis" and plan["complete"]
    for key in ("argument_size", "output_size", "temp_size",
                "alias_size", "generated_code_size", "peak_bytes"):
        assert isinstance(plan[key], int) and plan[key] >= 0
    # donated weight+state buffers must be covered by aliasing
    assert plan["alias_size"] >= donated
    assert plan["peak_bytes"] == (plan["argument_size"]
                                  + plan["output_size"]
                                  + plan["temp_size"]
                                  - plan["alias_size"])
    assert telemetry.REGISTRY.snapshot()["mem.plans"] == before + 1
    # the plan landed in the active-plans table the OOM dump reports
    assert "tiny_train" in mxinspect.active_plans()
    # json-safe (no CompiledMemoryStats / proto blobs leak through)
    json.dumps(plan)


def test_assert_donation_flips_on_donate_off_ab():
    step, x, y, donated = _tiny_train_step(donate=True)
    plan = mxinspect.memory_plan(step, x, y)
    assert mxinspect.assert_donation(plan, donated) >= donated
    step2, x2, y2, donated2 = _tiny_train_step(donate=False)
    plan2 = mxinspect.memory_plan(step2, x2, y2)
    with pytest.raises(MXNetError, match="donation"):
        mxinspect.assert_donation(plan2, donated2)


def test_memory_plan_fused_infer_step():
    net = gluon.nn.Dense(4, in_units=4)
    net.initialize()
    net.hybridize()
    step = FusedInferStep(net)
    plan = mxinspect.memory_plan(step, mx.np.ones((2, 4)))
    assert plan["source"] == "memory_analysis"
    assert plan["argument_size"] > 0 and plan["peak_bytes"] > 0


def test_memory_plan_exported_model(tmp_path):
    from incubator_mxnet_tpu import deploy

    net = gluon.nn.Dense(3, in_units=6)
    net.initialize()
    net.hybridize()
    x = mx.np.zeros((2, 6), dtype="float32")
    net(x)
    prefix = str(tmp_path / "net")
    net.export(prefix, example_inputs=x)
    model = deploy.ExportedModel(f"{prefix}-0000")
    plan = mxinspect.memory_plan(model)
    assert plan["source"] == "memory_analysis"
    # the bucket program's arguments include the weight buffers
    pbytes = sum(b.nbytes for b in model._pbufs)
    assert plan["argument_size"] >= pbytes
    # planning pre-populated the jit cache; run still works
    out = model.run(np.ones((2, 6), np.float32))
    assert np.asarray(out).shape == (2, 3)


def test_memory_plan_continuous_engine_and_zero_retrace():
    from incubator_mxnet_tpu import serve

    cfg = serve.DecoderConfig(vocab=32, embed=16, layers=2, heads=2,
                              head_dim=8, max_len=32)
    model = serve.CachedDecoder(cfg)
    with serve.ContinuousEngine(model, max_slots=4, decode_steps=2,
                                prefill_window=16) as eng:
        eng.generate([1, 2, 3], max_new_tokens=4)
        plans = eng.memory_plans()
        for name in ("prefill", "decode"):
            assert plans[name]["source"] == "memory_analysis"
            # the KV slab pair dominates the arguments of both programs
            assert plans[name]["argument_size"] >= eng.pool.nbytes()
        # both programs donate the slab: aliasing covers k+v
        assert plans["decode"]["alias_size"] >= eng.pool.nbytes()
        # lowering at the warmup avals must not have retraced anything
        eng.assert_no_retraces()
        eng.generate([4, 5], max_new_tokens=3)
        eng.assert_no_retraces()


def test_memory_plan_elastic_collectives():
    import jax.numpy as jnp
    from incubator_mxnet_tpu.fault import elastic

    def loss_fn(p, batch):
        return jnp.mean(batch["c"] @ p["w"])

    params = {"w": np.arange(24, dtype=np.float32)}
    tr = elastic.ElasticTrainer(loss_fn, params, optimizer="sgd", dp=4,
                                learning_rate=0.1)
    tr.step({"c": np.random.rand(8, 24).astype(np.float32)})
    plans = tr.memory_plans()
    kinds = {p["name"].split(".")[1].split("[")[0]
             for p in plans.values() if p["source"] != "unavailable"}
    # both halves of the ZeRO data path are planned
    assert {"reduce_scatter", "allgather"} <= kinds
    for p in plans.values():
        assert p["source"] == "memory_analysis", p


def test_memory_plan_degradation_contract():
    # memory_analysis missing -> HLO-shape lower bound
    class _NoStats:
        def as_text(self):
            return (
                "HloModule m\n\n"
                "ENTRY %main (p0: f32[8,8]) -> f32[8,8] {\n"
                "  %p0 = f32[8,8]{1,0} parameter(0)\n"
                "  ROOT %r = f32[8,8]{1,0} add(f32[8,8]{1,0} %p0, "
                "f32[8,8]{1,0} %p0)\n"
                "}\n")

        def cost_analysis(self):
            raise RuntimeError("no cost analysis either")

    plan = mxinspect.plan_from_compiled(_NoStats(), name="shapes")
    assert plan["source"] == "hlo_shapes" and plan["complete"] is False
    assert plan["argument_size"] == 8 * 8 * 4
    assert plan["output_size"] == 8 * 8 * 4
    assert plan["temp_size"] == 0
    assert plan["peak_bytes"] == 2 * 8 * 8 * 4
    # donation cannot be PROVEN from a shape lower bound: typed refusal
    with pytest.raises(MXNetError, match="cannot prove donation"):
        mxinspect.assert_donation(plan, 1)

    # unparseable text too -> all-zero plan, flagged, never a crash
    class _Garbage:
        def as_text(self):
            raise RuntimeError("text unavailable")

    plan2 = mxinspect.plan_from_compiled(_Garbage(), name="nothing")
    assert plan2["source"] == "unavailable" and plan2["peak_bytes"] == 0


def test_roofline_report_embeds_memory_plan():
    import jax.numpy as jnp
    rep = mxinspect.inspect_step(lambda x: (x @ x).sum(),
                                 jnp.ones((32, 32), jnp.float32))
    assert rep["memory"]["source"] == "memory_analysis"
    assert rep["memory"]["argument_size"] >= 32 * 32 * 4


# ---------------------------------------------------------------------------
# census + leakcheck
# ---------------------------------------------------------------------------
def test_register_tag_and_census_attribution():
    import jax.numpy as jnp
    a = jnp.zeros((128, 64))
    b = jnp.ones((32, 32))
    mxinspect.register(a, owner="test_owner_a")
    with mxinspect.tag("test_owner_b"):
        assert mxinspect.current_tag() == "test_owner_b"
        mxinspect.register({"nested": [b]})
    assert mxinspect.current_tag() is None
    before = telemetry.REGISTRY.snapshot().get("mem.census_runs", 0)
    c = mxinspect.census()
    assert c["owners"]["test_owner_a"]["bytes"] == a.nbytes
    assert c["owners"]["test_owner_b"]["bytes"] == b.nbytes
    assert c["total_bytes"] >= c["tagged_bytes"] > 0
    assert c["untagged_bytes"] == c["total_bytes"] - c["tagged_bytes"]
    snap = telemetry.REGISTRY.snapshot()
    assert snap["mem.census_runs"] == before + 1
    assert snap["mem.tagged_bytes"] == c["tagged_bytes"]
    assert snap["mem.untagged_bytes"] == c["untagged_bytes"]
    json.dumps(c)


def test_register_owner_validation_and_weakref_lifecycle():
    import jax.numpy as jnp
    with pytest.raises(MXNetError, match="owner"):
        mxinspect.register(jnp.zeros((2,)), owner="Bad.Owner")
    with pytest.raises(MXNetError, match="owner"):
        mxinspect.register(jnp.zeros((2,)))     # no ambient tag either
    x = jnp.zeros((64, 64))
    mxinspect.register(x, owner="shortlived")
    assert mxinspect.census()["owners"]["shortlived"]["bytes"] == x.nbytes
    del x
    # the weakref entry died with the array: the owner vanishes
    assert "shortlived" not in mxinspect.census()["owners"]


def test_census_diff():
    import jax.numpy as jnp
    before = mxinspect.census()
    grown = jnp.zeros((256, 256))
    mxinspect.register(grown, owner="diff_owner")
    after = mxinspect.census()
    d = mxinspect.census_diff(before, after)
    assert d["owners"]["diff_owner"]["bytes"] == grown.nbytes
    assert d["total_bytes"] >= grown.nbytes


def test_leakcheck_catches_planted_leak_and_passes_clean_loop():
    import jax.numpy as jnp
    leaked = []

    def leaky():
        leaked.append(jnp.zeros((128, 128)))

    with pytest.raises(mxinspect.MemoryLeakError) as ei:
        mxinspect.leakcheck(leaky, rounds=3)
    assert ei.value.report["leak"] and ei.value.report["growth_bytes"] > 0

    # the REAL train loop: donated buffers swap, nothing accumulates
    step, x, y, _ = _tiny_train_step()
    rep = mxinspect.leakcheck(lambda: step(x, y), rounds=3)
    assert rep["leak"] is False
    assert rep["growth_mb"] < 1.0


# ---------------------------------------------------------------------------
# timeline lane + monitor + device info (satellites 1 + 2)
# ---------------------------------------------------------------------------
def test_steptimeline_peak_hbm_lane():
    step, x, y, _ = _tiny_train_step()
    tl = telemetry.StepTimeline(name="memtest.step")
    for _ in range(3):
        with tl.step():
            step(x, y)
    rep = tl.report()
    # CPU backend: memory_stats is None, so the honest source is host RSS
    assert rep["peak_hbm_bytes"] > 0
    assert rep["mem_source"] in ("device", "host_rss")
    assert telemetry.REGISTRY.snapshot()["mem.peak_hbm_bytes"] >= \
        rep["peak_hbm_bytes"] > 0


def test_memory_monitor_host_rss_fallback_and_counter_source():
    import time
    from incubator_mxnet_tpu import profiler

    b, source = profiler.read_memory_sample()
    assert source in ("device", "host_rss") and b > 0
    with profiler.MemoryMonitor(interval=0.005) as mon:
        time.sleep(0.03)
    assert len(mon.samples) >= 1
    # on the CPU test backend the pre-fix reading was a flat 0; now the
    # samples are process RSS with an honest provenance stamp
    for ts, nbytes, src in mon.samples:
        assert nbytes > 0 and src in ("device", "host_rss")
    assert mon.peak_bytes > 0
    assert mon.source in ("device", "host_rss")
    # a monitor-only loop (no StepTimeline) moves the cataloged gauge too
    assert telemetry.REGISTRY.snapshot()["mem.peak_hbm_bytes"] >= \
        mon.peak_bytes
    # the Chrome counter events carry the stamp too
    from incubator_mxnet_tpu.profiler import _events, _lock
    with _lock:
        lanes = [e for e in _events if e["name"] == "device_memory"]
    assert lanes and all("source" in e["args"] for e in lanes)


def test_device_memory_info_typed_sentinel(monkeypatch):
    from incubator_mxnet_tpu import device as dev_mod

    info = dev_mod.device_memory_info()
    # CPU backend: memory_stats() is None -> an explicit don't-know,
    # not fake (0, 0) headroom
    assert info.known is False and info.free == 0 and info.total == 0
    assert tuple(info) == (0, 0, False)     # tuple-compatible

    class _FakeDev:
        def memory_stats(self):
            return {"bytes_limit": 1000, "bytes_in_use": 250}

    class _FakeDevNone:
        def memory_stats(self):
            return None

    monkeypatch.setattr(dev_mod.Device, "jax_device",
                        property(lambda self: _FakeDev()))
    info = dev_mod.device_memory_info()
    assert info == dev_mod.MemoryInfo(750, 1000, True)
    monkeypatch.setattr(dev_mod.Device, "jax_device",
                        property(lambda self: _FakeDevNone()))
    assert dev_mod.device_memory_info().known is False

    # the capi shim (deploy.py) reports (used, limit) and no longer
    # treats the tuple as a dict (the satellite's latent AttributeError)
    from incubator_mxnet_tpu.deploy import _capi_memory_info
    monkeypatch.setattr(dev_mod.Device, "jax_device",
                        property(lambda self: _FakeDev()))
    assert _capi_memory_info(0) == (250, 1000)
    monkeypatch.setattr(dev_mod.Device, "jax_device",
                        property(lambda self: _FakeDevNone()))
    assert _capi_memory_info(0) == (0, 0)


# ---------------------------------------------------------------------------
# kvpool slab gauge (satellite 3)
# ---------------------------------------------------------------------------
def test_kvpool_slab_gauge_matches_census_owner_bytes():
    import gc
    from incubator_mxnet_tpu.serve.kv_pool import KVCachePool

    gc.collect()    # pools of engines that earlier tests of this worker left
    pool = KVCachePool(max_slots=4, layers=2, max_len=16, heads=2,
                       head_dim=8)
    gauge = telemetry.REGISTRY.snapshot()["kvpool.slab_bytes"]
    assert gauge == pool.nbytes() == pool.stats()["slab_bytes"]
    c = mxinspect.census()
    assert c["owners"]["kv_pool"]["bytes"] == pool.nbytes()
    assert c["owners"]["kv_pool"]["count"] == 2          # k + v
    # reallocate (the engine's post-donation-failure path) re-registers
    pool.reallocate()
    c = mxinspect.census()
    assert c["owners"]["kv_pool"]["bytes"] == pool.nbytes()


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------
_OOM = RuntimeError("RESOURCE_EXHAUSTED: Out of memory while trying to "
                    "allocate 34359738368 bytes")


def test_is_oom_error_shapes():
    assert mxinspect.is_oom_error(_OOM)
    assert mxinspect.is_oom_error(MemoryError())
    assert mxinspect.is_oom_error(RuntimeError("xla: Resource exhausted"))
    assert not mxinspect.is_oom_error(ValueError("shape mismatch"))
    assert not mxinspect.is_oom_error(None)


def test_on_oom_dump_names_top_owner(tmp_path, monkeypatch):
    import jax.numpy as jnp
    monkeypatch.setenv("MXNET_MEM_OOM_DUMP", str(tmp_path))
    bomb = jnp.zeros((512, 512, 4))
    mxinspect.register(bomb, owner="planted")
    before = telemetry.REGISTRY.snapshot().get("mem.oom_dumps", 0)
    step, x, y, _ = _tiny_train_step()
    mxinspect.memory_plan(step, x, y, name="planted_plan")
    path = mxinspect.on_oom(_OOM, where="test.step")
    assert path and os.path.exists(path)
    dump = json.load(open(path))
    assert dump["reason"] == "test.step"
    assert "RESOURCE_EXHAUSTED" in dump["error"]
    owners = dump["census"]["owners"]
    assert owners["planted"]["bytes"] == bomb.nbytes
    # the planted slab tops every NAMED owner (the whole-suite process
    # may carry arbitrary untagged leftovers; the strict top-entry
    # assertion runs in the clean-process crashtest --oom harness)
    named = {k: v["bytes"] for k, v in owners.items() if k != "untagged"}
    assert max(named, key=named.get) == "planted"
    assert "planted_plan" in dump["plans"]
    assert isinstance(dump["flightrec"], list)
    assert dump["device_memory"]["known"] in (True, False)
    assert telemetry.REGISTRY.snapshot()["mem.oom_dumps"] == before + 1
    # non-OOM errors never dump; the knob disables entirely
    assert mxinspect.on_oom(ValueError("not oom")) is None
    monkeypatch.setenv("MXNET_MEM_OOM_DUMP", "0")
    assert mxinspect.on_oom(_OOM) is None


def test_serve_engine_survives_oom_and_dumps(tmp_path, monkeypatch):
    """A RESOURCE_EXHAUSTED step inside the continuous engine leaves the
    black box AND the engine keeps serving (slab reallocation path)."""
    from incubator_mxnet_tpu import serve

    monkeypatch.setenv("MXNET_MEM_OOM_DUMP", str(tmp_path))
    cfg = serve.DecoderConfig(vocab=32, embed=16, layers=1, heads=2,
                              head_dim=8, max_len=16)
    model = serve.CachedDecoder(cfg)
    with serve.ContinuousEngine(model, max_slots=2, decode_steps=1,
                                prefill_window=8) as eng:
        eng.generate([1, 2], max_new_tokens=2)    # healthy first
        orig = eng._prefill_prog

        def _boom(*a, **k):
            eng._prefill_prog = orig              # heal for the retry
            raise RuntimeError("RESOURCE_EXHAUSTED: Out of memory "
                               "(injected)")

        eng._prefill_prog = _boom
        with pytest.raises(Exception):
            eng.generate([3, 4], max_new_tokens=2)
        out = eng.generate([5, 6], max_new_tokens=2)   # keeps serving
        assert out.dtype == np.int32
    dumps = [f for f in os.listdir(tmp_path) if f.startswith("oomdump-")]
    assert dumps, "engine OOM left no black box"


# ---------------------------------------------------------------------------
# subprocess acceptance: clean-process census fractions, CLI
# ---------------------------------------------------------------------------
def _run(args, timeout=600, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if env_extra:
        env.update(env_extra)
    return subprocess.run([sys.executable] + args, capture_output=True,
                          text=True, timeout=timeout, cwd=REPO, env=env)


def test_memscope_cli_model_json(tmp_path):
    out = tmp_path / "scope.json"
    r = _run([os.path.join(REPO, "tools", "memscope.py"), "--model",
              "tiny", "--json", str(out)])
    assert r.returncode == 0, r.stdout + r.stderr
    rep = json.loads(out.read_text())
    assert rep["mode"] == "model" and rep["donation_ok"]
    assert rep["plans"][0]["source"] == "memory_analysis"
    assert rep["device_memory"]["known"] is False      # CPU honesty
    assert "census" in rep


def test_memscope_cli_serve_census_attribution(tmp_path):
    """Acceptance: in a clean process the serve-continuous resident set
    is >= 80% attributed to named owners (kv_pool + decoder_params)."""
    out = tmp_path / "serve.json"
    r = _run([os.path.join(REPO, "tools", "memscope.py"), "--serve",
              "--json", str(out)], timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    rep = json.loads(out.read_text())
    c = rep["census"]
    assert c["tagged_fraction"] >= 0.8, c
    assert "kv_pool" in c["owners"] and "decoder_params" in c["owners"]
    assert rep["kv_slab_mb"] > 0


def test_elastic_census_attribution_subprocess():
    """Acceptance: an elastic trainer's resident set is >= 80%
    attributed (optimizer_shards + elastic_params) in a clean process."""
    code = (
        "import os\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "import numpy as np, jax, jax.numpy as jnp\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import incubator_mxnet_tpu as mx\n"
        "from incubator_mxnet_tpu.fault import elastic\n"
        "from incubator_mxnet_tpu import inspect as mxi\n"
        "def loss_fn(p, b):\n"
        "    return jnp.mean(b['c'] @ p['w']) + jnp.mean(p['v'] ** 2)\n"
        "params = {'w': np.random.rand(512, 8).astype(np.float32),\n"
        "          'v': np.random.rand(256).astype(np.float32)}\n"
        "tr = elastic.ElasticTrainer(loss_fn, params, optimizer='adam',\n"
        "                            dp=4, learning_rate=0.01)\n"
        "tr.step({'c': np.random.rand(8, 512).astype(np.float32)})\n"
        "c = mxi.census()\n"
        "print('FRACTION', c['tagged_fraction'])\n"
        "assert c['tagged_fraction'] >= 0.8, c\n"
        "assert 'optimizer_shards' in c['owners']\n"
        "assert 'elastic_params' in c['owners']\n"
        "print('OK')\n")
    r = _run(["-c", code], env_extra={
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
    assert r.returncode == 0 and "OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_crashtest_oom_forensics():
    """The planted allocation bomb under run_elastic leaves an OOM dump
    naming the planted owner as the top census entry (the
    SIGKILL-parity-pattern harness; see tools/crashtest.py --oom)."""
    r = _run([os.path.join(REPO, "tools", "crashtest.py"), "--oom",
              "--steps", "8", "--ckpt-every", "3", "--kill-at", "4"],
             timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OOM forensics OK" in r.stdout
