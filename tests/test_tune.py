"""mx.tune (ISSUE 18): the deployment-profile autotuner.

Contracts under test:
  * the knob catalog is typed and closed — every default is a declared
    choice, pow2 ladders are real powers of two, unknown knobs and
    out-of-space values are typed errors (a hand-edited profile must
    fail loudly, never half-apply)
  * `scrubbed_env` (the tune trial runner's) removes exactly the tunable env surface: knob vars go,
    infra vars (JAX_PLATFORMS, MXNET_FAULT_SPEC, the compile cache)
    stay — the trial-contamination regression
  * profiles round-trip through JSON (same hash, same knobs), activate
    only when BOTH fingerprints match, and fall back loudly (counter +
    event, nothing applied) on mismatch or MXNET_TUNE_DISABLE
  * the precedence chain on a real wired constructor:
    explicit arg > active profile > MXNET_* env > built-in default
  * sweeps are deterministic (same space, same order, same result),
    structurally >= hand-tuned (trial 0 measures the hand-tuned
    baseline), and CRASH-CONTAINED: a `tune.trial` fault becomes a
    recorded failed trial while the sweep completes
  * a cold replica that finds a profile boots with exactly the tuned
    engine configuration (warm-and-tuned parity), reports the profile
    hash, and a Fleet flags divergent hashes across serving replicas
  * EDF dispatch tie-break: among equally-loaded replicas the gate
    grants the tightest deadline first, beating FIFO arrival order

Counter surface exercised here (mxlint stats-key-untested): tune.trials
("trials"), tune.trials_failed ("trials_failed"), tune.trial_ms
("trial_ms"), tune.profile_applied ("profile_applied"),
tune.profile_mismatch ("profile_mismatch"),
fleet.profile_divergence ("profile_divergence").
"""
import json
import os
import subprocess
import sys
import threading

import pytest

from incubator_mxnet_tpu import fault, tune
from incubator_mxnet_tpu.base import MXNetError
from incubator_mxnet_tpu.serve import fleet as fleet_mod
from incubator_mxnet_tpu.serve import replica as replica_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_profile_state():
    """Profile activation is process-global: never leak one into other
    tests (or from them)."""
    tune.deactivate()
    yield
    tune.deactivate()


def _tiny_profile(knobs, model_fp="m" * 12, hw_fp=None):
    return tune.DeploymentProfile(
        knobs, model_fp,
        hw_fp if hw_fp is not None else tune.hardware_fingerprint()["fp"])


# ---------------------------------------------------------------------------
# knob catalog
# ---------------------------------------------------------------------------
def test_catalog_is_typed_and_closed():
    cat = tune.catalog()
    assert len(cat) >= 10
    for name, k in cat.items():
        assert k.kind in ("categorical", "int", "pow2", "bool")
        assert any(k.default == c for c in k.choices)
        if k.kind == "pow2":
            for c in k.choices:
                if c is not None:
                    assert c > 0 and (c & (c - 1)) == 0
    # every swept phase has a hand-tuned seed assignment
    assert set(tune.HAND_TUNED) <= set(tune.phases())
    # typed errors, not KeyErrors / silent passes
    with pytest.raises(MXNetError):
        tune.knob("serve.nope")
    with pytest.raises(MXNetError):
        tune.validate_assignment({"serve.decode_steps": 3})   # not a choice
    with pytest.raises(MXNetError):
        tune.validate_assignment({"made.up": 1})
    norm = tune.validate_assignment({"serve.decode_steps": 8})
    assert norm == {"serve.decode_steps": 8}


def test_tune_trial_is_a_registered_fault_point():
    assert "tune.trial" in fault.POINTS


# ---------------------------------------------------------------------------
# scrubbed_env — the trial isolation helper
# ---------------------------------------------------------------------------
def test_scrubbed_env_removes_knob_surface_only():
    base = {"MXNET_SERVE_DECODE_STEPS": "8", "MXNET_IO_WORKERS": "4",
            "MXNET_ENGINE_BULK_SIZE": "512", "MXNET_TUNE_PROFILE": "/p",
            "JAX_PLATFORMS": "cpu", "MXNET_FAULT_SPEC": "p:1:error",
            "MXNET_COMPILE_CACHE_DIR": "/cc", "PATH": "/bin"}
    env = tune.scrubbed_env(base=base)
    for var in tune.knob_env_vars():
        assert var not in env
    assert "MXNET_TUNE_PROFILE" not in env      # parent profile never leaks
    # infra surface passes through untouched
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["MXNET_FAULT_SPEC"] == "p:1:error"
    assert env["MXNET_COMPILE_CACHE_DIR"] == "/cc"
    assert env["PATH"] == "/bin"
    # overrides apply on top; None deletes
    env2 = tune.scrubbed_env(
        overrides={"MXNET_IO_WORKERS": 2, "PATH": None}, base=base)
    assert env2["MXNET_IO_WORKERS"] == "2"
    assert "PATH" not in env2


def test_trial_children_get_scrubbed_env(monkeypatch):
    """An operator's ambient knob export must not reach a trial's child:
    the assignment under test arrives through argv alone."""
    from incubator_mxnet_tpu.tune import search
    seen = {}

    class _Child:
        pid, returncode = 0, 0

        def __init__(self, argv, env=None, **kw):
            seen.update(argv=argv, env=env)

        def communicate(self, timeout=None):
            return '{"ok": true, "score": 1.0}\n', ""

    monkeypatch.setenv("MXNET_SERVE_MAX_SLOTS", "32")
    monkeypatch.setenv("MXNET_TUNE_PROFILE", "/p")
    monkeypatch.setenv("MXNET_FAULT_SPEC", "p:1:error")
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(search.subprocess, "Popen", _Child)
    got = search._spawn_trial("serve_decode", {"serve.max_slots": 4},
                              "quick", 5.0)
    assert got == {"ok": True, "score": 1.0}
    assert "MXNET_SERVE_MAX_SLOTS" not in seen["env"]
    assert "MXNET_TUNE_PROFILE" not in seen["env"]
    assert seen["env"]["MXNET_FAULT_SPEC"] == "p:1:error"
    assert seen["env"]["JAX_PLATFORMS"] == "cpu"     # the runner's default
    assert json.loads(seen["argv"][seen["argv"].index("--knobs") + 1]) \
        == {"serve.max_slots": 4}


# ---------------------------------------------------------------------------
# profiles: round-trip, fingerprints, loud fallback
# ---------------------------------------------------------------------------
def test_profile_roundtrip_and_hash(tmp_path):
    prof = _tiny_profile({"serve.decode_steps": 8, "io.workers": 2})
    path = prof.save(directory=str(tmp_path))
    assert os.path.basename(path) == \
        f"profile-{prof.model_fp}-{prof.hw_fp}.json"
    back = tune.DeploymentProfile.load(path)
    assert back.knobs == prof.knobs
    assert back.profile_hash == prof.profile_hash
    # schema drift is a typed refusal, not a guess
    blob = json.loads(open(path).read())
    blob["schema"] = 99
    with pytest.raises(MXNetError):
        tune.DeploymentProfile.from_dict(blob)


def test_profile_fingerprint_mismatch_falls_back_loudly():
    prof = _tiny_profile({"serve.decode_steps": 8})
    before = tune.tune_stats()
    # model axis
    assert prof.apply(model_fp="x" * 12) is False
    # hardware axis
    bad_hw = _tiny_profile({"serve.decode_steps": 8}, hw_fp="h" * 12)
    assert bad_hw.apply() is False
    after = tune.tune_stats()
    assert after["profile_mismatch"] == before["profile_mismatch"] + 2
    assert tune.active() is None
    assert tune.resolve("serve.decode_steps", 4) == 4


def test_profile_disable_kills_the_tier(monkeypatch):
    prof = _tiny_profile({"serve.decode_steps": 8})
    assert prof.apply() is True
    assert tune.resolve("serve.decode_steps") == 8
    monkeypatch.setenv("MXNET_TUNE_DISABLE", "1")
    assert tune.resolve("serve.decode_steps", 4) == 4
    assert tune.active() is None
    # and activation itself is refused while disabled
    assert prof.apply() is False


def test_profile_stale_knob_resolves_to_default():
    """Catalog drift: a profile value outside today's choice set is
    skipped with a structured log — old profiles stay loadable."""
    prof = _tiny_profile({"serve.decode_steps": 8})
    prof.knobs["serve.decode_steps"] = 7      # post-validation corruption
    assert prof.apply() is True
    assert tune.resolve("serve.decode_steps", 4) == 4


def test_lookup_missing_and_corrupt(tmp_path):
    assert tune.lookup("m" * 12, hw_fp="h" * 12,
                       directory=str(tmp_path)) is None
    prof = _tiny_profile({"io.workers": 2})
    path = prof.save(directory=str(tmp_path))
    with open(path, "w") as f:
        f.write("{not json")
    assert tune.lookup(prof.model_fp, hw_fp=prof.hw_fp,
                       directory=str(tmp_path)) is None


def test_env_autoload_path_does_not_deadlock(tmp_path, monkeypatch):
    """Regression: the first resolve() with MXNET_TUNE_PROFILE set
    autoloads under _LOCK and then calls activate(), which takes _LOCK
    again — with a plain Lock that was a self-deadlock on the documented
    env-side activation path (replica children). Run the first resolve
    on a guarded thread so a regression fails the test instead of
    hanging the suite."""
    from incubator_mxnet_tpu.tune import profile as profile_mod
    prof = _tiny_profile({"serve.decode_steps": 8})
    path = prof.save(directory=str(tmp_path))
    monkeypatch.setenv("MXNET_TUNE_PROFILE", path)
    monkeypatch.setattr(profile_mod, "_AUTOLOADED", [False])
    got = []
    t = threading.Thread(
        target=lambda: got.append(tune.resolve("serve.decode_steps", 4)),
        daemon=True)
    t.start()
    t.join(timeout=30)
    assert not t.is_alive(), "env-autoload resolve() deadlocked"
    assert got == [8]
    assert tune.active() is not None


# ---------------------------------------------------------------------------
# precedence chain on a real wired constructor
# ---------------------------------------------------------------------------
def _tiny_engine(**kw):
    from incubator_mxnet_tpu.serve import CachedDecoder, DecoderConfig
    cfg = DecoderConfig(vocab=32, embed=16, layers=1, heads=2, head_dim=8,
                        max_len=32)
    from incubator_mxnet_tpu.serve import ContinuousEngine
    return ContinuousEngine(CachedDecoder(cfg, seed=0), **kw)


def test_precedence_explicit_over_profile_over_env(monkeypatch):
    prof = _tiny_profile({"serve.decode_steps": 8,
                          "serve.prefill_lanes": 2})
    assert prof.apply() is True
    monkeypatch.setenv("MXNET_SERVE_DECODE_STEPS", "6")
    # profile beats env
    eng = _tiny_engine()
    assert eng.decode_steps == 8
    assert eng.prefill_lanes == 2
    # explicit arg beats profile
    eng = _tiny_engine(decode_steps=2)
    assert eng.decode_steps == 2
    # drop the profile: env tier surfaces
    tune.deactivate()
    eng = _tiny_engine()
    assert eng.decode_steps == 6
    # drop the env: built-in default
    monkeypatch.delenv("MXNET_SERVE_DECODE_STEPS")
    eng = _tiny_engine()
    assert eng.decode_steps == 4


def test_cold_replica_with_profile_boots_tuned(tmp_path, monkeypatch):
    """Warm-and-tuned parity at the construction layer: an engine built
    under the replica-resolved profile equals one built with the tuned
    values passed explicitly."""
    model_meta = {"vocab": 32, "embed": 16, "layers": 1, "heads": 2,
                  "head_dim": 8, "max_len": 32}
    prof = tune.DeploymentProfile(
        {"serve.decode_steps": 8, "serve.prefill_lanes": 2},
        tune.model_fingerprint(model_meta),
        tune.hardware_fingerprint()["fp"])
    prof.save(directory=str(tmp_path))
    monkeypatch.setenv("MXNET_TUNE_PROFILE_DIR", str(tmp_path))
    # the replica-boot path: lookup by (model, hardware), activate,
    # report the hash in the hello
    h = replica_mod._resolve_profile({"config": model_meta})
    assert h == prof.profile_hash
    tuned = _tiny_engine()
    tune.deactivate()
    explicit = _tiny_engine(decode_steps=8, prefill_lanes=2)
    assert (tuned.decode_steps, tuned.prefill_lanes,
            tuned.draft_tokens, tuned.max_slots) == \
           (explicit.decode_steps, explicit.prefill_lanes,
            explicit.draft_tokens, explicit.max_slots)


def test_replica_stub_profile_hash_passthrough():
    assert replica_mod._resolve_profile(
        {"stub": True, "profile_hash": "abc123"}) == "abc123"
    assert replica_mod._resolve_profile({"stub": True}) is None


@pytest.mark.slow
def test_profile_roundtrip_cross_process(tmp_path):
    """A profile written here activates in a FRESH process via
    MXNET_TUNE_PROFILE_DIR lookup — the actual replica cold-boot path."""
    model_meta = {"vocab": 32}
    prof = tune.DeploymentProfile(
        {"serve.decode_steps": 8}, tune.model_fingerprint(model_meta),
        tune.hardware_fingerprint()["fp"])
    prof.save(directory=str(tmp_path))
    code = (
        "import json, sys\n"
        "from incubator_mxnet_tpu import tune\n"
        "from incubator_mxnet_tpu.serve import replica\n"
        "h = replica._resolve_profile({'config': {'vocab': 32}})\n"
        "print(json.dumps({'hash': h,"
        " 'steps': tune.resolve('serve.decode_steps', 4)}))\n")
    env = dict(os.environ, MXNET_TUNE_PROFILE_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=240)
    assert out.returncode == 0, out.stderr
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res == {"hash": prof.profile_hash, "steps": 8}


# ---------------------------------------------------------------------------
# sweeps: deterministic, >= hand-tuned, crash-contained
# ---------------------------------------------------------------------------
def _planted_runner(phase, assignment, scale):
    """Deterministic synthetic objective with a planted optimum at
    decode_steps=8 (hand-tuned baseline is 4)."""
    score = 100.0
    score += 10.0 * (assignment.get("serve.decode_steps") == 8)
    score -= 5.0 * (assignment.get("serve.draft_tokens") or 0)
    return {"ok": True, "score": score, "unit": "tok/s"}


def test_sweep_finds_planted_optimum_and_beats_hand():
    res = tune.sweep(phases=["serve_decode"], budget=12,
                     runner=_planted_runner)
    ph = res["phases"]["serve_decode"]
    # trial 0 IS the hand-tuned assignment
    assert ph["trials"][0]["knobs"]["serve.decode_steps"] == 4
    assert ph["best_knobs"]["serve.decode_steps"] == 8
    assert ph["speedup_vs_hand"] >= 1.0
    assert res["trials_failed"] == 0
    prof = tune.build_profile(res, model_meta={"m": 1})
    assert prof.knobs["serve.decode_steps"] == 8
    assert prof.phases["serve_decode"]["speedup_vs_hand"] >= 1.0


def test_sweep_is_deterministic():
    a = tune.sweep(phases=["serve_decode"], budget=10, seed=3,
                   runner=_planted_runner)
    b = tune.sweep(phases=["serve_decode"], budget=10, seed=3,
                   runner=_planted_runner)
    sig = lambda r: [(t["knobs"], t["score"], t["ok"])
                     for t in r["phases"]["serve_decode"]["trials"]]
    assert sig(a) == sig(b)
    assert a["knobs"] == b["knobs"]
    # and the dry-run schedule agrees with what the sweep visits first
    sched = tune.plan("serve_decode", budget=10)
    assert sched[0] == a["phases"]["serve_decode"]["trials"][0]["knobs"]


def test_sweep_contains_crashing_trial():
    """A `tune.trial` fault is a FAILED TRIAL, never a failed sweep —
    the subprocess-isolation contract, drilled without crashing
    anything real."""
    before = tune.tune_stats()
    with fault.scope("tune.trial:2:error"):
        res = tune.sweep(phases=["serve_decode"], budget=6,
                         runner=_planted_runner)
    ph = res["phases"]["serve_decode"]
    assert res["trials_failed"] == 1
    failed = [t for t in ph["trials"] if not t["ok"]]
    assert len(failed) == 1 and failed[0]["error"]
    # the sweep completed: later trials ran, a best was still chosen
    assert len(ph["trials"]) >= 3
    assert ph["best"] is not None and ph["best"]["ok"]
    after = tune.tune_stats()
    assert after["trials"] == before["trials"] + len(ph["trials"])
    assert after["trials_failed"] == before["trials_failed"] + 1
    assert after["trial_ms"] > before["trial_ms"]
    assert after["profile_applied"] == before["profile_applied"]


def test_build_profile_refuses_empty_sweep():
    res = {"phases": {}, "knobs": {}}
    with pytest.raises(MXNetError):
        tune.build_profile(res)


# ---------------------------------------------------------------------------
# fleet: divergence detection + EDF dispatch tie-break (satellites)
# ---------------------------------------------------------------------------
def _stub_fleet(tmp_path, hashes):
    fl = fleet_mod.Fleet({"stub": True}, replicas=len(hashes),
                         workdir=str(tmp_path))
    for h, ph in zip(fl._replicas, hashes):
        h.state = "serving"
        h.hello = {"profile_hash": ph} if ph else {}
    return fl


def test_fleet_profile_divergence_detection(tmp_path):
    before = fleet_mod.fleet_stats()["profile_divergence"]
    # homogeneous (including untuned Nones): no divergence
    assert _stub_fleet(tmp_path / "a",
                       ["p1", "p1", None])._check_profile_divergence() \
        is False
    # two distinct hashes among serving replicas: divergence, billed
    assert _stub_fleet(tmp_path / "b",
                       ["p1", "p2"])._check_profile_divergence() is True
    after = fleet_mod.fleet_stats()["profile_divergence"]
    assert after == before + 1


def _req(deadline_at, t_submit):
    r = fleet_mod._FleetRequest(0, [1], 1, deadline_at, None)
    r.t_submit = t_submit
    return r


def test_edf_gate_beats_fifo():
    """FIFO would grant the earlier-arrived deadline-less request; the
    gate grants the tightest deadline first."""
    gate = fleet_mod._EDFGate()
    first = _req(None, t_submit=1.0)          # arrived first, no deadline
    tight = _req(5.0, t_submit=2.0)           # arrived later, deadline
    loose = _req(9.0, t_submit=3.0)
    for r in (first, tight, loose):
        gate.enter(r)
    assert gate.wait_turn(tight, timeout=0.001) is True
    assert gate.wait_turn(first, timeout=0.001) is False
    assert gate.wait_turn(loose, timeout=0.001) is False
    gate.leave(tight)
    assert gate.wait_turn(loose, timeout=0.001) is True
    gate.leave(loose)
    assert gate.wait_turn(first, timeout=0.001) is True
    gate.leave(first)
    # empty gate admits anyone immediately
    assert gate.wait_turn(first, timeout=0.001) is True


def test_edf_gate_orders_concurrent_claims():
    """Threaded: N requests entered together are granted in deadline
    order regardless of arrival order."""
    gate = fleet_mod._EDFGate()
    reqs = [_req(float(10 - i), t_submit=float(i)) for i in range(4)]
    for r in reqs:                 # arrival order = loosest first
        gate.enter(r)
    order, lock = [], threading.Lock()

    def claim(r):
        while not gate.wait_turn(r, timeout=0.01):
            pass
        with lock:
            order.append(r.deadline_at)
        gate.leave(r)

    threads = [threading.Thread(target=claim, args=(r,)) for r in reqs]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    assert order == sorted(order)  # tightest deadline served first
