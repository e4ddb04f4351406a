"""bench.py resilience (VERDICT-r4 Weak #1): partial results flush per phase,
failed phases are recorded and skipped, a resumed worker re-runs only what's
missing, assemble() yields a valid JSON dict from ANY subset of raw metrics —
and without a TPU the orchestrator says so and exits non-zero instead of
measuring the CPU."""
import json
import os
import subprocess
import sys

import bench


def test_assemble_empty_is_valid_line():
    out = bench.assemble({})
    assert out["metric"] == "resnet50_train_images_per_sec_bs32"
    assert out["value"] == 0.0
    assert out["unit"] == "images/sec"
    assert out["vs_baseline"] == 0.0


def test_assemble_partial_derives_only_available():
    out = bench.assemble({"train_bs32_images_per_sec": 2600.0},
                         peak_flops=197e12)
    assert out["value"] == 2600.0
    assert out["vs_baseline"] > 8.0
    assert abs(out["mfu_bs32"]
               - 2600.0 * bench.FLOPS_TRAIN_PER_IMG / 197e12) < 1e-3
    assert "mfu_vs_attainable_bs32" not in out  # no calibration ran
    # a device the peaks table does not know: MFU left out, not guessed
    assert "mfu_bs32" not in bench.assemble(
        {"train_bs32_images_per_sec": 2600.0})
    out2 = bench.assemble({"train_bs32_images_per_sec": 2600.0,
                           "calib_attainable_bf16_tflops": 176.5})
    assert abs(out2["mfu_vs_attainable_bs32"]
               - 2600.0 * bench.FLOPS_TRAIN_PER_IMG / 1e12 / 176.5) < 1e-3


def test_worker_records_failures_and_resumes(tmp_path, capsys, monkeypatch):
    calls = []

    def ok_a():
        calls.append("a")
        return {"metric_a": 1}

    def boom():
        calls.append("b")
        raise RuntimeError("backend fell over")

    def ok_c():
        calls.append("c")
        return {"metric_c": 3}

    path = str(tmp_path / "partial.json")
    monkeypatch.setattr(bench, "PHASES",
                        [("a", ok_a), ("b", boom), ("c", ok_c)])
    assert bench.run_worker(path) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["metric_a"] == 1 and line["metric_c"] == 3
    assert "backend fell over" in line["phase_errors"]["b"]
    saved = json.load(open(path))
    assert sorted(saved["_phases_done"]) == ["a", "c"]

    # resume: a and c are cached; only b re-runs (and now succeeds)
    calls.clear()
    monkeypatch.setattr(
        bench, "PHASES",
        [("a", ok_a), ("b", lambda: {"metric_b": 2}), ("c", ok_c)])
    assert bench.run_worker(path) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert calls == []  # lambda isn't in calls; a/c never re-ran
    assert line["metric_a"] == 1 and line["metric_b"] == 2
    assert line["metric_c"] == 3


def test_orchestrator_emits_diagnostic_json_when_backend_dead(monkeypatch,
                                                              capsys,
                                                              tmp_path):
    """No TPU, no measurement: a dead backend AND a live backend without
    a TPU both print the one diagnostic line and exit non-zero, and no
    phase runs on the CPU in the chip's place. Only --quick (the runner's
    CI smoke) runs on a CPU, stamped as such."""
    ran = []

    def no_phases(**kwargs):
        ran.append(kwargs)
        return {}, {}

    monkeypatch.setattr(bench, "run_phases_isolated", no_phases)
    monkeypatch.setattr(
        bench, "probe_backend",
        lambda: (False, {"probe_failure": {"rc": -9, "elapsed_s": 150.0,
                                           "tail": "killed"}}))
    assert bench.main() != 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["value"] == 0.0 and line["backend_ok"] is False
    assert "unavailable" in line["error"]
    assert line["probe_failure"]["rc"] == -9
    assert "cpu_smoke" not in line and not ran

    cpu = {"platform": "cpu", "device_kind": "cpu", "n_devices": 1}
    monkeypatch.setattr(bench, "probe_backend", lambda: (True, dict(cpu)))
    assert bench.main() != 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["backend_ok"] is False and line["platform"] == "cpu"
    assert "no TPU visible" in line["error"] and not ran

    assert bench.main(quick=True) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["quick"] is True and line["platform"] == "cpu"
    assert line["warning"].startswith("no accelerator")
    assert len(ran) == 1 and "mfu_bs32" not in line


def test_importing_the_package_initialises_no_jax_backend():
    """bench.py's orchestrator imports the package (`_phase_child_env`)
    and must stay off jax: a chip belongs to one process, and a parent
    that holds it starves the phase children that need it."""
    code = ("import incubator_mxnet_tpu\n"
            "from incubator_mxnet_tpu import deploy, serve, telemetry\n"
            "from incubator_mxnet_tpu.tune.space import scrubbed_env\n"
            "scrubbed_env()\n"
            "from jax._src import xla_bridge\n"
            "print(sorted(xla_bridge._backends))\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       cwd=os.path.dirname(os.path.abspath(bench.__file__)))
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip().splitlines()[-1] == "[]"


def test_isolated_runner_resumes_from_partial(tmp_path):
    """run_phases_isolated skips phases already recorded in the partial
    file (an orchestrator death loses at most the in-flight phase) and
    reports unknown phase names as errors instead of dying."""
    path = str(tmp_path / "partial.json")
    with open(path, "w") as f:
        json.dump({"_phases_done": [n for n, _ in bench.PHASES],
                   "metric_a": 1}, f)
    partial, errors = bench.run_phases_isolated(
        names=["dispatch", "bogus"], partial_path=path)
    assert partial["metric_a"] == 1          # cached, no subprocess spawned
    assert "unknown phase" in errors["bogus"]
    assert "dispatch" not in errors


def test_phase_list_ordering_is_loadbearing():
    # eager before the big fused programs, calibration last (device-session
    # residue slows subsequent eager-class programs; bisected in r3)
    names = [n for n, _ in bench.PHASES]
    assert names.index("eager") < names.index("train32")
    assert names.index("calib") > names.index("infer")
