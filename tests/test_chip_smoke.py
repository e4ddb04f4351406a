"""chip_smoke.py under the tests' CPU mode.

`--tiny` shrinks every size and, on a CPU, runs the Pallas kernels in
interpret mode: the same phases, entry points and checks as the chip run,
reported under the platform they really ran on. Without `--tiny` the script
needs a TPU and must say so without printing a result.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _smoke(args, tmp_path, timeout=600):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    r = subprocess.run([sys.executable, SCRIPT] + args, env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=timeout)
    lines = [json.loads(ln) for ln in r.stdout.splitlines()
             if ln.startswith("{")]
    return r, lines


def _last_line_is_the_device(lines, count):
    last = lines[-1]
    assert last == {"ok": True, "tiny": True,
                    "device": {"platform": "cpu", "kind": "cpu",
                               "count": count}}


def test_tiny_smoke_runs_every_phase_on_cpu(tmp_path):
    r, lines = _smoke(["--tiny", "--seed", "3"], tmp_path)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"
    train, serve = lines[0], lines[1]
    assert [ln.get("phase") for ln in lines[:-1]] == ["train", "serve"]
    assert train["ok"] and train["platform"] == "cpu"
    assert train["fused_dispatches"] == 4 and len(train["fused_losses"]) == 5
    assert np.isfinite(train["fused_losses"] + train["eager_losses"]).all()
    assert train["step_custom_calls"] == 0 and train["pallas_calls"] == 0
    assert train["fell_back"] == [] and train["fallback_calls"] == 0
    assert train["eager_bulked_ops"] > 0
    assert serve["ok"] and serve["platform"] == "cpu"
    assert serve["token_exact"] is True and serve["divergences"] == {}
    kernel = serve["kernel_error_over_tolerance"]
    assert sorted(kernel) == ["bf16_C1", "bf16_C5_verify", "int8_C1"]
    assert 0 < max(kernel.values()) <= 1.0
    assert serve["retraces_after_warmup"] == 0 and serve["prefix_hits"] == 1
    assert serve["pallas_calls"] > 0 and serve["fallback_calls"] == 0
    assert serve["prompt_lengths"]["chunked"] > serve["prefill_window"]
    assert serve["requests"] == 6
    _last_line_is_the_device(lines, count=1)
    # the cache went where the variable placed it
    assert os.listdir(tmp_path / "jax_cache")


def test_tiny_four_device_smoke_runs_only_the_sharded_step(tmp_path):
    r, lines = _smoke(["--tiny", "--chips", "4"], tmp_path)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"
    assert [ln.get("phase") for ln in lines[:-1]] == ["sharded"]
    sharded = lines[0]
    for name in ("dp2_tp2", "sp2_tp2_ring"):
        np.testing.assert_allclose(sharded[name]["losses"],
                                   sharded["one_device_losses"],
                                   rtol=sharded["loss_rtol"])
        assert sharded[name]["qkv_placement"]["devices"] == 4
        assert sharded[name]["collectives"]["all-reduce"] > 0
    assert sharded["sp2_tp2_ring"]["collectives"]["collective-permute"] > 0
    _last_line_is_the_device(lines, count=4)


def test_smoke_without_tiny_needs_a_tpu(tmp_path):
    r, lines = _smoke([], tmp_path, timeout=120)
    assert r.returncode != 0
    assert lines == [] and "no TPU" in r.stderr


def test_divergence_report_names_the_position_and_judges_the_tie():
    """The bf16 near-tie protocol of the serve phase, on a float32 toy
    where logits are far apart: a swapped token is found at its position
    and is NOT inside the tolerance; equal outputs report nothing."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    from incubator_mxnet_tpu import serve
    model = serve.CachedDecoder(serve.DecoderConfig(
        vocab=64, embed=32, layers=2, heads=4, head_dim=8, max_len=32),
        seed=1)
    prompt = [5, 9, 2, 40, 7]
    want = list(model.reference_generate(prompt, 6, window=16))
    assert chip_smoke.divergence_report(model, 2, prompt, want, want) is None
    got = list(want)
    got[3] = (want[3] + 1) % 64
    report = chip_smoke.divergence_report(model, 2, prompt, got, want)
    assert report["position"] == 3
    assert report["reference_token"] == want[3]
    assert report["max_logit_gap_between_sides"] <= report["logit_tolerance"]
    assert report["within_tolerance"] is False
