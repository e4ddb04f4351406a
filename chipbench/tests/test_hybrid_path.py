"""The `serve_hybrid` path, its reference, weights, work functions, reader
and metric files: found by name with no edit to `harness.py`, counted by
hand, and rehearsed on the CPU at the tiny preset."""
import json
import os
import subprocess
import sys

from chipbench import harness, weights_sambay, work_sambay
from chipbench.readers import cache_live_share

ROOT = harness.os.path.dirname(harness.HERE)
PHI = {"vocab": 200064, "embed": 2560, "layers": 32, "heads": 40,
       "kv_heads": 20, "head_dim": 64, "mlp_hidden": 10240, "window": 512,
       "d_state": 16, "d_conv": 4, "expand": 2, "dt_rank": 160,
       "max_len": 4096, "dtype": "bfloat16", "ln_eps": 1e-5,
       "init_std": 0.02, "lambda_std": 0.1, "x_proj_std": 0.065}


def test_new_files_are_found_by_name():
    bench = harness.Bench(ROOT)
    found = bench.listing()
    assert {"serve_engine", "serve_hybrid", "train_fused"} <= set(found["paths"])
    assert "cache_live_share" in found["readers"]
    cell = next(c for c in found["cells"] if c["name"] == "phi4mf_serve.reason")
    assert cell["traffic"].endswith("traffic/reason.json")
    assert cell["end_to_end"] == ["out_tok_s", "tok_lat_p95_ms", "setup_s"]
    assert {"shared_attn_roofline.serve", "cache_live_share.serve",
            "mfu.serve", "decode_prog_ms.serve"} <= set(cell["per_layer"])
    assert "paged_attn_roofline.serve" not in cell["per_layer"]
    cfg = bench.config("phi4mf_serve")
    assert cfg["path"] == "serve_hybrid" and cfg["reduced"] == []
    assert cfg["model"] == PHI
    # the catalog's numbers sit at the top level under their own keys
    for key, value in cfg["published"].items():
        assert cfg[key] == value, key
    assert cfg["hidden_size"] == cfg["model"]["embed"]
    assert cfg["sliding_window"] == cfg["model"]["window"]
    assert cfg["num_key_value_heads"] == cfg["model"]["kv_heads"]


def test_parameters_and_work_against_hand_counts():
    # 9 Mamba (119.9 M with its MLP), 9 attention (98.3 M), 7 cross
    # (91.8 M), 7 GMU (104.9 M) layers + the tied embedding: 3.85 B
    p = work_sambay.matmul_params(PHI)
    assert p["mlp"] == 3 * 2560 * 10240
    assert p["mamba"] == 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    assert p["attn"] == 2560 * (2560 + 2 * 1280) + 2560 * 2560
    assert p["cross"] == 2 * 2560 * 2560 and p["gmu"] == 2 * 2560 * 5120
    assert abs(weights_sambay.sambay_param_count(PHI) - 3.852e9) < 2e6
    # one served token after a 1-token prompt: position 0 through both
    # halves, one key in each of the 8 window and 8 shared reads, one head
    lower = 2 * (9 * (p["mamba"] + p["mlp"]) + 8 * (p["attn"] + p["mlp"])
                 + 2560 * 2560) + 9 * (2 * 4 * 5120 + 6 * 5120 * 16)
    upper = 2 * (p["attn"] - 2560 * 2560 + p["mlp"]
                 + 7 * (p["cross"] + p["gmu"] + 2 * p["mlp"]))
    assert work_sambay.request_flops(PHI, 1, 1) == \
        lower + upper + 16 * 6 * 2560 + 2 * 200064 * 2560
    # the shared read of a 10-token prompt and 3 served tokens, first
    # token at t = 1, last at t = 3: reads of 10, 11 and 12 positions at
    # t = 1, 2, 3 in 8 layers, K and V of 1280 in bf16
    req = [(10, 3, 1.0, 3.0)]
    flops, byts = work_sambay.shared_attn_interval_work(PHI, req, 0.0, 9.0)
    assert flops == 8 * 6 * 2560 * 33
    assert byts == 8 * 2 * 33 * 1280 * 2
    # an interval owes only the reads that fall inside it
    for (t_a, t_b), seen in (((0.0, 1.0), 0), ((1.0, 2.0), 10),
                             ((1.5, 3.5), 23), ((3.5, 9.0), 0)):
        assert work_sambay.shared_attn_interval_work(PHI, req, t_a, t_b) \
            == (8 * 6 * 2560 * seen, 8 * 2 * seen * 1280 * 2)
    assert work_sambay.shared_attn_interval_work(PHI, [], 0.0, 1.0) \
        == (0.0, 0.0)
    # a window layer's keys: 600 positions under a window of 512
    assert work_sambay._window_keys(600, 512) == 512 * 513 // 2 + 88 * 512


def test_drawn_weights_are_what_the_configuration_states():
    """The leaves and their kinds are the program's table; the values are
    held here to `assumed.weights`, so that a change of the program's draw
    cannot move the benchmark's weights unseen."""
    import numpy as np
    m = dict(PHI, vocab=512, embed=256, mlp_hidden=512, heads=4, kv_heads=2,
             dt_rank=16, max_len=64, window=16, dtype="float32")
    w = {k: np.asarray(v) for k, v in
         weights_sambay.sambay_params(m, 2**31 + 5).items()}
    K, R, N = m["d_conv"], m["dt_rank"], m["d_state"]

    def uniform(a, bound):
        return np.abs(a).max() <= bound \
            and abs(a.std() - bound / np.sqrt(3)) < 0.03 * bound

    assert uniform(w["m_conv_w"], K ** -0.5) and uniform(w["m_dt_w"], R ** -0.5)
    for name, std in (("emb", 0.02), ("m_in", 0.02), ("mlp_down", 0.02),
                      ("a_qkv", 0.02), ("g_w1", 0.02), ("m_x", 0.065),
                      ("a_lq1", 0.1), ("c_lk2", 0.1)):
        assert abs(w[name].std() - std) < 0.06 * std, name
    assert np.allclose(w["m_A_log"][2, :, 7], np.log(np.arange(1, N + 1)))
    dt = np.log1p(np.exp(w["m_dt_b"]))
    assert 0.99e-3 <= dt.min() and dt.max() <= 1.01e-1
    assert np.median(dt) < 0.02             # log-uniform, not uniform
    for name in ("m_D", "ln1_w", "a_sub"):
        assert (w[name] == 1).all()
    for name in ("m_conv_b", "a_qkv_b", "c_o_b", "lnf_b"):
        assert (w[name] == 0).all()
    again = weights_sambay.sambay_params(m, 2**31 + 5)
    assert all((np.asarray(again[k]) == w[k]).all() for k in w)


def test_cache_live_share_reader():
    ctx = {"counters": {"cache_live_bytes_sum": 300.0, "cache_bytes": 100,
                        "decode_iterations": 4}}
    assert cache_live_share.read({}, ctx) == 75.0
    # the parent's stats() has no `cache` entry: the metric is left out
    assert cache_live_share.read({}, {"counters": {"decode_iterations": 4}}) \
        is None
    assert cache_live_share.read({}, {"counters": {
        "cache_live_bytes_sum": 1.0, "cache_bytes": 9}}) is None


def test_rehearsal_of_the_tiny_hybrid_cell_prints_a_correct_line():
    done = subprocess.run(
        [sys.executable, "chipbench/tests/rehearse.py", "--bench",
         "chipbench/tests/tiny/BENCHMARK_hybrid.json", "--workload",
         "hybrid_tiny_serve.reason_tiny", "--seed", str(2**31 + 11),
         "--seconds", "2", "--trace", "1"],
        cwd=ROOT, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["compared"]["kernel_fallbacks"]["value"] == 0
    assert 0 < line["metrics"]["cache_live_share.serve"]["value"] <= 100
    assert line["notes"]["paged_shared_traces"] > 0
    assert line["notes"]["paged_window_traces"] > 0
