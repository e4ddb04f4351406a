"""The `serve_delta_moe` path, its reference, weights, work functions and
metric files: found by name with no edit to `harness.py`, counted by hand,
rehearsed on the CPU at the tiny preset, and `correct` at that size: a
sound run reads true, the controls (the reference one precision below the
tiny configuration's float32) and every planted fault of
`reference/ling_kda.py` read false by a limit."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import checks, harness, traffic, weights_ling, work_ling
from chipbench.paths import serve_delta_moe
from chipbench.reference import ling_kda

ROOT = harness.os.path.dirname(harness.HERE)
TINY = "chipbench/tests/tiny/BENCHMARK_delta_moe.json"
CELL = "ling3f_serve.longgen"


def test_new_files_are_found_by_name():
    bench = harness.Bench(ROOT)
    found = bench.listing()
    assert "serve_delta_moe" in found["paths"]
    cell = next(c for c in found["cells"] if c["name"] == CELL)
    assert cell["traffic"].endswith("traffic/longgen.json")
    assert cell["end_to_end"] == ["out_tok_s", "tok_lat_p95_ms", "setup_s"]
    assert set(cell["per_layer"]) == {
        "sched_occupancy.serve", "decode_prog_ms.serve",
        "prefill_prog_ms.serve", "mfu.serve", "device_idle.serve",
        "wave_host_ms.serve", "wave_pack_ms.serve", "wave_turnover_ms.serve",
        "cache_live_share.serve", "expert_hit_share.serve",
        "kda_state_ms.serve", "kda_chunk_ms.serve", "group_kept_share.serve",
        "latent_read_roofline.serve"}
    # appended after the five cells that were there, in their order (a
    # later cell comes after it: nothing here says it is the last)
    names = [c["name"] for c in bench.spec["workloads"]]
    assert names[:6] == ["resnet50_train.feed", "cgpt13b_serve.decode",
                         "phi4mf_serve.reason", "cgpt13b_serve.prefill",
                         "glm52_serve.longctx", CELL]
    assert [c["name"] for c in bench.spec["configs"]][4] == "ling3f_serve"


def test_the_configuration_states_the_source_and_the_cut():
    cfg = harness.Bench(ROOT).config("ling3f_serve")
    pub, m = cfg["published"], cfg["model"]
    entry = next(c for c in harness.Bench(ROOT).spec["configs"]
                 if c["name"] == "ling3f_serve")
    assert cfg["source"] == entry["source"]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"]) == sorted(
        ["num_hidden_layers", "first_k_dense_replace", "num_experts",
         "vocab_size"])
    # every published key sits at the top level, changed only if reduced
    for key, value in pub.items():
        assert (cfg[key] == value) != (key in cfg["reduced"]), key
    # no width differs from the published one
    for ours, theirs in (
            ("embed", "hidden_size"), ("heads", "num_attention_heads"),
            ("head_dim", "head_dim"), ("kv_lora_rank", "kv_lora_rank"),
            ("qk_nope_head_dim", "qk_nope_head_dim"),
            ("qk_rope_head_dim", "qk_rope_head_dim"),
            ("v_head_dim", "v_head_dim"),
            ("conv_kernel", "short_conv_kernel_size"),
            ("kda_lower_bound", "kda_lower_bound"),
            ("mlp_hidden", "intermediate_size"),
            ("expert_hidden", "moe_intermediate_size"),
            ("routed_experts", "num_experts"),
            ("experts_per_token", "num_experts_per_tok"),
            ("n_group", "n_group"), ("topk_group", "topk_group"),
            ("routed_scaling_factor", "routed_scaling_factor"),
            ("rope_theta", "rope_theta"), ("norm_eps", "rms_norm_eps")):
        assert m[ours] == pub[theirs], ours
    assert pub["q_lora_rank"] is None
    assert pub["moe_shared_expert_intermediate_size"] == m["expert_hidden"]
    # the cut: which layers, how many experts held, the vocabulary's slice
    kept = cfg["layers_kept"]
    period = pub["layer_group_size"]
    assert m["mixer_types"] == ["mla" if (l + 1) % period == 0 else "kda"
                                for l in kept]
    assert m["mlp_types"] == ["dense" if l < pub["first_k_dense_replace"]
                              else "sparse" for l in kept]
    assert cfg["num_hidden_layers"] == len(kept) == 7
    assert cfg["first_k_dense_replace"] == m["mlp_types"].count("dense") == 1
    assert len(kept) - 1 == period          # one whole period, and >= 4
    assert cfg["num_experts"] == m["held_count"] == 64 \
        == pub["num_experts"] // pub["n_group"]     # one routing group
    assert cfg["vocab_size"] == m["vocab"] == pub["vocab_size"] // 8
    assert all(pub["expert_swiglu_limit_list"][l] == 0
               and pub["share_expert_swiglu_limit_list"][l] == 0
               for l in kept)               # no clamp on a layer kept
    assert cfg["engine"]["max_slots"] == 128 and m["max_len"] == 16384
    assert cfg["engine"]["prefix_cache_slots"] == 0
    assert cfg["engine"]["draft_tokens"] == 0
    assert -m["kda_lower_bound"] * weights_ling.delta_moe_config(
        m).sub_chunk == 80


LING = harness.Bench(ROOT).config("ling3f_serve")["model"]


def test_parameters_and_work_against_hand_counts():
    p = work_ling.matmul_params(LING)
    d, H, HD = 2560, 32, 4096
    assert p["kda"] == 6 * 10_485_760 + d * H
    assert p["mla"] + p["kv_b"] == 15_728_640 + 1_474_560 + 4_194_304 \
        + 10_485_760 + d * H
    assert p["dense"] == 47_185_920 and p["expert"] == 5_898_240
    assert p["router"] == 1_310_720
    assert p["experts"] == 5_898_240 * 8 * 64 / 512     # one expert
    assert weights_ling.param_count(LING) == work_ling.held_param_count(LING)
    shapes = weights_ling.ling_shapes(LING)
    held = sum(int(np.prod(shapes[n][0])) for n in ("e_gate_up", "e_down"))
    assert held == 2_264_924_160                        # the issue's 4.53 GB
    assert abs(weights_ling.param_count(LING) - 2.8663e9) < 1e6
    # a lane's state in one layer is 2 MiB; a micro-step of 128 lanes and
    # 6 layers has to read and to write 3.2 GB of it
    assert work_ling.kda_state_bytes(LING) == 2 * 2 ** 21
    assert work_ling.kda_state_bytes(LING, 128 * 6) == 3_221_225_472
    assert work_ling._keys_live(2046, 4) == 2047 + 2048 + 2049 + 2050
    # one served token after a 1-token prompt: position 0 through every
    # layer, one key read by the one MLA layer (its K and V rebuilt), a head
    want = work_ling.position_flops(LING) \
        + 2 * p["kv_b"] + 2 * H * (128 + 64 + 128) + 2 * 19648 * d
    assert work_ling.request_flops(LING, 1, 1) == want
    # a second served token: position 1, absorbed, over 2 keys
    more = work_ling.position_flops(LING) \
        + 2 * H * (128 * 512 + 512 * 128) + 2 * 2 * H * (2 * 512 + 64) \
        + 2 * 19648 * d
    assert work_ling.request_flops(LING, 1, 2) == want + more
    # the recurrence is 7 H D D = 3.7 MFLOP a position a layer, 2% of the
    # layer's matmuls
    kda = 2 * p["kda"] + 2 * 4 * 3 * HD + 7 * H * 128 * 128
    assert work_ling.position_flops(LING) == 6 * kda + 2 * p["mla"] \
        + 2 * p["dense"] + 6 * 2 * (p["router"] + 2 * p["expert"])
    assert 1.0e9 < work_ling.position_flops(LING) < 1.2e9


def test_drawn_weights_are_what_the_configuration_states():
    m = dict(LING, vocab=512, embed=256, heads=4, head_dim=32,
             kv_lora_rank=32, mlp_hidden=512, expert_hidden=64,
             mixer_types=["kda", "mla"], mlp_types=["dense", "sparse"],
             max_len=64, dtype="float32")
    w = {k: np.asarray(v) for k, v in
         weights_ling.ling_params(m, 2**31 + 5).items()}
    for name, key in (("emb", "emb_std"), ("k_qkv", "init_std"),
                      ("head", "init_std"), ("k_o", "o_std"),
                      ("m_wo", "o_std"), ("d_down", "down_std"),
                      ("e_down", "down_std"), ("m_wq", "q_std"),
                      ("m_wkv_b", "kv_b_std"), ("k_f", "kda_f_std"),
                      ("k_beta", "kda_beta_std"), ("k_conv", "conv_std"),
                      ("r_w", "router_std"), ("r_b", "router_bias_std"),
                      ("e_gate_up", "init_std"), ("s_down", "down_std")):
        assert abs(w[name].std() - m[key]) < 0.08 * m[key], name
    lo, hi = m["kda_bf_range"]
    assert lo <= w["k_bf"].min() and w["k_bf"].max() <= hi
    assert (w["k_A"] == 0).all()        # the program's own initial value
    assert w["k_bf"].dtype == w["k_A"].dtype == np.float32
    # half-lives of ln 2 / (5 sigmoid(b_f)): tens to thousands of positions
    half = np.log(2) / (5 / (1 + np.exp(-w["k_bf"])))
    assert half.min() > 8 and half.max() > 2000 and np.median(half) > 100
    assert w["e_gate_up"].shape == (1, 64, 256, 128)
    # one expert is not another
    assert not np.array_equal(w["e_gate_up"][0, 0], w["e_gate_up"][0, 1])
    for name in ("ln1_w", "k_onorm", "m_kv_norm", "lnf_w"):
        assert (w[name] == 1).all()
    assert w["r_b"].dtype == np.float32
    again = weights_ling.ling_params(m, 2**31 + 5)
    assert all((np.asarray(again[k]) == w[k]).all() for k in w)
    other = weights_ling.ling_params(m, 2**31 + 6)
    assert not np.array_equal(np.asarray(other["m_wq"]), w["m_wq"])


def test_model_counters_are_differences_of_two_snapshots():
    a = {"moe": {"groups_kept_here": 5, "tokens_routed": 9},
         "state": {"lane_layer_steps": 1}}
    b = {"moe": {"groups_kept_here": 9, "tokens_routed": 17},
         "state": {"lane_layer_steps": 4}}
    assert serve_delta_moe.model_counters(a, b) == {
        "moe_groups_kept_here": 4, "moe_tokens_routed": 8,
        "state_lane_layer_steps": 3}
    # the parent's stats() has neither group
    assert serve_delta_moe.model_counters({}, {}) == {}


def tiny_line(seed=2**31 + 11, trace="1"):
    done = subprocess.run(
        [sys.executable, "chipbench/tests/rehearse.py", "--bench", TINY,
         "--workload", "delta_moe_tiny_serve.longgen_tiny", "--seed",
         str(seed), "--seconds", "2", "--trace", trace],
        cwd=ROOT, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_rehearsal_of_the_tiny_cell_prints_a_correct_line():
    line = tiny_line()
    assert line["correct"] is True and line["failed"] == 0
    assert line["compared"]["retraces_in_window"]["value"] == 0
    assert line["compared"]["requests_cut_short"]["value"] == 0
    assert line["compared"]["kernel_fallbacks"]["value"] == 0
    assert 0 < line["metrics"]["expert_hit_share.serve"]["value"] <= 100
    assert 25 < line["metrics"]["group_kept_share.serve"]["value"] < 75
    assert 0 < line["metrics"]["cache_live_share.serve"]["value"] <= 100
    assert line["metrics"]["mfu.serve"]["value"] > 0
    # no device trace on the CPU: the two device metrics are left out
    assert "kda_state_ms.serve" not in line["metrics"]
    assert "kda_chunk_ms.serve" not in line["metrics"]
    assert "latent_read_roofline.serve" not in line["metrics"]
    assert line["notes"]["latent_read_bytes"] > 0
    # the two longest generations' states, 3 KDA layers each, read from
    # the drained pool: float32 against float32; the first layer's compared
    assert line["notes"]["states_checked"] == 2
    assert 0 < line["compared"]["kda_state_gap"]["value"] \
        <= line["notes"]["kda_state_gap_widest"] < 1e-4
    assert line["notes"]["served_tokens_checked"] > 0
    assert line["notes"]["kda_state_bytes"] \
        == 2 * 4 * 4 * 16 * 16 * line["notes"]["state_lane_layer_steps"]


@pytest.mark.parametrize(
    "judge", ("bfloat16", "bf16_state", "int8") + ling_kda.FAULTS)
def test_controls_and_planted_faults_fail_a_limit_at_the_tiny_size(judge):
    """Through `checks.served`, `state_check` and the tiny configuration's
    limits, as a run's line is judged: the tokens that the lower precision
    or the faulted forward puts first lie too far below the float32
    reference's best, or the state it leaves too far from the float32
    recurrence's. The two faults at a chunk edge cut at the engine's own
    window. A bfloat16 state fails BOTH here; at the served size it fails
    the state's limit alone (PERF.md section 6, PR 33)."""
    bench = harness.Bench(ROOT, TINY)
    cfg = bench.config("delta_moe_tiny_serve")
    tr = traffic.load(bench.find("traffic", "longgen_tiny"))
    params = weights_ling.ling_params(cfg["model"], 5)
    source = traffic.requests(tr, 5, cfg["model"]["vocab"])
    rng = np.random.default_rng(5)
    reqs = [{"prompt": p, "tokens": rng.integers(
                1, cfg["model"]["vocab"], size=40).astype(np.int32)}
            for _, p, _n in (next(source) for _ in range(4))]
    gaps, state_gaps = serve_delta_moe.read_against_reference(
        cfg, tr, params, reqs, [(r, []) for r in reqs[:2]], precision=judge)
    got = dict(checks.served(gaps), **serve_delta_moe.state_check(state_gaps))
    assert [len(g) for g in state_gaps] == [3, 3]
    assert any(got[k] > cfg["limits"][k] for k in got), got
    if judge == "bf16_state":
        assert got["kda_state_gap"] > 10 * cfg["limits"]["kda_state_gap"]


def test_latent_read_work_by_hand():
    """One request, 10 prompt tokens and 5 served between t = 0 and 4: the
    decode steps of tokens 1..4 read 11, 12, 13, 14 positions of the one
    MLA layer's leaf; the interval [0.5, 2.5) holds tokens 1 and 2."""
    flops, byts = work_ling.latent_read_interval_work(
        LING, [(10, 5, 0.0, 4.0)], 0.5, 2.5)
    assert byts == (11 + 12) * 576 * 2
    assert flops == (11 + 12) * 2 * 32 * (2 * 512 + 64)
    assert work_ling.latent_read_interval_work(
        LING, [(10, 1, 0.0, 0.0)], 0.0, 9.0) == (0.0, 0.0)


def test_no_state_read_is_under_no_limit():
    got = serve_delta_moe.state_check([])["kda_state_gap"]
    assert not got <= 1.0
    assert serve_delta_moe.state_check(
        [[0.1, 0.9], [0.2, 0.3]])["kda_state_gap"] == 0.2
