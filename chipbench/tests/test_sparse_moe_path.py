"""The `serve_sparse_moe` path, its reference, weights, work functions,
readers and metric files: found by name with no edit to `harness.py`,
counted by hand, rehearsed on the CPU at the tiny preset, and `correct` at
that size: a sound run reads true, the control (the reference one
precision below the tiny configuration's float32) and every planted fault
of `reference/glm_dsa.py` read false by a limit."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import checks, harness, traffic, weights_glm, work_glm
from chipbench.paths import serve_sparse_moe
from chipbench.readers import counter_share, xplane_ops_per_step_ms
from chipbench.reference import glm_dsa

ROOT = harness.os.path.dirname(harness.HERE)
TINY = "chipbench/tests/tiny/BENCHMARK_sparse_moe.json"
CELL = "glm52_serve.longctx"


def test_new_files_are_found_by_name():
    bench = harness.Bench(ROOT)
    found = bench.listing()
    assert "serve_sparse_moe" in found["paths"]
    assert {"counter_share", "xplane_ops_per_step_ms"} <= set(found["readers"])
    cell = next(c for c in found["cells"] if c["name"] == CELL)
    assert cell["traffic"].endswith("traffic/longctx.json")
    assert cell["end_to_end"] == ["out_tok_s", "tok_lat_p95_ms", "setup_s"]
    assert set(cell["per_layer"]) == {
        "sched_occupancy.serve", "decode_prog_ms.serve",
        "prefill_prog_ms.serve", "mfu.serve", "device_idle.serve",
        "wave_host_ms.serve", "wave_pack_ms.serve", "wave_turnover_ms.serve",
        "cache_live_share.serve", "expert_hit_share.serve",
        "select_keep_share.serve", "sparse_read_ms.serve",
        "index_select_ms.serve"}
    # the cell is the last entry, and nothing before it moved
    assert bench.spec["workloads"][-1]["name"] == CELL
    assert bench.spec["configs"][-1]["name"] == "glm52_serve"


def test_the_configuration_states_the_source_and_the_cut():
    cfg = harness.Bench(ROOT).config("glm52_serve")
    pub, m = cfg["published"], cfg["model"]
    entry = next(c for c in harness.Bench(ROOT).spec["configs"]
                 if c["name"] == "glm52_serve")
    assert cfg["source"] == entry["source"]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"]) == sorted(
        ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
         "vocab_size"])
    # every published key sits at the top level, changed only if reduced
    for key, value in pub.items():
        assert (cfg[key] == value) != (key in cfg["reduced"]), key
    # no width differs from the published one
    for ours, theirs in (
            ("embed", "hidden_size"), ("heads", "num_attention_heads"),
            ("q_lora_rank", "q_lora_rank"), ("kv_lora_rank", "kv_lora_rank"),
            ("qk_nope_head_dim", "qk_nope_head_dim"),
            ("qk_rope_head_dim", "qk_rope_head_dim"),
            ("v_head_dim", "v_head_dim"), ("index_heads", "index_n_heads"),
            ("index_head_dim", "index_head_dim"),
            ("index_topk", "index_topk"), ("mlp_hidden", "intermediate_size"),
            ("expert_hidden", "moe_intermediate_size"),
            ("routed_experts", "n_routed_experts"),
            ("experts_per_token", "num_experts_per_tok"),
            ("routed_scaling_factor", "routed_scaling_factor"),
            ("norm_eps", "rms_norm_eps")):
        assert m[ours] == pub[theirs], ours
    assert m["rope_theta"] == pub["rope_parameters"]["rope_theta"]
    # the cut: which layers, how many experts held, the vocabulary's slice
    kept = cfg["layers_kept"]
    assert m["indexer_types"] == [pub["indexer_types"][l] for l in kept]
    assert m["mlp_types"] == [pub["mlp_layer_types"][l] for l in kept]
    assert cfg["num_hidden_layers"] == len(kept) == 5
    assert cfg["first_k_dense_replace"] == m["mlp_types"].count("dense") == 1
    assert cfg["n_routed_experts"] == m["held_count"] == 16
    assert m["held_count"] >= 8 and len(kept) - 1 >= 4      # the floors
    assert cfg["vocab_size"] == m["vocab"] == pub["vocab_size"] // 8
    assert cfg["engine"]["max_slots"] == 32 and m["max_len"] == 16384
    assert cfg["engine"]["prefix_cache_slots"] == 0
    assert cfg["engine"]["draft_tokens"] == 0


GLM = harness.Bench(ROOT).config("glm52_serve")["model"]


def test_parameters_and_work_against_hand_counts():
    p = work_glm.matmul_params(GLM)
    # the issue's table: attention of one layer 165,019,648 with Wkv_b
    assert p["attn"] + p["kv_b"] == 165_019_648
    assert p["indexer"] == 9_371_648                    # without the norm
    assert p["dense"] == 226_492_416 and p["expert"] == 37_748_736
    assert p["router"] == 1_572_864
    assert p["experts"] == 37_748_736 * 8 * 16 / 256    # half an expert
    assert weights_glm.glm_param_count(GLM) == work_glm.held_param_count(GLM)
    assert abs(weights_glm.glm_param_count(GLM) - 3.8815e9) < 1e6
    # keys read by positions 2046 .. 2049 under a top-k of 2048
    assert work_glm._keys_read(2046, 4, 2048) == 2047 + 3 * 2048
    assert work_glm._keys_read(0, 3, 2048) == 6
    assert work_glm._keys_live(2046, 4) == 2047 + 2048 + 2049 + 2050
    # one served token after a 1-token prompt: position 0 through every
    # layer, one key read in each of the 5 reads (its own K and V rebuilt),
    # one key scored by each of the 2 indexers, one head
    d, H = 6144, 64
    want = work_glm.position_flops(GLM) \
        + 5 * (2 * p["kv_b"] + 2 * H * (192 + 64 + 256)) \
        + 2 * 2 * 32 * 128 + 2 * 19360 * d
    assert work_glm.request_flops(GLM, 1, 1) == want
    # a second served token: position 1, absorbed, over 2 keys
    more = work_glm.position_flops(GLM) \
        + 5 * (2 * H * (192 * 512 + 512 * 256) + 2 * 2 * H * (2 * 512 + 64)) \
        + 2 * 2 * 32 * 128 * 2 + 2 * 19360 * d
    assert work_glm.request_flops(GLM, 1, 2) == want + more
    # a long request's prompt token costs 3 to 4 GFLOP here
    per = work_glm.request_flops(GLM, 4096, 1) / 4096
    assert 2.5e9 < per < 4e9, per


def test_drawn_weights_are_what_the_configuration_states():
    m = dict(GLM, vocab=512, embed=256, heads=4, q_lora_rank=64,
             kv_lora_rank=32, mlp_hidden=512, expert_hidden=64,
             indexer_types=["full", "shared"], mlp_types=["dense", "sparse"],
             max_len=64, dtype="float32")
    w = {k: np.asarray(v) for k, v in
         weights_glm.glm_params(m, 2**31 + 5).items()}
    for name, key in (("emb", "emb_std"), ("wq_a", "init_std"),
                      ("head", "init_std"), ("wo", "o_std"),
                      ("d_down", "down_std"), ("e_down", "down_std"),
                      ("wq_b", "q_b_std"), ("wkv_b", "kv_b_std"),
                      ("i_wq", "index_q_std"), ("i_wk", "index_k_std"),
                      ("r_w", "router_std"), ("r_b", "router_bias_std"),
                      ("e_gate_up", "init_std"), ("s_down", "down_std")):
        assert abs(w[name].std() - m[key]) < 0.08 * m[key], name
    assert w["e_gate_up"].shape == (1, 16, 256, 128)
    # one expert is not another
    assert not np.array_equal(w["e_gate_up"][0, 0], w["e_gate_up"][0, 1])
    for name in ("ln1_w", "q_norm", "kv_norm", "i_k_norm_w", "lnf_w"):
        assert (w[name] == 1).all()
    assert (w["i_k_norm_b"] == 0).all()
    assert w["r_b"].dtype == np.float32
    again = weights_glm.glm_params(m, 2**31 + 5)
    assert all((np.asarray(again[k]) == w[k]).all() for k in w)
    other = weights_glm.glm_params(m, 2**31 + 6)
    assert not np.array_equal(np.asarray(other["wq_b"]), w["wq_b"])


def test_readers_return_none_where_there_is_nothing_to_read():
    how = {"part": "moe_experts_hit", "whole": "moe_experts_offered"}
    assert counter_share.read(how, {"counters": {
        "moe_experts_hit": 30, "moe_experts_offered": 120}}) == 25.0
    # the parent's stats() has no such counters; an idle window offers 0
    assert counter_share.read(how, {"counters": {}}) is None
    assert counter_share.read(how, {"counters": {
        "moe_experts_hit": 0, "moe_experts_offered": 0}}) is None
    how = {"pattern": "x", "module": "y", "steps": "decode_steps"}
    assert xplane_ops_per_step_ms.read(how, {"trace": None,
                                             "counters": {}}) is None

    class Trace:
        def op_time_s(self, pattern):
            return (0.06, 40) if pattern == "x" else (0.0, 0)

        def module_time_s(self, pattern):
            return (1.0, 5)

    ctx = {"trace": Trace(), "counters": {"decode_steps": 4}}
    assert xplane_ops_per_step_ms.read(how, ctx) == pytest.approx(3.0)
    assert xplane_ops_per_step_ms.read(dict(how, pattern="z"), ctx) is None
    assert xplane_ops_per_step_ms.read(how, dict(ctx, counters={})) is None


def test_model_counters_are_differences_of_two_snapshots():
    a = {"moe": {"pairs_held": 5, "experts_hit": 2}, "sparse": {"queries": 1}}
    b = {"moe": {"pairs_held": 9, "experts_hit": 7}, "sparse": {"queries": 4}}
    assert serve_sparse_moe.model_counters(a, b) == {
        "moe_pairs_held": 4, "moe_experts_hit": 5, "sparse_queries": 3}
    assert serve_sparse_moe.model_counters({}, {}) == {}


def tiny_line(seed=2**31 + 11, trace="1"):
    done = subprocess.run(
        [sys.executable, "chipbench/tests/rehearse.py", "--bench", TINY,
         "--workload", "sparse_moe_tiny_serve.longctx_tiny", "--seed",
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
    assert 0 < line["metrics"]["expert_hit_share.serve"]["value"] <= 100
    assert 0 < line["metrics"]["select_keep_share.serve"]["value"] < 100
    assert 0 < line["metrics"]["cache_live_share.serve"]["value"] <= 100
    assert line["metrics"]["mfu.serve"]["value"] > 0
    # no device trace on the CPU: the two device metrics are left out
    assert "sparse_read_ms.serve" not in line["metrics"]
    assert line["notes"]["served_tokens_checked"] > 0


@pytest.mark.parametrize("judge", ("bfloat16", "int8") + glm_dsa.FAULTS)
def test_control_and_planted_faults_fail_a_limit_at_the_tiny_size(judge):
    """Through `checks.served` and the tiny configuration's limits, as a
    run's line is judged: the tokens that the lower precision or the
    faulted forward puts first lie too far below the float32 reference's
    best."""
    bench = harness.Bench(ROOT, TINY)
    cfg = bench.config("sparse_moe_tiny_serve")
    tr = traffic.load(bench.find("traffic", "longctx_tiny"))
    params = weights_glm.glm_params(cfg["model"], 5)
    source = traffic.requests(tr, 5, cfg["model"]["vocab"])
    rng = np.random.default_rng(5)
    reqs = [{"prompt": p, "tokens": rng.integers(
                1, cfg["model"]["vocab"], size=60).astype(np.int32)}
            for _, p, _n in (next(source) for _ in range(4))]
    got = checks.served(serve_sparse_moe.served_gaps(
        cfg, tr, params, reqs, precision=judge))
    assert any(got[k] > cfg["limits"][k] for k in got), got
