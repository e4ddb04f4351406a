"""The `serve_mamba_moe` path, its reference, weights, work functions,
reader and metric files: found by name with no edit to `harness.py`,
counted by hand, rehearsed on the CPU at the tiny preset, and `correct` at
that size: a sound run reads true, the controls (the reference one
precision below the tiny configuration's float32) and every planted fault
of `reference/nemotron_h.py` read false by a limit."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import checks, harness, traffic, weights_nemotron, \
    work_nemotron
from chipbench.paths import serve_mamba_moe
from chipbench.readers import xplane_scope_roofline
from chipbench.reference import nemotron_h
from chipbench.tests.test_scope_ms import Plane

ROOT = harness.os.path.dirname(harness.HERE)
TINY = "chipbench/tests/tiny/BENCHMARK_mamba_moe.json"
CELL = "nemotron3nano_serve.chat"
NEW = ("ssm_state_roofline.serve", "ssm_chunk_ms.serve")


def test_new_files_are_found_by_name():
    bench = harness.Bench(ROOT)
    found = bench.listing()
    assert "serve_mamba_moe" in found["paths"]
    assert "xplane_scope_roofline" in found["readers"]
    cell = next(c for c in found["cells"] if c["name"] == CELL)
    assert cell["traffic"].endswith("traffic/chat.json")
    assert cell["end_to_end"] == ["out_tok_s", "tok_lat_p95_ms", "setup_s"]
    assert set(cell["per_layer"]) == {
        "sched_occupancy.serve", "decode_prog_ms.serve",
        "prefill_prog_ms.serve", "mfu.serve", "device_idle.serve",
        "wave_host_ms.serve", "wave_pack_ms.serve", "wave_turnover_ms.serve",
        "cache_live_share.serve", "expert_hit_share.serve",
        "scope_mixer_ms.serve", "scope_ffn_ms.serve",
        "scope_experts_ms.serve", "scope_head_ms.serve",
        "scope_none_ms.serve", "queue_wait_ms.serve",
        "paged_attn_roofline.serve", *NEW}
    # the two new metrics are this cell's alone
    for c in found["cells"]:
        if c["name"] != CELL:
            assert not set(NEW) & set(c["per_layer"]), c["name"]
    for name in NEW:
        mt = next(m for m in bench.spec["per_layer"] if m["name"] == name)
        assert mt["workloads"] == [CELL] and mt["moves"] == "out_tok_s"
        assert mt["layer"] == "state-space mixer"
    # appended after the seven cells that were there, in their order
    names = [c["name"] for c in bench.spec["workloads"]]
    assert names[:8] == ["resnet50_train.feed", "cgpt13b_serve.decode",
                         "phi4mf_serve.reason", "cgpt13b_serve.prefill",
                         "glm52_serve.longctx", "ling3f_serve.longgen",
                         "ouro26b_serve.math", CELL]
    assert [c["name"] for c in bench.spec["configs"]][6] \
        == "nemotron3nano_serve"


def test_the_configuration_states_the_source_and_the_cut():
    bench = harness.Bench(ROOT)
    cfg = bench.config("nemotron3nano_serve")
    pub, m = cfg["published"], cfg["model"]
    entry = next(c for c in bench.spec["configs"]
                 if c["name"] == "nemotron3nano_serve")
    assert cfg["source"] == entry["source"]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"]) == [
        "n_routed_experts", "vocab_size"]
    # every published key sits at the top level, changed only if reduced
    for key, value in pub.items():
        assert (cfg[key] == value) != (key in cfg["reduced"]), key
    # no width differs from the published one
    for ours, theirs in (
            ("embed", "hidden_size"), ("pattern", "hybrid_override_pattern"),
            ("mamba_heads", "mamba_num_heads"),
            ("mamba_head_dim", "mamba_head_dim"), ("n_groups", "n_groups"),
            ("ssm_state", "ssm_state_size"), ("conv_kernel", "conv_kernel"),
            ("chunk_size", "chunk_size"), ("heads", "num_attention_heads"),
            ("kv_heads", "num_key_value_heads"), ("head_dim", "head_dim"),
            ("expert_hidden", "moe_intermediate_size"),
            ("shared_hidden", "moe_shared_expert_intermediate_size"),
            ("experts_per_token", "num_experts_per_tok"),
            ("routed_experts", "n_routed_experts"),
            ("routed_scaling_factor", "routed_scaling_factor"),
            ("norm_eps", "norm_eps")):
        assert m[ours] == pub[theirs], ours
    # d_inner is heads x head_dim (4096), as NemotronH computes it; `expand`
    # (2 x 2688 = 5376) is read by nothing
    assert m["mamba_heads"] * m["mamba_head_dim"] == 4096 \
        != pub["expand"] * pub["hidden_size"]
    assert (pub["n_group"], pub["topk_group"], pub["n_shared_experts"]) \
        == (1, 1, 1)
    assert pub["mlp_hidden_act"] == "relu2" and not pub["tie_word_embeddings"]
    # the cut: all 52 layers, 8 of the 128 experts, an eighth of the ids
    assert len(m["pattern"]) == pub["num_hidden_layers"] == 52
    assert cfg["n_routed_experts"] == m["held_count"] == 8
    assert cfg["vocab_size"] == m["vocab"] == pub["vocab_size"] // 8
    assert m["held_first"] == 0 and m["dtype"] == "bfloat16"
    assert m["conv_range"] == [-0.5, 0.5] and m["A_log_range"] == [1, 16]
    assert m["dt_range"] == [pub["time_step_min"], pub["time_step_max"],
                             pub["time_step_floor"]]
    e = cfg["engine"]
    assert (e["max_slots"], m["max_len"], e["prefix_cache_slots"],
            e["draft_tokens"], e["decode_steps"]) == (64, 4096, 0, 0, 4)
    assert e["prefill_window"] % m["chunk_size"] == 0
    for key in ("nope", "weights", "max_len", "state", "logits", "engine",
                "limits"):
        assert cfg["assumed"][key], key
    assert "16" in cfg["deployment"]


NANO = harness.Bench(ROOT).config("nemotron3nano_serve")["model"]


def test_parameters_and_work_against_hand_counts():
    """The deployment's table: every layer, 8 held experts a layer, ids [0,
    16384): 3,422,495,040 parameters."""
    p = work_nemotron.matmul_params(NANO)
    d = 2688
    assert p["mamba"] + 4 * 6144 + 6144 + 3 * 64 + 4096 + d == 38_744_896
    assert p["attn"] + d == 23_399_040
    assert p["router"] + 128 + p["shared"] + d == 20_302_592
    assert p["expert"] == 9_977_856
    assert p["experts"] == 9_977_856 * 6 * 8 / 128
    assert weights_nemotron.param_count(NANO) \
        == work_nemotron.held_param_count(NANO) == 3_422_495_040
    shapes = weights_nemotron.nemotron_shapes(NANO)
    held = sum(int(np.prod(shapes[n][0])) for n in ("e_up", "e_down"))
    assert held == 1_835_925_504
    # a lane's state in one layer is 2 MiB; a micro-step of 64 lanes and
    # 23 layers has to read and write 6.17 GB of it, and its tails
    assert work_nemotron.state_bytes(NANO) == 2 ** 21
    flops, byts = work_nemotron.ssm_state_work(NANO, 64 * 23)
    assert byts == 64 * 23 * (2 * 2 ** 21 + 2 * 3 * 6144 * 2)
    assert flops == 64 * 23 * 5 * 64 * 64 * 128
    # one served token after a 1-token prompt: position 0 through every
    # layer, one key in each of 6 attention layers, a head
    want = work_nemotron.position_flops(NANO) + 6 * 4 * 32 * 128 \
        + 2 * 16384 * d
    assert work_nemotron.request_flops(NANO, 1, 1) == want
    assert work_nemotron.request_flops(NANO, 1, 2) == 2 * want \
        + 6 * 4 * 32 * 128
    # about 3.2 GFLOP a position
    assert 3.1e9 < work_nemotron.position_flops(NANO) < 3.3e9


def test_drawn_weights_are_what_the_configuration_states():
    m = dict(NANO, vocab=512, embed=256, pattern="ME*", mamba_heads=16,
             mamba_head_dim=16, n_groups=4, ssm_state=16, heads=4,
             kv_heads=2, head_dim=32, expert_hidden=64, shared_hidden=128,
             routed_experts=512, held_count=2, max_len=64, dtype="float32")
    w = {k: np.asarray(v) for k, v in
         weights_nemotron.nemotron_params(m, 2**31 + 5).items()}
    for name, key in (("emb", "emb_std"), ("m_in", "init_std"),
                      ("head", "init_std"), ("m_out", "o_std"),
                      ("a_o", "o_std"), ("e_down", "down_std"),
                      ("s_down", "down_std"), ("a_q", "init_std"),
                      ("r_w", "router_std"), ("r_b", "router_bias_std"),
                      ("e_up", "init_std")):
        assert abs(w[name].std() - m[key]) < 0.08 * m[key], name
    lo, hi = m["conv_range"]
    assert lo <= w["m_conv"].min() and w["m_conv"].max() <= hi
    assert w["m_conv"].std() > 0.25 and w["m_conv_b"].std() > 0.25
    A = np.exp(w["m_A_log"])
    assert 1 <= A.min() and A.max() <= 16
    dt = np.log1p(np.exp(w["m_dt_bias"]))           # softplus
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 0.1 * 1.001
    assert (w["m_D"] == 1).all()
    for name in ("m_dt_bias", "m_A_log", "m_D", "r_b"):
        assert w[name].dtype == np.float32, name
    assert w["e_up"].shape == (1, 2, 64, 256)          # (F, d) an expert
    assert not np.array_equal(w["e_up"][0, 0], w["e_up"][0, 1])
    for name in ("norm", "m_norm", "lnf_w"):
        assert (w[name] == 1).all()
    again = weights_nemotron.nemotron_params(m, 2**31 + 5)
    assert all((np.asarray(again[k]) == w[k]).all() for k in w)
    other = weights_nemotron.nemotron_params(m, 2**31 + 6)
    assert not np.array_equal(np.asarray(other["a_q"]), w["a_q"])


def test_scope_roofline_sums_the_scope_over_every_execution():
    """Two executions of `jit_decode` and one of `jit_prefill`: the
    scope's self time in the two decode executions (a `while` holding its
    body's operations counts its own time only) against the path's work."""
    ops = [("%while.1 = (f32[8]) while(x)", 0, 10), ("%state.2 = f", 1, 4),
           ("%proj.3 = f", 5, 2), ("%while.1 = (f32[8]) while(x)", 20, 10),
           ("%state.2 = f", 21, 4), ("%state.2 = f", 40, 5)]
    modules = [("jit_decode(1)", 0, 10), ("jit_decode(1)", 20, 10),
               ("jit_prefill(2)", 40, 5)]
    scopes = {"jit_decode": {"while.1": ("", ""),
                             "state.2": ("layer0/mamba/state", ""),
                             "proj.3": ("layer0/mamba/proj", "")},
              "jit_prefill": {"state.2": ("layer0/mamba/state", "")}}
    lines = xplane_scope_roofline.joined(Plane(ops, modules))
    params = {"module": r"^jit_decode\(",
              "scope": r"^layer\d+/mamba/state(/|$)", "work": "ssm_state"}
    got = xplane_scope_roofline.scope_seconds(lines, params, scopes)
    assert got == pytest.approx(8e-6)               # 4 + 4 us, not 13
    # a table that lacks most of the time: no number
    assert xplane_scope_roofline.scope_seconds(
        lines, params, {"jit_decode": {"proj.3": ("x", "")}}) is None


def test_model_counters_give_the_state_step_its_work():
    a = {"moe": {"experts_hit": 5}, "state": {"lane_layer_steps": 10}}
    b = {"moe": {"experts_hit": 9}, "state": {"lane_layer_steps": 56}}
    got = serve_mamba_moe.model_counters(a, b)
    assert got == {"moe_experts_hit": 4, "state_lane_layer_steps": 46}
    assert serve_mamba_moe.model_counters({}, {}) == {}


def tiny_line(seed=2**31 + 11, trace="1"):
    done = subprocess.run(
        [sys.executable, "chipbench/tests/rehearse.py", "--bench", TINY,
         "--workload", "mamba_moe_tiny_serve.chat_tiny", "--seed",
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
    assert 0 < line["metrics"]["cache_live_share.serve"]["value"] <= 100
    assert line["metrics"]["mfu.serve"]["value"] > 0
    # no device trace on the CPU: the two device metrics are left out
    for name in NEW:
        assert name not in line["metrics"]
    # the two longest generations' states, 3 Mamba layers each, read from
    # the drained pool: float32 against float32; the first layer's compared
    assert line["notes"]["states_checked"] == 2
    assert 0 < line["compared"]["ssm_state_gap"]["value"] \
        <= line["notes"]["ssm_state_gap_widest"] < 1e-4
    assert 0 < line["compared"]["ssm_state_layer_gap"]["value"] < 1e-4
    assert line["notes"]["served_tokens_checked"] > 0
    # the paged read's work: decode steps and chunks of the interval
    assert line["notes"]["paged_attn_bytes"] > 0
    assert line["notes"]["paged_attn_flops"] > 0
    steps = line["notes"]["state_lane_layer_steps"]
    assert line["notes"]["ssm_state_bytes"] \
        == steps * (2 * 4 * 8 * 4 * 8 + 2 * 3 * 64 * 2)


@pytest.mark.parametrize("judge", nemotron_h.PRECISIONS[1:]
                         + nemotron_h.FAULTS)
def test_controls_and_planted_faults_fail_a_limit_at_the_tiny_size(judge):
    """Through `checks.served`, `state_check` and the tiny configuration's
    limits, as a run's line is judged: the tokens that the lower precision
    or the faulted forward puts first lie too far below the float32
    reference's best, or the state it leaves too far from the float32
    recurrence's. The two faults at a chunk edge cut at the engine's own
    window."""
    bench = harness.Bench(ROOT, TINY)
    cfg = bench.config("mamba_moe_tiny_serve")
    tr = traffic.load(bench.find("traffic", "chat_tiny"))
    params = weights_nemotron.nemotron_params(cfg["model"], 5)
    source = traffic.requests(tr, 5, cfg["model"]["vocab"])
    rng = np.random.default_rng(5)
    reqs = [{"prompt": p, "tokens": rng.integers(
                1, cfg["model"]["vocab"], size=40).astype(np.int32)}
            for _, p, _n in (next(source) for _ in range(4))]
    gaps, state_gaps = serve_mamba_moe.read_against_reference(
        cfg, tr, params, reqs, [(r, []) for r in reqs[:2]], precision=judge)
    got = dict(checks.served(gaps), **serve_mamba_moe.state_check(state_gaps))
    assert [(len(h), len(w)) for h, w in state_gaps] == [(3, 3), (3, 3)]
    assert any(got[k] > cfg["limits"][k] for k in got), got
    if judge == "bf16_state":
        assert got["ssm_state_gap"] > 10 * cfg["limits"]["ssm_state_gap"]


def test_no_state_read_is_under_no_limit():
    got = serve_mamba_moe.state_check([])["ssm_state_gap"]
    assert not got <= 1.0
    assert not serve_mamba_moe.state_check([])["ssm_state_layer_gap"] <= 1.0
    # the first layer's gaps, the widest over the requests, by either norm
    assert serve_mamba_moe.state_check(
        [([0.1, 0.9], [0.05, 0.4]), ([0.2, 0.3], [0.07, 0.1])]) \
        == {"ssm_state_gap": 0.2, "ssm_state_layer_gap": 0.07}


def test_reference_passes_are_a_few_lengths():
    """The longest request's length halved while the half holds the
    request and the shortest pass: three shapes at the cell's size."""
    tr = traffic.load(harness.Bench(ROOT).find("traffic", "chat"))
    got = {n: serve_mamba_moe.pad_to(tr, n)
           for n in (63, 1024, 1025, 2048, 2049, 4095)}
    assert got == {63: 1024, 1024: 1024, 1025: 2048, 2048: 2048,
                   2049: 4096, 4095: 4096}
    assert serve_mamba_moe.pad_to(tr) == 4096


def test_attention_read_work_by_hand():
    """One request of a 600-token prompt (windows of 256: the dense one,
    then [256, 512) and [512, 600)) and 3 tokens served, admitted at 0,
    first token at 2, last at 4. Decode tokens 1 and 2 come at 3 and 4."""
    m = dict(NANO)
    reqs = [(600, 3, 0.0, 2.0, 4.0)]
    kv = 6 * 2 * 2 * 128 * 2              # layers x (K, V) x width x bytes
    per_key = 6 * 4 * 32 * 128
    # window 1 at t = 1, window 2 at t = 2, decode token 1 at t = 3
    flops, byts = work_nemotron.attn_read_interval_work(
        m, reqs, 0.5, 3.5, 256)
    chunk_keys = sum(t + 1 for t in range(256, 600))
    assert byts == kv * (512 + 600 + 601)
    assert flops == per_key * (chunk_keys + 601)
    # nothing of it outside the request's life
    assert work_nemotron.attn_read_interval_work(m, reqs, 5, 9, 256) \
        == (0.0, 0.0)
