"""The `serve_looped` path, its reference, weights, work functions and
metric files: found by name with no edit to `harness.py`, counted by hand,
and rehearsed on the CPU at the tiny preset."""
import json
import os
import subprocess
import sys

from chipbench import harness, weights_ouro, work_ouro

ROOT = harness.os.path.dirname(harness.HERE)
OURO = {"vocab": 49152, "embed": 2048, "layers": 48, "heads": 16,
        "head_dim": 128, "mlp_hidden": 5632, "ut_steps": 4,
        "rope_theta": 1000000.0, "norm_eps": 1e-6,
        "early_exit_threshold": 1.0, "max_len": 512, "dtype": "bfloat16"}


def test_new_files_are_found_by_name():
    bench = harness.Bench(ROOT)
    found = bench.listing()
    assert "serve_looped" in found["paths"]
    assert {"xplane_kernel_roofline", "xplane_scope_ms"} <= set(
        found["readers"])
    assert "math" in found["traffic"]
    assert {"loop_read_roofline.serve", "loop_pass_ms.serve"} <= set(
        found["layer_metrics"])
    cell = next(c for c in found["cells"]
                if c["name"] == "ouro26b_serve.math")
    assert cell["config"] == "ouro26b_serve"
    assert cell["traffic"].endswith("traffic/math.json")
    assert cell["end_to_end"] == ["out_tok_s", "tok_lat_p95_ms", "setup_s"]
    assert {"loop_read_roofline.serve", "loop_pass_ms.serve", "mfu.serve",
            "decode_prog_ms.serve", "prefill_prog_ms.serve",
            "cache_live_share.serve", "sched_occupancy.serve",
            "device_idle.serve", "wave_host_ms.serve", "wave_pack_ms.serve",
            "wave_turnover_ms.serve", "scope_mixer_ms.serve",
            "scope_ffn_ms.serve", "scope_head_ms.serve",
            "scope_none_ms.serve", "queue_wait_ms.serve"} \
        == set(cell["per_layer"])
    # the two metrics this configuration brought are no other cell's
    for other in found["cells"]:
        if other is not cell:
            assert not {"loop_read_roofline.serve", "loop_pass_ms.serve"} \
                & set(other["per_layer"]), other["name"]
    assert bench.cell("ouro26b_serve.math")["chips"] == 1
    cfg = bench.config("ouro26b_serve")
    assert cfg["path"] == "serve_looped" and cfg["reduced"] == []
    assert {k: cfg["model"][k] for k in OURO} == OURO
    # the catalog's numbers sit at the top level under their own keys
    for key, value in cfg["published"].items():
        assert cfg[key] == value, key
    assert cfg["hidden_size"] == cfg["model"]["embed"]
    assert cfg["total_ut_steps"] == cfg["model"]["ut_steps"]
    assert cfg["num_key_value_heads"] == cfg["model"]["heads"]
    assert set(cfg["limits"]) == {
        "served_logit_gap_p99", "served_logit_gap", "requests_cut_short",
        "retraces_in_window", "kernel_fallbacks"}
    # every engine option is stated: no tuned profile, no environment
    assert set(cfg["engine"]) >= {
        "max_slots", "prefix_cache_slots", "prefill_window", "prefill_lanes",
        "prefill_budget", "decode_steps", "draft_tokens", "kv_dtype"}
    with open(bench.find("layer_metrics", "loop_pass_ms.serve")) as f:
        how = json.load(f)
    import re
    scope = re.compile(how["params"]["scope"])
    assert all(scope.search(s) for s in (
        "layer0/attn", "layer47/mlp", "head/loop_norm"))
    assert not any(scope.search(s) for s in ("head", "embed", "sampler", ""))


def test_parameters_and_work_against_hand_counts():
    m = OURO
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632
    assert work_ouro.matmul_params(m) == 48 * layer
    assert work_ouro.param_count(m) == 48 * (layer + 4 * 2048) \
        + 2 * 49152 * 2048 + 4097 == 2_667_974_657
    assert work_ouro.planes(m) == 192
    assert work_ouro.position_cache_bytes(m) == 192 * 2 * 4096 == 1_572_864
    # one served token after a 1-token prompt: position 0 four times
    # through the stack, one key in each of the 192 planes, one head
    assert work_ouro.request_flops(m, 1, 1) == \
        4 * 2 * 48 * layer + 192 * 4 * 2048 + 2 * 49152 * 2048
    # three positions: 1 + 2 + 3 keys a plane
    assert work_ouro.request_flops(m, 2, 2) == \
        4 * 3 * 2 * 48 * layer + 192 * 4 * 2048 * 6 + 2 * 2 * 49152 * 2048
    # the decode read of a 10-token prompt and 3 served tokens, first token
    # at t = 1, last at t = 3: reads of 11 and 12 positions at t = 2, 3
    # (the first token is the dense prefill's), 192 planes of K and V
    req = [(10, 3, 1.0, 3.0)]
    assert work_ouro.loop_read_interval_work(m, req, 0.0, 9.0) == \
        (192 * 4 * 2048 * 23, 23 * 1_572_864)
    for (t_a, t_b), seen in (((0.0, 2.0), 0), ((2.0, 3.0), 11),
                             ((1.5, 3.5), 23), ((3.5, 9.0), 0)):
        assert work_ouro.loop_read_interval_work(m, req, t_a, t_b) == \
            (192 * 4 * 2048 * seen, seen * 1_572_864)
    assert work_ouro.loop_read_interval_work(m, [], 0.0, 1.0) == (0.0, 0.0)
    assert work_ouro.loop_read_interval_work(m, [(5, 1, 0.0, 0.0)], 0.0, 1.0) \
        == (0.0, 0.0)


def test_drawn_weights_are_what_the_configuration_states():
    """The leaves and their kinds are the program's table; the values are
    held here to `assumed.weights`, so that a change of the program's draw
    cannot move the benchmark's weights unseen."""
    import numpy as np
    cfg = harness.Bench(ROOT).config("ouro26b_serve")
    stated = {k: cfg["model"][k] for k in
              ("init_std", "emb_std", "q_std", "k_std")}
    m = dict(OURO, vocab=512, embed=256, layers=3, mlp_hidden=512, heads=4,
             head_dim=64, max_len=64, dtype="float32", **stated)
    w = {k: np.asarray(v) for k, v in
         weights_ouro.ouro_params(m, 2**31 + 5).items()}
    assert set(w) == {"emb", "head", "n1", "n2", "n3", "n4", "nf", "wq", "wk",
                      "wv", "wo", "mlp_gate_up", "mlp_down", "gate_w",
                      "gate_b"}
    for name, key in (("emb", "emb_std"), ("head", "init_std"),
                      ("wq", "q_std"), ("wk", "k_std"), ("wv", "init_std"),
                      ("wo", "init_std"), ("mlp_gate_up", "init_std"),
                      ("mlp_down", "init_std")):
        assert abs(w[name].std() - stated[key]) < 0.06 * stated[key], name
    assert w["wq"].shape == (3, 256, 256) and w["head"].shape == (256, 512)
    # a layer of a stacked leaf is its own draw
    assert np.abs(w["wq"][0] - w["wq"][1]).max() > 0
    for name in ("n1", "n2", "n3", "n4", "nf"):
        assert (w[name] == 1).all()
    assert (w["gate_b"] == 0).all()
    again = weights_ouro.ouro_params(m, 2**31 + 5)
    assert all((np.asarray(again[k]) == w[k]).all() for k in w)
    other = weights_ouro.ouro_params(m, 2**31 + 6)
    assert np.abs(np.asarray(other["wq"]) - w["wq"]).max() > 0


def test_rehearsal_of_the_tiny_looped_cell_prints_a_correct_line():
    done = subprocess.run(
        [sys.executable, "chipbench/tests/rehearse.py", "--bench",
         "chipbench/tests/tiny/BENCHMARK_looped.json", "--workload",
         "looped_tiny_serve.math_tiny", "--seed", str(2**31 + 11),
         "--seconds", "2", "--trace", "1"],
        cwd=ROOT, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["compared"]["kernel_fallbacks"]["value"] == 0
    assert 0 < line["metrics"]["cache_live_share.serve"]["value"] <= 100
    assert line["metrics"]["mfu.serve"]["value"] > 0
    notes = line["notes"]
    # whole passes of the stack, three a position (the engine's own token
    # counts are taken at dispatch and the model's where a wave is read, so
    # over a short interval the two differ by a wave or two)
    assert notes["loop_stack_passes"] > 0 \
        and notes["loop_stack_passes"] % 3 == 0
    assert notes["loop_plane_positions_read"] > 0
    assert notes["loop_read_bytes"] > 0
    # a CPU trace has no device plane: the two device metrics are left out
    assert "loop_read_roofline.serve" not in line["metrics"]
    assert "loop_pass_ms.serve" not in line["metrics"]
