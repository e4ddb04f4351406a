"""The scope reader's arithmetic on hand-made lines (a `while` that holds
its body's operations, two modules with one instruction name, a table that
lacks 3% of the time), the mean of the request-scale spans, each new
metric file's patterns against the scope names the four decoders and the
fused step pin, and the join with a real program's own table."""
import json
import os
import re

import pytest

from chipbench import harness, xplane
from chipbench.readers import span_mean_ms, xplane_scope_ms as reader

US = 1000.0     # the trace's times are nanoseconds

SERVE_PARTITION = ("scope_mixer_ms.serve", "scope_ffn_ms.serve",
                   "scope_head_ms.serve", "scope_none_ms.serve")
TRAIN = ("scope_fwd_ms.train", "scope_bwd_ms.train",
         "scope_update_ms.train", "scope_stage1_ms.train")


def how(metric):
    with open(os.path.join(harness.HERE, "layer_metrics",
                           metric + ".json")) as f:
        return json.load(f)


def op(name, kind="fusion"):
    return f"%{name} = f32[8,8]{{1,0}} {kind}(f32[8,8]{{1,0}} %p), x=1"


class Plane:
    """What `xplane.Trace` hands a reader, from hand-made events:
    ops and modules as [(name, start_us, dur_us)]."""

    def __init__(self, ops, modules):
        def line(events):
            return xplane.Line([e[0] for e in events],
                               [e[1] * US for e in events],
                               [e[2] * US for e in events])
        self.devices = {"/device:TPU:0": {"XLA Ops": line(ops),
                                          "XLA Modules": line(modules)}}


# two executions of `jit_decode`, one of `jit_prefill` between them. A
# wave: a copy of 10 outside the loop, a `while` of 80 that holds two
# micro-steps of (attn 20, mlp 15) and 10 of its own, a head of 5
def wave(t):
    return [(op("copy.1", "copy"), t, 10),
            (op("while.9", "while"), t + 10, 80),
            (op("fusion.17"), t + 10, 20), (op("fusion.18"), t + 30, 15),
            (op("fusion.17"), t + 50, 20), (op("fusion.18"), t + 70, 15),
            (op("fusion.3"), t + 90, 5)]


OPS = wave(0) + [(op("fusion.17"), 100, 40), (op("fusion.5"), 140, 10)] \
    + wave(200)
MODULES = [("jit_decode(11)", 0, 95), ("jit_prefill(22)", 100, 50),
           ("jit_decode(11)", 200, 95)]
TABLES = {
    "jit_decode": {"copy.1": ("", ""), "while.9": ("", ""),
                   "fusion.17": ("layer0/attn/paged_attention_fwd", ""),
                   "fusion.18": ("layer0/mlp", ""),
                   "fusion.3": ("head", "")},
    # the same instruction name, another program, another scope
    "jit_prefill": {"fusion.17": ("layer0/mlp", ""),
                    "fusion.5": ("head", "")},
}


def read(scope, module=r"^jit_decode\(", tables=TABLES, ops=OPS, **more):
    return reader.reduce(reader.joined(Plane(ops, MODULES)),
                         dict(module=module, scope=scope, **more), tables)


def test_self_time_leaves_out_what_is_nested():
    import numpy as np
    # [0,100) holds [0,60), which holds [10,30) and [40,50)
    starts = np.array([0.0, 0, 10, 40])
    durs = np.array([100.0, 60, 20, 10])
    assert reader.self_times(starts, durs).tolist() == [40, 30, 20, 10]
    # side by side: nothing nested
    assert reader.self_times(np.array([0.0, 5]), np.array([5.0, 5])) \
        .tolist() == [5, 5]
    # one that leaves its parent takes only what lies inside it
    assert reader.self_times(np.array([0.0, 50]), np.array([100.0, 60])) \
        .tolist() == [50, 60]


def test_a_while_counts_its_own_time_and_its_body_once():
    # per execution: attn 2 x 20, mlp 2 x 15, head 5, none = the copy's 10
    # and the while's own 80 - 70
    assert read(r"^layer\d+/attn(/|$)") == pytest.approx(0.040)
    assert read(r"^layer\d+/mlp(/|$)") == pytest.approx(0.030)
    assert read(r"^head$") == pytest.approx(0.005)
    assert read(r"^$") == pytest.approx(0.020)
    # the four are the whole program: its module time
    assert 0.040 + 0.030 + 0.005 + 0.020 == pytest.approx(0.095)


def test_an_operation_belongs_to_the_module_that_holds_its_start():
    # `fusion.17` is attention in one program and the feed-forward in the
    # other: the prefill's 40 count for neither of the decode's metrics
    assert read(r"/mlp", module=r"^jit_prefill\(") == pytest.approx(0.040)
    assert read(r"/attn", module=r"^jit_prefill\(") == 0.0
    both = read(r"/mlp", module=r"^jit_(decode|prefill)\(")
    assert both == pytest.approx((2 * 0.030 + 0.040) / 3)


def test_pass_and_the_read_through_the_context(monkeypatch):
    tables = {"jit_decode": dict(TABLES["jit_decode"],
                                 **{"fusion.18": ("forward/x", "bwd")})}
    assert read(r"^forward", tables=tables, **{"pass": "bwd"}) \
        == pytest.approx(0.030)
    assert read(r"^forward", tables=tables, **{"pass": "fwd"}) == 0.0
    assert read(r"^forward", tables=tables) == pytest.approx(0.030)
    # through `read`: per execution, whatever counters a path hands over
    ctx = {"trace": Plane(OPS, MODULES), "counters": {"decode_steps": 4}}
    monkeypatch.setattr(reader, "tables", lambda pattern: TABLES)
    p = {"module": r"^jit_decode\(", "scope": r"/attn"}
    assert reader.read(p, ctx) == pytest.approx(0.040)
    assert reader.read(p, dict(ctx, trace=None)) is None


def test_none_when_the_table_lacks_more_than_a_fiftieth_of_the_time():
    # 3% of the time under a name the table does not hold: a stale table
    stale = {"jit_decode": {k: v for k, v in TABLES["jit_decode"].items()
                            if k != "fusion.3"}}
    ops = [(n, s, 2.85 if "fusion.3 " in n else d) for n, s, d in OPS]
    assert 2.85 / (95 - 5 + 2.85) > 0.03
    assert read(r"/attn", tables=stale, ops=ops) is None
    # 1%: read, the unknown under no metric
    ops = [(n, s, 0.9 if "fusion.3 " in n else d) for n, s, d in OPS]
    assert read(r"/attn", tables=stale, ops=ops) == pytest.approx(0.040)
    assert read(r"^$", tables=stale, ops=ops) == pytest.approx(0.020)
    # no table at all for the module, no execution, no accessor
    assert read(r"/attn", tables={}) is None
    assert read(r"/attn", module=r"^jit_step\(") is None
    assert reader.read({"module": "x", "scope": "y"}, {"trace": None}) is None


def test_span_mean_reads_the_spans_of_the_requests_that_retire_in_the_interval():
    complete = [("serve.decode_batch", 1, 1_000_000.0, 50.0)]
    ends = [("serve.queue", 1_400_000.0, 4000.0),      # before the interval
            ("serve.queue", 1_600_000.0, 1000.0),
            ("serve.queue", 2_000_000.0, 3000.0),
            ("serve.prefill", 2_000_000.0, 9000.0),
            ("serve.queue", 2_600_000.0, 8000.0)]      # after it
    p = {"name": r"^serve\.queue$", "skip_head_s": 0.5}
    assert span_mean_ms.reduce(complete, ends, p, 1.0) == 2.0
    assert span_mean_ms.reduce(complete, ends, dict(p, name="x"), 1.0) \
        is None
    assert span_mean_ms.reduce([], ends, p, 1.0) is None
    assert span_mean_ms.reduce(complete, [], p, 1.0) is None


# -- the metric files -----------------------------------------------------------
# the scopes the decoders pin (tests/test_spans_hot_path.py,
# tests/test_hybrid_decoder.py, tests/test_sparse_moe_decoder.py,
# tests/test_delta_moe_decoder.py), as the tables print them
MIXER = ["layer0/attn", "layer3/attn/paged_attention_fwd", "layer0/mamba",
         "layer1/swa", "layer5/full", "layer7/cross", "layer6/gmu",
         "layer2/mla", "layer2/indexer", "layer2/select",
         "layer2/sparse_read", "layer1/kda_proj", "layer1/kda_conv",
         "layer1/kda_state", "layer12/mla/paged_attention_fwd"]
FFN = ["layer0/mlp", "layer3/router", "layer3/experts",
       "layer3/shared_expert", "layer23/mlp/x"]
HEAD = ["embed", "head", "sampler", "sampler/sample_tokens.<locals>.draw"]


def picks(metric, path):
    return bool(re.search(how(metric)["params"]["scope"], path))


def test_the_four_serve_metrics_part_every_scope_once():
    for path in MIXER + FFN + HEAD + [""]:
        hit = [m for m in SERVE_PARTITION if picks(m, path)]
        assert len(hit) == 1, (path, hit)
    assert all(picks("scope_mixer_ms.serve", p) for p in MIXER)
    assert all(picks("scope_ffn_ms.serve", p) for p in FFN)
    assert all(picks("scope_head_ms.serve", p) for p in HEAD)
    assert picks("scope_none_ms.serve", "")
    experts = [p for p in FFN if picks("scope_experts_ms.serve", p)]
    assert experts == ["layer3/router", "layer3/experts"]
    # a scope that only looks like one of them is in none
    for path in ("layer0/attnx", "layerx/attn", "embedding", "xlayer0/mlp"):
        assert not any(picks(m, path) for m in SERVE_PARTITION), path
    for m in SERVE_PARTITION + ("scope_experts_ms.serve",):
        assert how(m)["reader"] == "xplane_scope_ms"
        assert re.search(how(m)["params"]["module"], "jit_decode(123)")
        assert not re.search(how(m)["params"]["module"],
                             "jit_decode_x(1)")


def test_the_train_metrics_read_the_fused_step():
    for m in TRAIN:
        p = how(m)["params"]
        assert re.search(p["module"], "jit_step(99)")
    fwd, bwd = how(TRAIN[0])["params"], how(TRAIN[1])["params"]
    assert (fwd["pass"], bwd["pass"]) == ("fwd", "bwd")
    for path in ("forward", "forward/features/4/0/0", "forward/output"):
        assert re.search(fwd["scope"], path) and re.search(bwd["scope"], path)
    assert not re.search(fwd["scope"], "update")
    assert picks("scope_update_ms.train", "update")
    assert "pass" not in how("scope_stage1_ms.train")["params"]
    assert picks("scope_stage1_ms.train", "forward/features/4/2/0")
    assert picks("scope_stage1_ms.train", "forward/features/4")
    assert not picks("scope_stage1_ms.train", "forward/features/40/0")
    assert not picks("scope_stage1_ms.train", "forward/features/5/0")


def test_the_first_residual_stage_is_features_4():
    from incubator_mxnet_tpu.gluon.model_zoo import vision
    net = vision.resnet50_v1(layout="NHWC")
    kinds = [type(b).__name__ for b in net.features._children.values()]
    assert kinds[:4] == ["Conv2D", "BatchNorm", "Activation", "MaxPool2D"]
    assert kinds[4:8] == ["HybridSequential"] * 4
    assert net.features._children["4"]._scope_name == "4"
    assert [len(net.features._children[str(k)]._children)
            for k in (4, 5, 6, 7)] == [3, 4, 6, 3]


def test_every_new_metric_is_in_the_benchmark_with_its_cells():
    bench = harness.Bench(os.path.dirname(harness.HERE))
    by_name = {m["name"]: m for m in bench.spec["per_layer"]}
    serve = [c["name"] for c in bench.spec["workloads"]
             if c["name"] != "resnet50_train.feed"]
    for m in SERVE_PARTITION + ("queue_wait_ms.serve",):
        assert sorted(by_name[m]["workloads"]) == sorted(serve), m
    assert by_name["scope_experts_ms.serve"]["workloads"] == [
        "glm52_serve.longctx", "ling3f_serve.longgen"]
    for m in TRAIN:
        assert by_name[m]["workloads"] == ["resnet50_train.feed"]
    assert by_name["queue_wait_ms.serve"]["moves"] == "tok_lat_p95_ms"
    assert list(by_name)[-10:] == [
        "scope_mixer_ms.serve", "scope_ffn_ms.serve",
        "scope_experts_ms.serve", "scope_head_ms.serve",
        "scope_none_ms.serve", "scope_fwd_ms.train", "scope_bwd_ms.train",
        "scope_update_ms.train", "scope_stage1_ms.train",
        "queue_wait_ms.serve"]


# -- the join with a real program's own table -----------------------------------
def test_a_tiny_engines_decode_program_is_parted_whole():
    """Every instruction of a tiny engine's compiled decode program as one
    event of 1 us (nested in the `while` where the program nests it): the
    four metrics sum to the module's time, through `read` and the
    program's own accessor, after the engine is closed."""
    from incubator_mxnet_tpu import profiler, serve
    from incubator_mxnet_tpu.inspect import hlo
    cfg = serve.DecoderConfig(vocab=64, embed=32, layers=2, heads=4,
                              head_dim=8, max_len=48)
    eng = serve.ContinuousEngine(serve.CachedDecoder(cfg, seed=11),
                                 max_slots=4, prefill_window=16,
                                 decode_steps=2).start()
    try:
        eng.generate([1, 2, 3], 4)
        module = hlo.parse_module(
            eng.lowered_programs()["decode"].compile().as_text())
    finally:
        eng.close()
    del eng
    entry = module.entry
    loop = next(i for i in entry.instructions if i.opcode == "while")
    body = module.computations[
        re.search(r"body=%?([\w.\-]+)", loop.attrs_text).group(1)]
    ops, t = [], 0.0
    for ins in entry.instructions:
        if ins is loop:
            inner = [(op(b.name, b.opcode), t + 1 + k, 1.0)
                     for k, b in enumerate(body.instructions)]
            ops += [(op(ins.name, "while"), t, len(inner) + 2.0)] + inner
            t += len(inner) + 2
        else:
            ops.append((op(ins.name, ins.opcode), t, 1.0))
            t += 1
    plane = Plane(ops, [("jit_decode(7)", 0.0, t)])
    ctx = {"trace": plane, "counters": {}, "window_s": 1.0}
    parts = {m: reader.read(how(m)["params"], ctx)
             for m in SERVE_PARTITION}
    assert None not in parts.values(), parts
    assert sum(parts.values()) == pytest.approx(1e-3 * t)
    assert parts["scope_mixer_ms.serve"] > 0 < parts["scope_ffn_ms.serve"]
    assert parts["scope_head_ms.serve"] > 0
    assert profiler.program_scopes(r"^jit_decode\(")["jit_decode"]
