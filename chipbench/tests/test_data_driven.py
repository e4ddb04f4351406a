"""Traffic from the seed, work counted by hand, and files found by name."""
import json
import os
import shutil

import numpy as np
import pytest

from chipbench import harness, traffic, work

ROOT = harness.os.path.dirname(harness.HERE)
DECODE = {"kind": "closed_loop", "callers": 4, "lead_in_s": 0, "pool": 64,
          "prompt": {"dist": "uniform", "min": 16, "max": 128},
          "output": {"dist": "lognormal", "median": 256, "sigma": 0.6,
                     "min": 64, "max": 768},
          "check_requests": 2}


def take(spec, seed, n):
    it = traffic.requests(spec, seed, vocab=1000)
    return [next(it) for _ in range(n)]


def test_same_seed_same_requests_other_seed_other_ids_same_schedule():
    a, b, c = take(DECODE, 7, 80), take(DECODE, 7, 80), take(DECODE, 2**31 + 5, 80)
    assert all(x[0] == y[0] and x[2] == y[2] and np.array_equal(x[1], y[1])
               for x, y in zip(a, b))
    # another seed: the same schedule of lengths, other token ids
    assert [(x[1].size, x[2]) for x in a] == [(x[1].size, x[2]) for x in c]
    assert not any(np.array_equal(x[1], y[1]) for x, y in zip(a, c))
    # one pass over the pool holds every pair of lengths once; the next
    # pass comes in another order
    sizes = lambda rs: sorted((r[1].size, r[2]) for r in rs[:64])
    assert sizes(a) == sorted(traffic.length_pool(DECODE))
    two = take(DECODE, 7, 128)
    assert [x[2] for x in two[:64]] != [x[2] for x in two[64:]]
    assert all(16 <= p <= 128 and 64 <= n <= 768 for p, n in sizes(a))
    assert all(1 <= int(r[1].min()) and int(r[1].max()) < 1000 for r in a)


def test_host_batches_from_seed():
    spec = {"kind": "fed_steps", "batch": 4, "pool_batches": 2}
    m = {"input_hw": 8, "in_channels": 3, "classes": 10}
    a, b = traffic.host_batches(spec, 3, m), traffic.host_batches(spec, 3, m)
    c = traffic.host_batches(spec, 4, m)
    assert np.array_equal(a[0][0], b[0][0]) and np.array_equal(a[1][1], b[1][1])
    assert not np.array_equal(a[0][0], c[0][0])
    assert not np.array_equal(a[0][0][0], a[0][0][1])      # rows differ
    assert a[0][0].dtype == np.float32 and a[0][1].dtype == np.int32


GPT = {"vocab": 50257, "embed": 2048, "layers": 24, "heads": 16,
       "head_dim": 128, "mlp_hidden": 8192, "max_len": 2048}
R50 = {"blocks": [3, 4, 6, 3], "channels": [256, 512, 1024, 2048],
       "stem_channels": 64, "in_channels": 3, "input_hw": 224,
       "classes": 1000}


def test_decoder_work_against_hand_counts():
    # per layer: 4 * 2048^2 + 2 * 2048 * 8192 = 50 331 648 weights
    assert work.decoder_matmul_params(GPT) == 24 * 50_331_648
    # one decode step at cache length 300 reads 301 positions:
    # 2 * weights + 24 layers * 4 * 301 * 2048 (QK^T and PV)
    assert work.decoder_position_flops(GPT, 301) == \
        2 * 24 * 50_331_648 + 24 * 4 * 301 * 2048
    # a request of 1 prompt token and 2 served tokens feeds 2 positions
    assert work.decoder_request_flops(GPT, 1, 2) == \
        work.decoder_position_flops(GPT, 1) \
        + work.decoder_position_flops(GPT, 2) \
        + 2 * 2 * 50257 * 2048
    # paged attention of a 10-token prompt (inside one window) and 3
    # served tokens: decode steps at lengths 10 and 11 read 11 + 12
    # positions; K and V in bf16
    flops, byts = work.paged_attention_request_work(GPT, 10, 3, 128)
    assert flops == 24 * 4 * 2048 * 23
    assert byts == 24 * 2 * 23 * 2048 * 2
    # a 200-token prompt: its second chunk (72 positions at offset 128)
    f2, b2 = work.paged_attention_request_work(GPT, 200, 1, 128)
    assert f2 == 24 * 4 * 2048 * (72 * 128 + 72 * 73 // 2)
    assert b2 == 24 * 2 * 200 * 2048 * 2


def test_resnet50_work_against_hand_counts():
    # the literature's 3.86 GMAC for v1 with the stride on the first 1x1
    macs = work.resnet_forward_flops(R50) / 2
    assert abs(macs - 3.86e9) / 3.86e9 < 0.005
    assert work.resnet_train_flops_per_image(R50) == 3 * 2 * macs
    stem = work.resnet_layers(R50)[0]
    assert stem == ("stem", 112, 112, 3, 64, 7)
    # stem BN alone: 112*112*64 elements read and written in bf16
    one = dict(R50, blocks=[], channels=[])
    assert work.resnet_bn_apply_bytes_per_image(one) == 2 * 112 * 112 * 64 * 2


def test_list_finds_files_dropped_in(tmp_path):
    """A cell, a configuration of an existing path, a traffic mix and a
    metric of an existing reader are new files plus new entries: nothing
    that is there is edited."""
    root = tmp_path
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests",
                                                  "testdata"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cfg = json.load(open(root / "chipbench/configs/cgpt13b_serve.json"))
    cfg["engine"]["max_slots"] = 8
    json.dump(cfg, open(root / "chipbench/configs/cgpt13b_serve8.json", "w"))
    mix = dict(DECODE, callers=12)
    json.dump(mix, open(root / "chipbench/traffic/decode12.json", "w"))
    json.dump({"reader": "xplane_module_ms",
               "params": {"pattern": "^jit_copy"}},
              open(root / "chipbench/layer_metrics/copy_prog_ms.serve.json",
                   "w"))
    spec["configs"].append({"name": "cgpt13b_serve8", "source": "x",
                            "file": "chipbench/configs/cgpt13b_serve8.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "cgpt13b_serve8.decode12",
                              "config": "cgpt13b_serve8",
                              "traffic": "decode12", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "copy_prog_ms.serve", "unit": "ms",
                              "better": "lower", "source": "device_trace",
                              "layer": "compiled programs",
                              "moves": "out_tok_s",
                              "workloads": ["cgpt13b_serve8.decode12"]})
    json.dump(spec, open(root / "BENCHMARK.json", "w"))
    found = harness.Bench(str(root)).listing()
    cell = next(c for c in found["cells"]
                if c["name"] == "cgpt13b_serve8.decode12")
    assert cell["traffic"].endswith("traffic/decode12.json")
    assert cell["per_layer"] == ["copy_prog_ms.serve"]
    assert "setup_s" in cell["end_to_end"]
    assert found["configs"]["cgpt13b_serve8"].endswith("cgpt13b_serve8.json")
    assert "decode12" in found["traffic"]
    assert "copy_prog_ms.serve" in found["layer_metrics"]
    assert {"serve_engine", "train_fused"} <= set(found["paths"])
    assert "xplane_module_ms" in found["readers"]


def test_every_metric_of_the_benchmark_has_its_file_and_reader():
    bench = harness.Bench(ROOT)
    for mt in bench.spec["per_layer"]:
        how = json.load(open(bench.find("layer_metrics", mt["name"])))
        assert how["reader"] in harness._modules("readers"), mt["name"]
    for c in bench.spec["workloads"]:
        assert bench.config(c["config"])["path"] in harness._modules("paths")
        traffic.load(bench.find("traffic", c["traffic"]))


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        harness.load_peaks("TPU v9 imaginary")
    assert harness.load_peaks("TPU v5 lite")["flops_per_s"] == 197e12
