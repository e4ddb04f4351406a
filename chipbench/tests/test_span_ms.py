"""The span reader's arithmetic on hand-made spans, what each metric's
patterns pick, and the two tiny cells rehearsed with the four metrics."""
import json
import os
import re
import subprocess
import sys

import pytest

from chipbench import harness
from chipbench.readers import span_ms

ROOT = os.path.dirname(harness.HERE)
METRICS = ("wave_host_ms.serve", "wave_pack_ms.serve",
           "wave_turnover_ms.serve", "step_host_ms.train")
MS = 1000.0     # spans are in microseconds


def how(metric):
    with open(os.path.join(harness.HERE, "layer_metrics",
                           metric + ".json")) as f:
        return json.load(f)


# one thread's wave, hand-made: (name, thread, start_us, dur_us)
WAVE = [
    ("serve.admit", 1, 0 * MS, 1 * MS),
    ("serve.prefill_batch", 1, 1 * MS, 10 * MS),
    ("serve.prefill_batch.pack", 1, 1 * MS, 2 * MS),
    ("serve.prefill_batch.dispatch", 1, 3 * MS, 1 * MS),
    ("serve.prefill_batch.readback", 1, 4 * MS, 6 * MS),
    ("serve.decode_batch", 1, 11 * MS, 60 * MS),
    ("serve.decode_batch.pack", 1, 11 * MS, 3 * MS),
    ("serve.decode_batch.dispatch", 1, 14 * MS, 2 * MS),
    ("serve.decode_batch.readback", 1, 16 * MS, 50 * MS),
    ("serve.decode_batch.emit", 1, 66 * MS, 4 * MS),
    ("serve.retire", 1, 71 * MS, 5 * MS),
    ("serve.copy.dispatch", 1, 72 * MS, 3 * MS),    # inside the retire
    ("serve.request", 2, 0 * MS, 70 * MS),          # another thread
]


def test_self_time_counts_a_nested_span_once():
    assert span_ms.self_time_us([(0, 10), (2, 5), (5, 8)]) == 10
    assert span_ms.self_time_us([(0, 4), (6, 9)]) == 7
    assert span_ms.self_time_us([(0, 4), (3, 9)]) == 9
    assert span_ms.self_time_us([]) == 0
    both = {"sum": r"^serve\.(retire|copy\.dispatch)$",
            "per": r"^serve\.decode_batch$"}
    # the retire's 5 ms hold the copy's 3: 5, not 8
    assert span_ms.reduce(WAVE, both, 1.0) == 5.0
    only = dict(both, sum=r"^serve\.copy\.dispatch$")
    assert span_ms.reduce(WAVE, only, 1.0) == 3.0


def test_threads_are_summed_apart():
    two = WAVE + [("serve.retire", 3, 71 * MS, 5 * MS)]
    p = {"sum": r"^serve\.retire$", "per": r"^serve\.decode_batch$"}
    assert span_ms.reduce(two, p, 1.0) == 10.0


def test_interval_keeps_spans_by_their_start():
    waves = []
    for i in range(10):         # a wave every 100 ms, 10 ms of pack each
        waves.append(("serve.decode_batch", 1, i * 100 * MS, 90 * MS))
        waves.append(("serve.decode_batch.pack", 1, i * 100 * MS, 10 * MS))
    p = {"sum": r"\.pack$", "per": r"^serve\.decode_batch$",
         "skip_head_s": 0.25}
    # kept: the waves that start in [0.25 s, 0.25 + 0.4 s]: 300 .. 600 ms
    assert span_ms.reduce(waves, p, 0.4) == 10.0
    waves[7] = ("serve.decode_batch.pack", 1, 300 * MS, 50 * MS)
    assert span_ms.reduce(waves, p, 0.4) == (50 + 10 + 10 + 10) / 4
    # a window that holds no `per`: nothing to read, not 0
    assert span_ms.reduce(waves, p, 0.01) is None


def test_none_when_per_is_absent_or_nothing_was_recorded():
    p = {"sum": r"^train\.step\.dispatch$", "per": r"^train\.step$"}
    assert span_ms.reduce(WAVE, p, 1.0) is None
    assert span_ms.reduce([], p, 1.0) is None


def test_a_program_without_the_accessor_reads_as_none(monkeypatch):
    from incubator_mxnet_tpu import profiler
    monkeypatch.delattr(profiler, "events")
    assert span_ms.spans() == []
    assert span_ms.read(how("step_host_ms.train")["params"],
                        {"window_s": 3.0}) is None


PICKS = {
    "wave_host_ms.serve": {
        "serve.admit", "serve.prefill_batch.pack",
        "serve.prefill_batch.dispatch", "serve.decode_batch.pack",
        "serve.decode_batch.dispatch", "serve.decode_batch.emit",
        "serve.retire", "serve.copy.dispatch"},
    "wave_pack_ms.serve": {"serve.decode_batch.pack",
                           "serve.decode_batch.dispatch"},
    "wave_turnover_ms.serve": {
        "serve.retire", "serve.admit", "serve.prefill_batch.pack",
        "serve.prefill_batch.dispatch", "serve.copy.dispatch"},
    "step_host_ms.train": {"train.step.dispatch"},
}
# hand sums over WAVE, ms a wave: the copy lies inside the retire
HAND = {"wave_host_ms.serve": 1 + 2 + 1 + 3 + 2 + 4 + 5,
        "wave_pack_ms.serve": 3 + 2,
        "wave_turnover_ms.serve": 1 + 2 + 1 + 5,
        "step_host_ms.train": None}


@pytest.mark.parametrize("metric", METRICS)
def test_metric_file_picks_the_spans_of_its_row(metric):
    params = how(metric)["params"]
    assert how(metric)["reader"] == "span_ms"
    assert params["skip_head_s"] == 0.5
    names = {s[0] for s in WAVE} | {"serve.idle", "train.step",
                                    "train.step.dispatch", "io.feed"}
    assert {n for n in names if re.search(params["sum"], n)} \
        == PICKS[metric]
    per = {n for n in names if re.search(params["per"], n)}
    assert per == ({"train.step"} if metric.endswith(".train")
                   else {"serve.decode_batch"})
    assert span_ms.reduce(WAVE, dict(params, skip_head_s=0.0), 1.0) \
        == HAND[metric]


def test_the_benchmark_lists_the_four_on_their_cells():
    listing = harness.Bench(ROOT).listing()
    cells = {c["name"]: c["per_layer"] for c in listing["cells"]}
    assert set(METRICS[:3]) <= set(cells["cgpt13b_serve.decode"])
    assert METRICS[3] in cells["resnet50_train.feed"]
    assert not set(METRICS[:3]) & set(cells["resnet50_train.feed"])
    assert "span_ms" in listing["readers"]


CELLS = {"decoder_tiny_serve.decode_tiny": METRICS[:3],
         "resnet_tiny_train.feed_tiny": METRICS[3:]}


@pytest.fixture(scope="module")
def tiny_bench(tmp_path_factory):
    """The tiny presets' benchmark with the four metrics on its cells;
    their traced interval settles in 0.2 s, not the cells' 0.5."""
    root = tmp_path_factory.mktemp("tiny_spans")
    with open(os.path.join(harness.HERE, "tests", "tiny",
                           "BENCHMARK.json")) as f:
        spec = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = {mt["name"]: mt for mt in json.load(f)["per_layer"]}
    os.makedirs(root / "layer_metrics")
    for cell, metrics in CELLS.items():
        for metric in metrics:
            spec["per_layer"].append(dict(entries[metric], workloads=[cell]))
            data = how(metric)
            data["params"]["skip_head_s"] = 0.2
            with open(root / "layer_metrics" / (metric + ".json"),
                      "w") as f:
                json.dump(data, f)
    spec["paths"].insert(0, str(root))
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(spec, f)
    return str(root / "BENCHMARK.json")


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_rehearsal_prints_the_new_metrics(cell, tiny_bench):
    out = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "tests", "rehearse.py"),
         "--workload", cell, "--seed", "5", "--seconds", "4", "--trace", "1",
         "--bench", tiny_bench],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"]
    for metric in CELLS[cell]:
        got = line["metrics"][metric]
        assert got["unit"] == "ms" and got["value"] > 0, metric
    if cell.startswith("decoder"):
        m = line["metrics"]
        assert m["wave_pack_ms.serve"]["value"] \
            < m["wave_host_ms.serve"]["value"]
        assert m["wave_turnover_ms.serve"]["value"] \
            < m["wave_host_ms.serve"]["value"]
