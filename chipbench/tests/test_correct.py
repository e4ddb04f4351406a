"""`correct` at a tiny size on the CPU: a sound run reads true; the timed
path broken underneath reads false (once per fault the cells can have); the
control — the reference in the precision below the configuration's, put in
the program's place — fails a limit. The look for a chip is skipped and
the rest of a run is driven as the command drives it."""
import time

import numpy as np
import pytest

from chipbench import checks, harness, tracing, traffic
from chipbench.paths import serve_engine, train_fused

ROOT = harness.os.path.dirname(harness.HERE)
TINY = "chipbench/tests/tiny/BENCHMARK.json"
TRAIN, SERVE = "resnet_tiny_train.feed_tiny", "decoder_tiny_serve.decode_tiny"


@pytest.fixture(scope="module", autouse=True)
def interpret_kernels():
    from incubator_mxnet_tpu.ops import fused
    fused.set_interpret(True)
    yield
    fused.set_interpret(None)


def run(cell, seed=11, seconds=1.0):
    return harness.run_cell(harness.Bench(ROOT, TINY), cell, seed, seconds,
                            False, time.perf_counter(), check_device=False)


def failed_limits(line):
    return [k for k, c in line["compared"].items()
            if not c["value"] <= c["limit"]]


# -- serve -------------------------------------------------------------------
def test_serve_sound_run_is_correct():
    line = run(SERVE)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"]["out_tok_s"]["value"] > 0
    assert list(line)[-1] == "compared"


def test_serve_token_altered_where_it_is_produced(monkeypatch):
    from concurrent.futures import Future
    from incubator_mxnet_tpu import serve
    real = serve.ContinuousEngine.submit

    def submit(self, prompt, max_new_tokens=16, **kw):
        inner, outer = real(self, prompt, max_new_tokens, **kw), Future()

        def relay(f):
            if f.exception() is not None:
                outer.set_exception(f.exception())
                return
            tokens = np.array(f.result())
            k = tokens.size // 2
            tokens[k] = (tokens[k] + 1) % 512 or 1
            outer.set_result(tokens)

        inner.add_done_callback(relay)
        return outer

    monkeypatch.setattr(serve.ContinuousEngine, "submit", submit)
    line = run(SERVE)
    assert not line["correct"]
    assert "served_logit_gap" in failed_limits(line)


def test_serve_control_int8_reference_fails_the_limit():
    bench = harness.Bench(ROOT, TINY)
    cfg = bench.config("decoder_tiny_serve")
    tr = traffic.load(bench.find("traffic", "decode_tiny"))
    from chipbench import weights
    for seed in (1, 2, 3):
        params = weights.decoder_params(cfg["model"], seed)
        source = traffic.requests(tr, seed, cfg["model"]["vocab"])
        rng = np.random.default_rng(seed)
        # long streams, so that some positions are near-ties
        reqs = [{"prompt": p[:8], "tokens": rng.integers(
                    1, cfg["model"]["vocab"], size=100).astype(np.int32)}
                for _, p, _n in (next(source) for _ in range(6))]
        gaps = serve_engine.served_gaps(cfg, tr, params, reqs,
                                        precision="int8")
        got = checks.served(gaps)       # the control fails one number
        assert any(got[k] > cfg["limits"][k] for k in got), got


# -- train -------------------------------------------------------------------
def test_train_sound_run_is_correct():
    line = run(TRAIN)
    assert line["correct"], line["compared"]
    assert line["metrics"]["train_step_ms"]["value"] > 0


def test_train_step_that_returns_its_state_unchanged(monkeypatch):
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon.contrib import FusedTrainStep
    real = FusedTrainStep.__call__

    def call(self, *inputs):
        before = [p.data().asnumpy() for p in self._params]
        out = real(self, *inputs)
        for p, old in zip(self._params, before):
            p.set_data(mx.np.array(old))
        return out

    monkeypatch.setattr(FusedTrainStep, "__call__", call)
    line = run(TRAIN)
    assert not line["correct"]
    assert "change_worst_leaf_gap" in failed_limits(line)


def test_train_half_of_the_batch_left_out(monkeypatch):
    from incubator_mxnet_tpu.gluon.contrib import FusedTrainStep
    real = FusedTrainStep.__call__

    def call(self, x, y):
        half = x.shape[0] // 2
        self._opt.rescale_grad = 1.0 / half     # the mean over the rest
        return real(self, x[:half], y[:half])

    monkeypatch.setattr(FusedTrainStep, "__call__", call)
    line = run(TRAIN)
    assert not line["correct"], line["compared"]


def test_train_control_fp8_reference_fails_a_limit():
    bench = harness.Bench(ROOT, TINY)
    cfg = bench.config("resnet_tiny_train")
    tr = traffic.load(bench.find("traffic", "feed_tiny"))
    from chipbench import weights
    names = sorted(n for n, (_, k) in
                   weights.resnet_shapes(cfg["model"]).items()
                   if weights.trainable(k))
    for seed in (1, 2, 3):
        pool = traffic.host_batches(tr, seed, cfg["model"])
        ref = train_fused.reference_steps(cfg, seed, pool, names)
        low = train_fused.reference_steps(cfg, seed, pool, names,
                                          precision="fp8")
        got = checks.training(*low[:3], *ref[:3])
        assert any(got[k] > cfg["limits"][k] for k in got), got


def test_a_request_counts_by_the_share_of_its_life_inside_the_window():
    share = lambda due, done: serve_engine.window_share(
        {"t_due": due, "t_done": done}, 10.0, 50.0)
    assert share(12.0, 20.0) == 1.0             # all inside
    assert share(2.0, 8.0) == 0.0               # over before it opened
    assert share(52.0, 60.0) == 0.0             # due after it closed
    assert share(6.0, 14.0) == 0.5              # straddles the opening
    assert share(44.0, 52.0) == 0.75            # straddles the close
    assert share(0.0, 100.0) == 0.4             # spans the whole window


def test_tracer_marks_and_interval():
    t = tracing.Tracer(False, None)
    assert not t.due(100.0) and not t.traced()
