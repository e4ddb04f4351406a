"""Path `serve_looped`: `serve.ContinuousEngine` over
`models.looped_decoder.LoopedDecoder` (one stack of layers run `ut_steps`
times over the same weights, a key/value plane for every pass) under the
closed loop of callers that `paths/serve_engine.py` drives. The driver
(`drive`), the share of a request inside the window (`window_share`), the
sample that the reference reads (`sample`) and the comparison
(`checks.served`) are that path's, the cache's counters (`cache_counters`)
and the engine that keeps its requests' timelines (`Timed`, `timelines`)
`paths/serve_hybrid.py`'s, and are imported, not copied; the model, its
weights, its plain reference and its work functions are this
configuration's own (`weights_ouro`, `reference/ouro_loop`, `work_ouro`)."""
from __future__ import annotations

import gc
import time

from .. import checks, weights_ouro, work_ouro
from ..memory import peak_bytes
from ..reference import ouro_loop as reference
from .serve_engine import COUNTED, drive, sample, window_share
from .serve_hybrid import Timed, cache_counters, timelines

COUNTER_GROUPS = ("loop",)


def build(cfg, params):
    """The system under test, warmed: (engine, model)."""
    from incubator_mxnet_tpu import serve
    from incubator_mxnet_tpu.models import looped_decoder
    model = looped_decoder.LoopedDecoder(
        weights_ouro.looped_config(cfg["model"]), params=params)
    eng = serve.ContinuousEngine(model, eos_id=None, **cfg["engine"])
    return eng.start(), model


def served_gaps(cfg, tr, params, requests, precision="float32", fault=None,
                exact=None):
    """Per served token of `requests`: how far below the float32
    reference's best logit the token lies. With a lower `precision` or a
    planted `fault` the token judged is the one that forward puts first
    (what a program computing so would have served). `exact` is the
    float32 forward, for a caller that reads several controls."""
    import numpy as np
    m = cfg["model"]
    pad_to = -(-(tr["prompt"]["max"] + tr["output"]["max"]) // 128) * 128
    exact = exact or reference.make_forward(m)
    judge = None if precision == "float32" and fault is None else \
        reference.make_forward(m, precision, fault,
                               edge=cfg["engine"]["prefill_window"])
    out = [reference.served_gaps(exact, params, r["prompt"], r["tokens"],
                                 pad_to, judge=judge) for r in requests]
    return np.concatenate(out) if out else np.zeros((0,), np.float32)


def model_counters(a, b):
    """The model's own counters (`stats()["loop"]`) between two snapshots,
    as `<group>_<field>`; {} where the program's `stats()` has none."""
    return {f"{group}_{field}": b[group][field] - a[group][field]
            for group in COUNTER_GROUPS if group in a and group in b
            for field in b[group]}


def run(ctx):
    import numpy as np
    import jax
    from incubator_mxnet_tpu.ops import fused

    # a program without this decoder (this path's parent) fails here, at
    # once, before any weight is made
    from incubator_mxnet_tpu.models import looped_decoder  # noqa: F401

    cfg, tr, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    m, eng_kw = cfg["model"], cfg["engine"]
    tracer = ctx["tracer"]

    params = weights_ouro.ouro_params(m, seed)
    eng, model = build(cfg, params)
    timed = Timed(eng)
    try:
        d = drive(timed, tr, seed, m["vocab"], ctx["seconds"], tracer)
        retraces = eng.retraces_after_warmup()
        peak = peak_bytes(jax.devices()[0])
    finally:
        eng.close(drain=False, timeout=30.0)
    del eng, model
    gc.collect()
    records, t_open, t_close = d["records"], d["t_open"], d["t_close"]
    setup_s = t_open - ctx["t_process_start"]

    # -- end-to-end metrics (as `serve_engine.run` counts them) --------------
    window_s = t_close - t_open
    ok = [r for r in records if r["tokens"] is not None]
    in_window = [r for r in ok if t_open <= r["t_done"] <= t_close]
    due = [r for r in records if t_open <= r["t_due"] < t_close]
    failed = [r for r in due if r["tokens"] is None]
    lat = []
    for r in due:
        if r["tokens"] is None:     # failed or never came: the worst
            lat.append(1e3 * ((r["t_done"] or d["t_end"]) - r["t_due"]))
        else:
            lat.append(1e3 * (r["t_done"] - r["t_due"]) / len(r["tokens"]))
    share = [window_share(r, t_open, t_close) for r in ok]
    e2e = {"out_tok_s": sum(len(r["tokens"]) * s
                            for r, s in zip(ok, share)) / window_s,
           "tok_lat_p95_ms": float(np.percentile(lat, 95)) if lat else None,
           "setup_s": setup_s}

    # -- the reference reads a sample of what was served ---------------------
    t_ref = time.perf_counter()
    gaps = served_gaps(cfg, tr, params, sample(tr, seed, ok))
    compared = checks.served(gaps) if gaps.size else {}
    compared["requests_cut_short"] = float(
        sum(len(r["tokens"]) != r["n_out"] for r in ok))
    compared["retraces_in_window"] = float(retraces)
    compared["kernel_fallbacks"] = float(
        fused.fused_stats()["fallback_calls"])
    reference_s = time.perf_counter() - t_ref

    # -- counters for the per-layer readers ----------------------------------
    # useful FLOPs are counted per request, by the share of its life inside
    # the window, and shared out to the traced interval by the tokens the
    # engine processed there (as `serve_engine.run` does); the paged
    # read's work is what the requests' own timelines put in the interval
    window = {k: d["stats1"][k] - d["stats0"][k] for k in COUNTED}
    useful = sum(s * work_ouro.request_flops(
        m, r["prompt"].size, len(r["tokens"])) for r, s in zip(ok, share))
    if tracer.traced():
        a, b = tracer.marks
        t_a, t_b = tracer.t_started, tracer.t_stopping
        counters = {k: b[k] - a[k] for k in COUNTED}
        traced_share = (counters["decode_tokens"]
                        + counters["prefill_tokens"]) \
            / max(1, window["decode_tokens"] + window["prefill_tokens"])
        counters.update(useful_flops=useful * traced_share,
                        interval_s=tracer.interval_s(),
                        interval_token_share=traced_share)
    else:
        a, b = d["stats0"], d["stats1"]
        t_a, t_b = t_open, t_close
        counters = dict(window, useful_flops=useful)
    counters.update(cache_counters(a, b))
    counters.update(model_counters(a, b))
    counters["loop_read_flops"], counters["loop_read_bytes"] = \
        work_ouro.loop_read_interval_work(
            m, timelines(records, timed.futures), t_a, t_b)
    counters["requests_in_window"] = len(in_window)
    counters.update(requests_sent=len(records), requests_finished=len(ok))
    counters.update(max_slots=eng_kw["max_slots"],
                    decode_steps=eng_kw["decode_steps"],
                    requests_due=len(due),
                    served_tokens_checked=int(gaps.size),
                    reference_s=reference_s,
                    drain_s=d["t_end"] - t_close)
    return {"e2e": e2e, "attempted": len(due), "failed": len(failed),
            "compared": compared,
            "memory_peak_bytes": peak, "counters": counters}
