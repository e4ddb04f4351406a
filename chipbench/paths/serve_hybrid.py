"""Path `serve_hybrid`: `serve.ContinuousEngine` over
`models.hybrid_decoder.HybridDecoder` (the SambaY decoder) under the closed
loop of callers that `paths/serve_engine.py` drives. The driver (`drive`:
lead-in, window, drain), the share of a request inside the window
(`window_share`), the sample that the reference reads (`sample`) and the
comparison (`checks.served`) are that path's and are imported, not copied;
the model, its weights, its plain reference and its work functions are this
configuration's own (`weights_sambay`, `reference/sambay`, `work_sambay`)."""
from __future__ import annotations

import gc
import time

from .. import checks, weights_sambay, work_sambay
from ..memory import peak_bytes
from ..reference import sambay as reference
from .serve_engine import COUNTED, drive, sample, window_share

def build(cfg, params):
    """The system under test, warmed: (engine, model)."""
    from incubator_mxnet_tpu import serve
    from incubator_mxnet_tpu.models import hybrid_decoder
    model = hybrid_decoder.HybridDecoder(
        weights_sambay.hybrid_config(cfg["model"]), params=params)
    eng = serve.ContinuousEngine(model, eos_id=None, **cfg["engine"])
    return eng.start(), model


def served_gaps(cfg, tr, params, requests, precision="float32"):
    """Per served token of `requests`: how far below the float32
    reference's best logit the token lies. With a lower `precision` the
    token judged is the one that precision puts first (the control)."""
    import numpy as np
    m = cfg["model"]
    pad_to = -(-(tr["prompt"]["max"] + tr["output"]["max"]) // 128) * 128
    exact = reference.make_forward(m)
    judge = None if precision == "float32" else \
        reference.make_forward(m, precision)
    out = [reference.served_gaps(exact, params, r["prompt"], r["tokens"],
                                 pad_to, judge=judge) for r in requests]
    return np.concatenate(out) if out else np.zeros((0,), np.float32)


class Timed:
    """The engine as `drive` sees it (`submit`, `stats`), keeping every
    request's future so that its `timing` can be read afterwards; `drive`
    appends a record and then submits, so `futures[i]` is `records[i]`'s
    (None where the engine refused the request at the door)."""

    def __init__(self, eng):
        self.eng, self.futures = eng, []
        self.stats = eng.stats

    def submit(self, prompt, n_out):
        self.futures.append(None)
        fut = self.futures[-1] = self.eng.submit(prompt, n_out)
        return fut


def timelines(records, futures):
    """[(prompt, out, t_first, t_done)] of the finished requests, for
    `work_sambay.shared_attn_interval_work`."""
    return [(r["prompt"].size, len(r["tokens"]), f.timing.t_first,
             f.timing.t_done)
            for r, f in zip(records, futures) if r["tokens"] is not None]


def cache_counters(a, b):
    """What `readers/cache_live_share.py` reads, from two `stats()`
    snapshots; {} where the program's `stats()` has no `cache` entry."""
    if "cache" not in a or "cache" not in b:
        return {}
    return {"cache_bytes": sum(k["bytes"] for k in b["cache"].values()),
            "cache_live_bytes_sum": sum(
                b["cache"][kind]["live_bytes_sum"] - k["live_bytes_sum"]
                for kind, k in a["cache"].items())}


def run(ctx):
    import numpy as np
    import jax
    from incubator_mxnet_tpu.ops import fused

    # a program without the hybrid decoder (this path's parent) fails here,
    # at once, before any weight is made
    from incubator_mxnet_tpu.models import hybrid_decoder  # noqa: F401

    cfg, tr, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    m, eng_kw = cfg["model"], cfg["engine"]
    tracer = ctx["tracer"]

    params = weights_sambay.sambay_params(m, seed)
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
    # engine processed there (as `serve_engine.run` does); the shared
    # read's work is what the requests' own timelines put in the interval
    window = {k: d["stats1"][k] - d["stats0"][k] for k in COUNTED}
    useful = sum(s * work_sambay.request_flops(
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
    counters["shared_attn_flops"], counters["shared_attn_bytes"] = \
        work_sambay.shared_attn_interval_work(
            m, timelines(records, timed.futures), t_a, t_b)
    counters["requests_in_window"] = len(in_window)
    counters.update(max_slots=eng_kw["max_slots"], requests_due=len(due),
                    served_tokens_checked=int(gaps.size),
                    reference_s=reference_s,
                    drain_s=d["t_end"] - t_close,
                    **{k: v for k, v in fused.fused_stats().items()
                       if k.startswith("paged_")})
    return {"e2e": e2e, "attempted": len(due), "failed": len(failed),
            "compared": compared,
            "memory_peak_bytes": peak, "counters": counters}
