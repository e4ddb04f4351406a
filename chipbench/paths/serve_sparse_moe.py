"""Path `serve_sparse_moe`: `serve.ContinuousEngine` over
`models.sparse_moe_decoder.SparseMoEDecoder` (latent attention, a learned
choice of the positions read, routed experts of which this chip holds a
share) under the closed loop of callers that `paths/serve_engine.py`
drives. The driver (`drive`), the share of a request inside the window
(`window_share`), the sample that the reference reads (`sample`) and the
comparison (`checks.served`) are that path's, the cache's counters
(`cache_counters`) `paths/serve_hybrid.py`'s, and are imported, not copied;
the model, its weights, its plain reference and its work functions are this
configuration's own (`weights_glm`, `reference/glm_dsa`, `work_glm`)."""
from __future__ import annotations

import gc
import time

from .. import checks, weights_glm, work_glm
from ..memory import peak_bytes
from ..reference import glm_dsa as reference
from .serve_engine import COUNTED, drive, sample, window_share
from .serve_hybrid import cache_counters


def build(cfg, params):
    """The system under test, warmed: (engine, model)."""
    from incubator_mxnet_tpu import serve
    from incubator_mxnet_tpu.models import sparse_moe_decoder
    model = sparse_moe_decoder.SparseMoEDecoder(
        weights_glm.sparse_moe_config(cfg["model"]), params=params)
    eng = serve.ContinuousEngine(model, eos_id=None, **cfg["engine"])
    return eng.start(), model


def pad_to(tr):
    """Positions of the reference's pass: the longest request, in whole
    query blocks."""
    block = reference.Q_BLOCK
    return -(-(tr["prompt"]["max"] + tr["output"]["max"]) // block) * block


def served_gaps(cfg, tr, params, requests, precision="float32"):
    """Per served token of `requests`: how far below the float32
    reference's best logit the token lies. With a lower `precision` (or a
    planted fault) the token judged is the one that forward puts first."""
    import numpy as np
    m = cfg["model"]
    exact = reference.make_forward(m)
    judge = None if precision == "float32" else \
        reference.make_forward(m, precision)
    out = [reference.served_gaps(exact, params, r["prompt"], r["tokens"],
                                 pad_to(tr), judge=judge) for r in requests]
    return np.concatenate(out) if out else np.zeros((0,), np.float32)


def model_counters(a, b):
    """What `readers/counter_share.py` reads: the model's own counters
    (`stats()["moe"]`, `["sparse"]`) between two snapshots, as
    `<group>_<field>`; {} where the program's `stats()` has none."""
    return {f"{group}_{field}": b[group][field] - a[group][field]
            for group in ("moe", "sparse") if group in a and group in b
            for field in b[group]}


def run(ctx):
    import numpy as np
    import jax
    from incubator_mxnet_tpu.ops import fused

    # a program without this decoder (this path's parent) fails here, at
    # once, before any weight is made
    from incubator_mxnet_tpu.models import sparse_moe_decoder  # noqa: F401

    cfg, tr, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    m, eng_kw = cfg["model"], cfg["engine"]
    tracer = ctx["tracer"]

    params = weights_glm.glm_params(m, seed)
    eng, model = build(cfg, params)
    try:
        d = drive(eng, tr, seed, m["vocab"], ctx["seconds"], tracer)
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
    # engine processed there (as `serve_engine.run` does)
    window = {k: d["stats1"][k] - d["stats0"][k] for k in COUNTED}
    useful = sum(s * work_glm.request_flops(
        m, r["prompt"].size, len(r["tokens"])) for r, s in zip(ok, share))
    if tracer.traced():
        a, b = tracer.marks
        counters = {k: b[k] - a[k] for k in COUNTED}
        traced_share = (counters["decode_tokens"]
                        + counters["prefill_tokens"]) \
            / max(1, window["decode_tokens"] + window["prefill_tokens"])
        counters.update(useful_flops=useful * traced_share,
                        interval_s=tracer.interval_s(),
                        interval_token_share=traced_share)
    else:
        a, b = d["stats0"], d["stats1"]
        counters = dict(window, useful_flops=useful)
    counters.update(cache_counters(a, b))
    counters.update(model_counters(a, b))
    counters["requests_in_window"] = len(in_window)
    counters.update(max_slots=eng_kw["max_slots"],
                    decode_steps=eng_kw["decode_steps"],
                    requests_due=len(due),
                    served_tokens_checked=int(gaps.size),
                    reference_s=reference_s,
                    drain_s=d["t_end"] - t_close)
    return {"e2e": e2e, "attempted": len(due), "failed": len(failed),
            "compared": compared,
            "memory_peak_bytes": peak, "counters": counters}
