"""Path `serve_engine`: `serve.ContinuousEngine` over `serve.CachedDecoder`
under a closed loop of callers.

One thread drives all callers: a request's completion (the future's
callback, on the engine's thread) stamps its time and wakes the driver,
which sends that caller's next request. A lead-in at the cell's own load
fills the slots at staggered phases before the window opens, so the window
sees the steady state and not the ramp; it counts as set-up. After the
window closes nothing new is sent, every request in flight is waited for
and its latency counts the wait. The plain reference then reads a sample of
finished requests, drawn from the seed with the longest in it."""
from __future__ import annotations

import gc
import queue
import time

from .. import checks, traffic, weights, work
from ..memory import peak_bytes
from ..reference import decoder as reference

DRAIN_TIMEOUT_S = 90.0
COUNTED = ("decode_iterations", "active_sum", "decode_tokens",
           "prefill_tokens", "prefill_batches", "prefix_hits", "admitted",
           "retired")


def build(cfg, params):
    """The system under test, warmed: (engine, model)."""
    from incubator_mxnet_tpu import serve
    m = cfg["model"]
    model = serve.CachedDecoder(
        serve.DecoderConfig(vocab=m["vocab"], embed=m["embed"],
                            layers=m["layers"], heads=m["heads"],
                            head_dim=m["head_dim"],
                            mlp_hidden=m["mlp_hidden"], max_len=m["max_len"],
                            dtype=m["dtype"]),
        params=params)
    eng = serve.ContinuousEngine(model, eos_id=None, **cfg["engine"])
    return eng.start(), model


def drive(eng, tr, seed, vocab, seconds, tracer):
    """Lead-in, window, drain. Returns the requests' records and the
    clock and counters at the window's ends."""
    source = traffic.requests(tr, seed, vocab)
    done = queue.Queue()
    records = []
    in_flight = 0

    def send(caller, t_due):
        nonlocal in_flight
        index, prompt, n_out = next(source)
        rec = {"index": index, "caller": caller, "prompt": prompt,
               "n_out": n_out, "t_due": t_due, "t_done": None,
               "tokens": None, "error": None}
        records.append(rec)

        def finished(fut, rec=rec):
            rec["t_done"] = time.perf_counter()
            err = fut.exception()
            if err is None:
                rec["tokens"] = fut.result()
            else:
                rec["error"] = repr(err)
            done.put(rec)

        try:
            eng.submit(prompt, n_out).add_done_callback(finished)
            in_flight += 1
        except Exception as e:          # refused at the door: a failure
            rec["t_done"], rec["error"] = time.perf_counter(), repr(e)

    def pump(until, resend):
        """Handle completions until the clock reaches `until`."""
        nonlocal in_flight
        while True:
            now = time.perf_counter()
            if tracer.due(now - t_open):
                tracer.toggle(eng.stats())
            left = until - now
            if left <= 0:
                return
            try:
                rec = done.get(timeout=min(left, 0.05))
            except queue.Empty:
                continue
            in_flight -= 1
            if resend:
                send(rec["caller"], rec["t_done"])

    t_lead = time.perf_counter()
    t_open = t_lead + tr["lead_in_s"]
    for caller in range(tr["callers"]):
        send(caller, t_lead)
    pump(t_open, resend=True)
    stats0 = eng.stats()
    t_close = t_open + seconds
    pump(t_close, resend=True)
    stats1 = eng.stats()
    tracer.finish(stats1)
    # nothing new is sent; what is in flight is waited for
    t_give_up = time.perf_counter() + DRAIN_TIMEOUT_S
    while in_flight and time.perf_counter() < t_give_up:
        pump(min(t_give_up, time.perf_counter() + 0.5), resend=False)
    return {"records": records, "t_open": t_open, "t_close": t_close,
            "t_end": time.perf_counter(), "stats0": stats0,
            "stats1": stats1}


def window_share(r, t_open, t_close):
    """The share of a finished request's life, from when it was due to
    its completion, that lies inside the window. A served rate counts each
    request's tokens and work by this share and not whole requests at their
    completion: whole requests move the rate in steps of a request (PR 23
    read 848.8 and 828.5 tokens/s where the engine's own token count
    differed by 1%: two requests had crossed the window's edge)."""
    inside = min(r["t_done"], t_close) - max(r["t_due"], t_open)
    return max(0.0, inside) / (r["t_done"] - r["t_due"])


def sample(tr, seed, ok):
    """The finished requests that the reference reads: the longest, and
    others drawn from the seed."""
    import numpy as np
    pool = sorted(ok, key=lambda r: r["index"])
    if not pool:
        return []
    longest = max(pool, key=lambda r: (r["prompt"].size + len(r["tokens"]),
                                       -r["index"]))
    others = [r for r in pool if r is not longest]
    take = min(tr["check_requests"] - 1, len(others))
    picks = np.random.default_rng([seed, 17]).choice(
        len(others), size=take, replace=False)
    return [longest] + [others[int(i)] for i in picks]


def served_gaps(cfg, tr, params, requests, precision="float32"):
    """Per served token of `requests`: how far below the float32
    reference's best logit the token lies. With a lower `precision` the
    token judged is the one that precision puts first (the control)."""
    import numpy as np
    import jax.numpy as jnp
    m = cfg["model"]
    pad_to = -(-(tr["prompt"]["max"] + tr["output"]["max"]) // 128) * 128
    exact = reference.make_forward(m)
    low = None if precision == "float32" else \
        reference.make_forward(m, precision)
    out = []
    for r in requests:
        at = reference.served_logits(exact, params, r["prompt"],
                                     r["tokens"], pad_to)
        tokens = r["tokens"]
        if low is not None:
            tokens = np.asarray(jnp.argmax(reference.served_logits(
                low, params, r["prompt"], r["tokens"], pad_to), -1))
        out.append(reference.gaps_below_best(at, tokens))
    return np.concatenate(out) if out else np.zeros((0,), np.float32)


def run(ctx):
    import numpy as np
    import jax

    cfg, tr, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    m, eng_kw = cfg["model"], cfg["engine"]
    tracer = ctx["tracer"]

    params = weights.decoder_params(m, seed)
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

    # -- end-to-end metrics ------------------------------------------------
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
    reference_s = time.perf_counter() - t_ref

    # -- counters for the per-layer readers ----------------------------------
    # work is counted per request, by the share of its life inside the
    # window; the window's work is shared out to the traced interval by
    # the tokens the engine processed there
    window = {k: d["stats1"][k] - d["stats0"][k] for k in COUNTED}
    kernel = [work.paged_attention_request_work(
        m, r["prompt"].size, len(r["tokens"]), eng_kw["prefill_window"])
        for r in ok]
    done_work = {
        "useful_flops": sum(s * work.decoder_request_flops(
            m, r["prompt"].size, len(r["tokens"]))
            for r, s in zip(ok, share)),
        "paged_attn_flops": sum(s * k[0] for k, s in zip(kernel, share)),
        "paged_attn_bytes": sum(s * k[1] for k, s in zip(kernel, share))}
    if tracer.traced():
        a, b = tracer.marks
        counters = {k: b[k] - a[k] for k in COUNTED}
        traced_share = (counters["decode_tokens"]
                        + counters["prefill_tokens"]) \
            / max(1, window["decode_tokens"] + window["prefill_tokens"])
        counters.update({k: v * traced_share for k, v in done_work.items()},
                        interval_s=tracer.interval_s(),
                        interval_token_share=traced_share)
    else:
        counters = dict(window, **done_work)
    counters["requests_in_window"] = len(in_window)
    counters.update(max_slots=eng_kw["max_slots"], requests_due=len(due),
                    served_tokens_checked=int(gaps.size),
                    reference_s=reference_s,
                    drain_s=d["t_end"] - t_close)
    return {"e2e": e2e, "attempted": len(due), "failed": len(failed),
            "compared": compared,
            "memory_peak_bytes": peak, "counters": counters}
