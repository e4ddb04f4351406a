"""Path `serve_mamba_moe`: `serve.ContinuousEngine` over
`models.mamba_moe_decoder.MambaMoEDecoder` (Mamba-2 mixers with a matrix
state a head, sigmoid-routed relu^2 experts of which this chip holds a
share and a shared one, attention with no positional encoding) under the
closed loop of callers that `paths/serve_engine.py` drives. The loop
(`drive`), the share of a request inside the window (`window_share`), the
sample that the reference reads (`sample`) and the comparison
(`checks.served`) are that path's, the engine that keeps its requests'
timelines (`Timed`) and the cache's counters `paths/serve_hybrid.py`'s, the
model's counters `paths/serve_delta_moe.py`'s (the same two groups, `moe`
and `state`), and are imported, not copied; the
model, its weights, its plain reference, its reading of the states that a
drained engine left in the pool and its work functions are this
configuration's own (`weights_nemotron`, `reference/nemotron_h`,
`work_nemotron`).

Two things are compared. The served tokens of a sample of requests
against the float32 reference's logits (`checks.served`), as in every
serve cell; and the float32 STATE that requests left in the drained pool
(the longest and the shortest: `left_states`) against the reference's
position-by-position recurrence over the same tokens (`ssm_state_gap`),
the number that a state kept in a narrower type moves (`reference/
nemotron_h.py` `bf16_state`: the longest request's) and a state that
starts from another tenant's (`stale_state`: the shortest's), in the head
where it is widest (`reference.state_gaps`), and the number that a
narrower product feeding the state (W8A8) moves in every head, over the
whole layer (`reference.layer_state_gaps`). The numbers are the FIRST
Mamba layer's: its inputs are the embedding through one norm and one
matmul, all but the reference's own, so its gap is the state's arithmetic
and little else; a deeper layer's gap is mostly the stream's rounding on
the way to it (reported, `ssm_state_gap_widest`, not compared)."""
from __future__ import annotations

import gc
import time

from .. import checks, weights_nemotron, work_nemotron
from ..memory import peak_bytes
from ..reference import nemotron_h as reference
from .serve_delta_moe import model_counters
from .serve_engine import COUNTED, drive, sample, window_share
from .serve_hybrid import Timed, cache_counters


def build(cfg, params):
    """The system under test, warmed: (engine, model)."""
    from incubator_mxnet_tpu import serve
    from incubator_mxnet_tpu.models import mamba_moe_decoder
    model = mamba_moe_decoder.MambaMoEDecoder(
        weights_nemotron.mamba_moe_config(cfg["model"]), params=params)
    eng = serve.ContinuousEngine(model, eos_id=None, **cfg["engine"])
    return eng.start(), model


#: the shortest pass of the reference, in positions
SHORTEST_PASS = 1024


def pad_to(tr, n=None):
    """Positions of the reference's pass over `n` tokens: the cell's
    longest request in whole query blocks, halved while the half still
    holds `n` and `SHORTEST_PASS` (a few shapes, each compiled once: one
    shape for every request made a request of a few hundred tokens cost
    what the longest does)."""
    block = reference.Q_BLOCK
    size = -(-(tr["prompt"]["max"] + tr["output"]["max"]) // block) * block
    while n is not None:
        half = -(-size // 2 // block) * block
        if half < max(n, SHORTEST_PASS) or half == size:
            break
        size = half
    return size


def left_states(eng, records, futures, n):
    """[(record, [a Mamba layer's state (H, P, N) float32])] of `n` of the
    requests whose states the drained engine's pool still holds (a row
    keeps what its last tenant left: an idle lane's dt is 0): the longest
    ones, where the state's rounding has had the most positions to gather,
    and the shortest, where what a previous tenant left would still show
    (at this configuration's decays most of a state is under a hundred
    positions old: after a long request a stale start has faded)."""
    import numpy as np
    last = {}
    for r, f in zip(records, futures):
        if f is not None and f.timing.slot is not None and (
                f.timing.slot not in last
                or f.timing.t_admit > last[f.timing.slot][1].timing.t_admit):
            last[f.timing.slot] = (r, f)
    by_length = sorted(
        (x for x in last.values() if x[0]["tokens"] is not None),
        key=lambda x: (-(x[0]["prompt"].size + len(x[0]["tokens"])),
                       x[0]["index"]))
    held = by_length[:max(n - 1, 0)] + by_length[max(n - 1, 0):][-1:] \
        if n else []
    leaves, = eng.pool.buffers()
    names = sorted((k for k in leaves if k.startswith("ssm")),
                   key=lambda k: int(k[3:]))
    return [(r, [np.asarray(leaves[k][f.timing.slot]) for k in names])
            for r, f in held]


def read_against_reference(cfg, tr, params, sampled, left,
                           precision="float32", exact_passes=None):
    """-> (gaps, state gaps). gaps: per served token of the `sampled`
    requests, how far below the float32 reference's best logit the token
    lies. state gaps: per request of `left` ([(record, states)]), by Mamba
    layer, how far the state it left lies from the float32 reference's
    after the same tokens. With a lower `precision` (or a planted fault)
    the token judged is the one that forward puts first and the state its
    own: what a program computing so would have served and left.
    `exact_passes` (a dict, for a caller that reads several precisions over
    the same requests) keeps the float32 passes from call to call."""
    import numpy as np
    m = cfg["model"]
    kept = {} if exact_passes is None else exact_passes
    if "forward" not in kept:
        kept["forward"] = reference.make_forward(m)
    exact = kept["forward"]
    judge = None if precision == "float32" else reference.make_forward(
        m, precision, edge=cfg["engine"]["prefill_window"])
    gaps, state_gaps = [np.zeros((0,), np.float32)], []
    todo = [(r, None) for r in sampled
            if not any(r is q for q, _ in left)] + list(left)
    for r, states in todo:
        size = pad_to(tr, r["prompt"].size + len(r["tokens"]) - 1)
        if id(r) not in kept:
            kept[id(r)] = reference.served_rows_and_states(
                exact, params, r["prompt"], r["tokens"], size)
        rows, want = kept[id(r)]
        tokens = r["tokens"]
        if judge is not None:
            low, states_low = reference.served_rows_and_states(
                judge, params, r["prompt"], r["tokens"], size)
            tokens = reference.first_choices(judge, params, low)
            states = states_low if states is not None else None
        if any(r is q for q in sampled):
            gaps.append(reference.gaps_below_best(exact, params, rows,
                                                  tokens))
        if states is not None:
            state_gaps.append((reference.state_gaps(states, want),
                               reference.layer_state_gaps(states, want)))
    return np.concatenate(gaps), state_gaps


def state_check(state_gaps):
    """The first Mamba layer's gaps, the widest over the requests read
    ([(by head, over the layer)] a request, by layer): in its widest head
    (`ssm_state_gap`: what a state kept in a narrower type and a state
    started from another tenant's move) and over the whole layer
    (`ssm_state_layer_gap`: what a narrower product feeding the state
    moves, in every head at once); NaN (under no limit) where no state
    could be read."""
    nan = float("nan")
    return {"ssm_state_gap": max((h[0] for h, _ in state_gaps), default=nan),
            "ssm_state_layer_gap": max((w[0] for _, w in state_gaps),
                                       default=nan)}


def attn_timelines(records, futures):
    """[(prompt, out, t_admit, t_first, t_done)] of the finished requests,
    for `work_nemotron.attn_read_interval_work`."""
    return [(r["prompt"].size, len(r["tokens"]), f.timing.t_admit,
             f.timing.t_first, f.timing.t_done)
            for r, f in zip(records, futures) if r["tokens"] is not None]


def run(ctx):
    import numpy as np
    import jax
    from incubator_mxnet_tpu.ops import fused

    # a program without this decoder (this path's parent) fails here, at
    # once, before any weight is made
    from incubator_mxnet_tpu.models import mamba_moe_decoder  # noqa: F401

    cfg, tr, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    m, eng_kw = cfg["model"], cfg["engine"]
    tracer = ctx["tracer"]

    params = weights_nemotron.nemotron_params(m, seed)
    eng, model = build(cfg, params)
    timed = Timed(eng)
    try:
        d = drive(timed, tr, seed, m["vocab"], ctx["seconds"], tracer)
        retraces = eng.retraces_after_warmup()
        peak = peak_bytes(jax.devices()[0])
        left = left_states(eng, d["records"], timed.futures,
                           tr["check_states"])
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
    gaps, state_gaps = read_against_reference(
        cfg, tr, params, sample(tr, seed, ok), left)
    compared = checks.served(gaps) if gaps.size else {}
    compared.update(state_check(state_gaps))
    compared["requests_cut_short"] = float(
        sum(len(r["tokens"]) != r["n_out"] for r in ok))
    compared["retraces_in_window"] = float(retraces)
    compared["kernel_fallbacks"] = float(
        fused.fused_stats()["fallback_calls"])
    reference_s = time.perf_counter() - t_ref

    # -- counters for the per-layer readers ----------------------------------
    # useful FLOPs are counted per request, by the share of its life inside
    # the window, and shared out to the traced interval by the tokens the
    # engine processed there (as `serve_engine.run` does); the state step's
    # work is what the micro-steps of the interval owed its live lanes, the
    # paged read's what the requests' own timelines put in the interval
    window = {k: d["stats1"][k] - d["stats0"][k] for k in COUNTED}
    useful = sum(s * work_nemotron.request_flops(
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
    if "state_lane_layer_steps" in counters:
        counters["ssm_state_flops"], counters["ssm_state_bytes"] = \
            work_nemotron.ssm_state_work(m, counters["state_lane_layer_steps"])
    counters["paged_attn_flops"], counters["paged_attn_bytes"] = \
        work_nemotron.attn_read_interval_work(
            m, attn_timelines(records, timed.futures), t_a, t_b,
            eng_kw["prefill_window"])
    counters["requests_in_window"] = len(in_window)
    counters.update(requests_sent=len(records), requests_finished=len(ok))
    counters.update(max_slots=eng_kw["max_slots"],
                    decode_steps=eng_kw["decode_steps"],
                    requests_due=len(due),
                    served_tokens_checked=int(gaps.size),
                    states_checked=len(state_gaps),
                    ssm_state_gap_widest=max(
                        (max(h) for h, _ in state_gaps), default=None),
                    reference_s=reference_s,
                    drain_s=d["t_end"] - t_close)
    return {"e2e": e2e, "attempted": len(due), "failed": len(failed),
            "compared": compared,
            "memory_peak_bytes": peak, "counters": counters}
