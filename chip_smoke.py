#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, in ONE process, through the entry points a
user calls, at the full width of a model the repo supports (depth as
published, weights random from `--seed`), and checks what comes out by the
repo's own means:

  train   ResNet-50 v1 (NHWC, 224x224x3, 1000 classes, batch 32, bf16 AMP)
          through `gluon.contrib.FusedTrainStep` (steps_per_call 1 and 2),
          whose compiled step holds no Pallas call (`step_custom_calls`),
          then two iterations of the README's eager loop (`autograd.record`
          -> `backward` -> `gluon.Trainer.step`) so op bulking runs once.
  serve   `serve.ContinuousEngine` over `serve.CachedDecoder` at GPT-2-small's
          published widths (vocab 50257, embed 768, 12 layers, 12 heads of
          64, mlp 3072, 1024 positions, bf16), 8 slots: mixed prompts (one
          longer than the prefill window, one prefix-cache hit), greedy
          outputs token-exact against the 1-slot `reference_generate`, zero
          retraces after warm-up, paged attention taken as a Pallas kernel
          and, at the engine's shapes (bf16 C=1, C=5, int8), within a stated
          bf16 tolerance of a float64 evaluation of the same read; one
          sampled request and one through a `draft_tokens` engine, both
          held to the reference like the greedy ones.

With `--chips 4` it runs ONLY the sharded train step of the flagship
transformer (`models.transformer.make_train_step`) on the four real chips —
dp=2 x tp=2, and sp=2 x tp=2 with ring attention — against the same config
and seed on a one-device mesh.

One JSON line per phase, then as the LAST line of stdout
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}` with
the device as jax reports it. Without `--tiny` the script needs a TPU: on
anything else it exits non-zero and prints no result. `--tiny` shrinks every
size for the CPU rehearsal (Pallas kernels in interpret mode there) and the
tier-1 test; its last line carries the platform it really ran on, so a tiny
CPU run can never pass for a chip run. A phase that raises or mismatches
ends the run non-zero. Facts printed here (warm-up seconds, peak bytes) are
smoke facts, not benchmark numbers.

The persistent compile cache follows `deploy.maybe_enable_compile_cache`:
`JAX_COMPILATION_CACHE_DIR` if set, else `MXNET_COMPILE_CACHE_DIR`, else
`<checkout>/.jax_cache`.
"""
from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# two bf16 ulps (2**-7 of a value each). What the engine's and the 1-slot
# reference's logits may differ by, relative to the largest logit, when only
# the batch shape differs; and what the paged-attention kernel's output may
# differ by from a float64 evaluation of the same read, relative to the
# element plus its lane's rms (on the chip the kernel reads 0.64-0.85 of ONE
# ulp, about twice its interpret-mode distance)
TWO_BF16_ULPS = 2.0 ** -6
# sharded vs one-device loss of the same bf16 step: reduction order and
# partitioned matmuls differ, the update is identical. The largest gap seen
# on four chips is 4.6e-5; the loss falls about 7% a step, so a 1e-3 band
# still catches an update that is wrong by a few percent (a gradient
# reduction missing on dp or sp), which a band of the loss's own step would
# not
SHARDED_LOSS_RTOL = 1e-3


def emit(obj):
    print(json.dumps(obj), flush=True)


def require(cond, what):
    """A failed check ends the run (never `assert`: -O strips it)."""
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {what}")


class _FellBack(logging.Handler):
    """Collects the `mx.ops.fused` DEBUG lines that name each dispatch
    served by a jnp composition instead of a kernel."""

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.names = []

    def emit(self, record):
        self.names.append(record.getMessage())


def _fused_scope():
    """(fused module, fallback-name collector) with counters reset."""
    from incubator_mxnet_tpu.ops import fused
    handler = _FellBack()
    fused.logger.addHandler(handler)
    fused.logger.setLevel(logging.DEBUG)
    fused.fused_stats(reset=True)
    return fused, handler


def _on_device(tree, platform):
    import jax
    return all(d.platform == platform
               for leaf in jax.tree_util.tree_leaves(tree)
               for d in leaf.devices())


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------
def phase_train(tiny, seed, platform):
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import amp, gluon, profiler
    from incubator_mxnet_tpu import optimizer as opt_mod
    from incubator_mxnet_tpu.gluon.contrib import FusedTrainStep
    from incubator_mxnet_tpu.gluon.model_zoo import vision

    model, batch, hw, classes = (("resnet18_v1", 4, 32, 10) if tiny else
                                 ("resnet50_v1", 32, 224, 1000))
    fused, fell_back = _fused_scope()
    rng = np.random.RandomState(seed)
    mx.seed(seed)
    t0 = time.perf_counter()
    amp.init("bfloat16")
    try:
        net = getattr(vision, model)(layout="NHWC", classes=classes)
        net.initialize()
        net.hybridize()
        loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

        def batch_of(k=None):
            lead = () if k is None else (k,)
            x = rng.uniform(-1, 1, lead + (batch, hw, hw, 3))
            y = rng.randint(0, classes, lead + (batch,))
            return mx.np.array(x.astype(np.float32)), mx.np.array(y)

        x, y = batch_of()
        net(x)                                     # resolve deferred shapes
        params = net.collect_params()
        require(_on_device([p.data()._arr for p in params.values()],
                           platform), f"parameters live on {platform}")
        watched = next(p for p in params.values() if p.grad_req != "null")
        before = watched.data().asnumpy().astype(np.float32)

        def objective(n, xb, yb):
            return loss_fn(n(xb), yb).sum()

        def sgd():
            return opt_mod.create("sgd", learning_rate=0.05, momentum=0.9,
                                  rescale_grad=1.0 / batch)

        step = FusedTrainStep(net, objective, sgd())
        program = step.lowered(x, y).compile().as_text()
        losses = [float(step(*batch_of()).asnumpy()) for _ in range(3)]
        step2 = FusedTrainStep(net, objective, sgd(), steps_per_call=2)
        losses += [float(v) for v in step2(*batch_of(2)).asnumpy()]
        after = watched.data().asnumpy().astype(np.float32)
        stats = fused.fused_stats()
        fused_s = round(time.perf_counter() - t0, 1)

        require(all(np.isfinite(losses)), f"fused losses finite: {losses}")
        require(np.isfinite(after).all() and (after != before).any(),
                "a parameter changed and stayed finite")
        # batch norm and the pool lower to their jnp composition, which the
        # compiler fuses into the convolutions: a Pallas call in the step
        # would pin a layout and cost two copies of a whole activation
        step_custom_calls = program.count("tpu_custom_call")
        require(step_custom_calls == 0 and stats["pallas_calls"] == 0,
                f"the compiled train step holds no Pallas kernel: "
                f"{step_custom_calls} custom calls, {stats}")
        require(not fell_back.names,
                f"no op fell back: {sorted(set(fell_back.names))}")

        # the README's eager loop on the same net: bulked segments
        profiler.dispatch_stats(reset=True)
        trainer = gluon.Trainer(params, "sgd",
                                {"learning_rate": 0.05, "momentum": 0.9})
        eager = []
        for _ in range(2):
            xb, yb = batch_of()
            with mx.autograd.record():
                loss = loss_fn(net(xb), yb).mean()
            loss.backward()
            trainer.step(batch, ignore_stale_grad=True)
            eager.append(float(loss.asnumpy()))
        mx.waitall()
        dispatch = profiler.dispatch_stats()
        require(all(np.isfinite(eager)), f"eager losses finite: {eager}")
        require(dispatch["bulked"] > 0 and dispatch["segment_flush"] > 0,
                f"eager ops ran as bulked segments: {dispatch}")
    finally:
        amp.uninit()
        fused.logger.removeHandler(fell_back)
    return {
        "model": model, "batch": batch, "input": [hw, hw, 3],
        "dtype": "bfloat16", "fused_dispatches": 4,
        "fused_losses": [round(v, 4) for v in losses],
        "eager_losses": [round(v, 4) for v in eager],
        "pallas_calls": stats["pallas_calls"],
        "fallback_calls": stats["fallback_calls"],
        "fell_back": sorted(set(fell_back.names)),
        "step_custom_calls": step_custom_calls,
        "eager_bulked_ops": dispatch["bulked"],
        "eager_segment_flushes": dispatch["segment_flush"],
        "smoke_fused_seconds_with_compile": fused_s,
        "smoke_peak_device_bytes": _peak_bytes(),
    }


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------
def _decode_program_text(engine):
    """Compiled text of the engine's decode program at its warm-up shapes."""
    return engine.lowered_programs()["decode"].compile().as_text()


def kernel_check(cfg, lanes, seed):
    """`ops.fused.paged_attention` at the engine's shapes — plain decode
    (C=1), the speculative-verify chunk (C=5) and an int8 slab with its
    per-position scales — against a float64 numpy evaluation of the same
    masked read, lane by lane. The token comparison below cannot see the
    kernel's numbers (engine and reference both go through it); this
    can. Returns {case: worst error / tolerance}, each required <= 1."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops import fused

    rows, L, T, H, D = lanes + 1, cfg.layers, cfg.max_len, cfg.heads, \
        cfg.head_dim
    layer = min(3, L - 1)

    def oracle(q, k, v, lengths, k_scale, v_scale):
        q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
        if k_scale is not None:
            k = k * np.asarray(k_scale, np.float64)[..., None, None]
            v = v * np.asarray(v_scale, np.float64)[..., None, None]
        scores = np.einsum("schd,sthd->shct", q, k) / np.sqrt(D)
        reach = lengths[:, None, None] + np.arange(q.shape[1])[None, :, None]
        scores = np.where((np.arange(T)[None, None, :] <= reach)[:, None],
                          scores, -np.inf)
        p = np.exp(scores - scores.max(-1, keepdims=True))
        return np.einsum("shct,sthd->schd", p / p.sum(-1, keepdims=True), v)

    out = {}
    for name, kv_dtype, chunk in (("bf16_C1", cfg.dtype, 1),
                                  ("bf16_C5_verify", cfg.dtype, 5),
                                  ("int8_C1", "int8", 1)):
        kq, kk, kv, ks, kvs = jax.random.split(
            jax.random.fold_in(jax.random.PRNGKey(seed), len(out)), 5)
        q = jax.random.normal(kq, (lanes, chunk, H, D), cfg.dtype)
        slab = (rows, L, T, H, D)
        if kv_dtype == "int8":
            k = jax.random.randint(kk, slab, -127, 128, jnp.int8)
            v = jax.random.randint(kv, slab, -127, 128, jnp.int8)
            scales = {"k_scale": jax.random.uniform(
                          ks, slab[:3], jnp.float32, 0.004, 0.03),
                      "v_scale": jax.random.uniform(
                          kvs, slab[:3], jnp.float32, 0.004, 0.03)}
        else:
            k = jax.random.normal(kk, slab, kv_dtype)
            v = jax.random.normal(kv, slab, kv_dtype)
            scales = {}
        # lanes from an empty page to a full one
        lengths = np.linspace(0, T - chunk - 1, lanes).astype(np.int32)
        got = jax.jit(lambda q, k, v, n, sc: fused.paged_attention(
            q, k, v, n, layer, **sc))(q, k, v, jnp.asarray(lengths), scales)
        want = oracle(q, k[:lanes, layer], v[:lanes, layer], lengths,
                      *(scales[s][:lanes, layer] if scales else None
                        for s in ("k_scale", "v_scale")))
        rms = np.sqrt((want ** 2).mean(axis=(1, 2, 3), keepdims=True))
        err = np.abs(np.asarray(got, np.float64) - want)
        out[name] = round(float(
            (err / (TWO_BF16_ULPS * (np.abs(want) + rms))).max()), 4)
    return out


def divergence_report(model, lanes, prompt, got, want):
    """The first position where an engine output leaves the reference,
    and whether it is a bf16 near-tie: the logits after the common
    context, computed at the engine's lane count and at the reference's
    single lane, must agree within `TWO_BF16_ULPS` of the largest
    logit, and so must the two sides' chosen tokens' logits. Returns a
    dict with `within_tolerance`; None when the outputs are equal."""
    import numpy as np
    import jax.numpy as jnp

    n = min(len(got), len(want))
    pos = next((i for i in range(n) if got[i] != want[i]), None)
    if pos is None:
        return None if len(got) == len(want) else {
            "position": n, "within_tolerance": False,
            "why": f"lengths differ: {len(got)} vs {len(want)}"}
    context = np.concatenate([np.asarray(prompt, np.int32),
                              np.asarray(got[:pos], np.int32)])
    width = model.config.max_len

    def logits_at(n_lanes):
        pool = model.new_pool(max_slots=n_lanes)
        k, v = pool.buffers()
        tokens = np.zeros((n_lanes, width), np.int32)
        tokens[0, :context.size] = context
        lengths = np.ones((n_lanes,), np.int32)
        lengths[0] = context.size
        rows = np.full((n_lanes,), pool.garbage_row, np.int32)
        rows[0] = 0
        _, _, out = model.prefill_program(width)(
            model.params, k, v, jnp.asarray(tokens), jnp.asarray(lengths),
            jnp.asarray(rows))
        return np.asarray(out[0], np.float32)

    wide, single = logits_at(lanes), logits_at(1)
    tol = TWO_BF16_ULPS * float(np.abs(single).max())
    sides = float(np.abs(wide - single).max())
    margin = float(abs(single[want[pos]] - single[got[pos]]))
    return {"position": pos, "engine_token": int(got[pos]),
            "reference_token": int(want[pos]),
            "logit_tolerance": round(tol, 4),
            "max_logit_gap_between_sides": round(sides, 4),
            "logit_margin_between_tokens": round(margin, 4),
            "within_tolerance": sides <= tol and margin <= tol}


def phase_serve(tiny, seed, platform):
    import numpy as np
    from incubator_mxnet_tpu import serve

    if tiny:
        cfg = serve.DecoderConfig(vocab=128, embed=32, layers=2, heads=4,
                                  head_dim=8, max_len=128, dtype="bfloat16")
        slots, window, block, short, mid, long_ = 4, 16, 4, 5, 23, 40
    else:
        cfg = serve.DecoderConfig(vocab=50257, embed=768, layers=12, heads=12,
                                  head_dim=64, mlp_hidden=3072, max_len=1024,
                                  dtype="bfloat16")
        slots, window, block, short, mid, long_ = 8, 128, 16, 12, 100, 300
    fused, fell_back = _fused_scope()
    rng = np.random.RandomState(seed)

    def prompt(n):
        return rng.randint(1, cfg.vocab, size=n).astype(np.int32)

    shared = prompt((mid // block) * block)        # whole prefix blocks
    cold = np.concatenate([shared, prompt(mid - shared.size)])
    hit = np.concatenate([shared, prompt(mid - shared.size + 1)])
    greedy = [("short", prompt(short), 16, {}),
              ("cold_prefix", cold, 12, {}),
              ("chunked", prompt(long_), 8, {})]
    hit_req = ("prefix_hit", hit, 12, {"cached_prefix_len": shared.size})
    sampled = ("sampled", prompt(short + 3), 12,
               {"temperature": 0.8, "top_k": 40, "seed": seed})
    draft_tokens = 4

    try:
        kernel = kernel_check(cfg, slots + 2, seed)   # slots + prefix rows
        require(max(kernel.values()) <= 1.0,
                f"paged attention within {TWO_BF16_ULPS} of the float64 "
                f"read at the engine's shapes (error / tolerance): {kernel}")
        t0 = time.perf_counter()
        model = serve.CachedDecoder(cfg, seed=seed)
        require(_on_device(model.params, platform),
                f"decoder weights live on {platform}")
        # references FIRST: their 1-slot programs compiled after start()
        # would read as engine retraces
        want = {name: model.reference_generate(p, m, window=window, **kw)
                for name, p, m, kw in greedy + [hit_req, sampled]}
        reference_s = round(time.perf_counter() - t0, 1)

        common = dict(max_slots=slots, prefill_window=window,
                      prefix_block=block, prefix_cache_slots=2)
        got, divergences = {}, {}
        with serve.ContinuousEngine(model, **common) as eng:
            require(_on_device(eng.pool.buffers(), platform),
                    f"KV slabs live on {platform}")
            futs = {name: eng.submit(p, m) for name, p, m, _ in greedy}
            got = {name: f.result(timeout=600) for name, f in futs.items()}
            # after `cold_prefix` retired and published its prefix
            got["prefix_hit"] = eng.generate(hit, hit_req[2], timeout=600)
            _, p, m, kw = sampled
            got["sampled"] = eng.submit(p, m, **kw).result(timeout=600)
            program = _decode_program_text(eng)
            hits, retraces = eng.prefix_hit_count(), eng.assert_no_retraces()
            warmup_s, lanes = eng.warmup_s, eng.prefill_lanes
        require(hits == 1, f"one prefix-cache hit, got {hits}")
        require(retraces == 0, f"zero retraces after warm-up, got {retraces}")

        with serve.ContinuousEngine(model, draft_tokens=draft_tokens,
                                    **common) as spec:
            got["draft"] = spec.generate(greedy[0][1], greedy[0][2],
                                         timeout=600)
            spec_program = _decode_program_text(spec)
            spec_retraces = spec.assert_no_retraces()
            spec_warmup_s = spec.warmup_s
            drafted = spec.stats()
        require(spec_retraces == 0, "zero retraces on the draft engine")
        # the sampled stream (its draw key is a function of seed and
        # position alone) and the drafted one (exact verification) are
        # held to the reference like the plain greedy ones
        want["draft"] = want["short"]
        for name, p in [(n, p) for n, p, _, _ in greedy + [hit_req, sampled]
                        ] + [("draft", greedy[0][1])]:
            report = divergence_report(model, lanes, p, list(got[name]),
                                       list(want[name]))
            if report is not None:
                divergences[name] = report
                require(report["within_tolerance"],
                        f"{name} left the reference outside the bf16 "
                        f"tolerance: {report}")
        stats = fused.fused_stats()
        require(stats["pallas_calls"] > 0 and stats["fallback_calls"] == 0,
                f"paged attention ran as a kernel everywhere: {stats} "
                f"{sorted(set(fell_back.names))}")
        if platform == "tpu":
            require("tpu_custom_call" in program
                    and "tpu_custom_call" in spec_program,
                    "the decode programs hold the paged-attention kernel")
    finally:
        fused.logger.removeHandler(fell_back)
    return {
        "config": cfg.as_dict(), "max_slots": slots,
        "prefill_window": window, "requests": len(got),
        "prompt_lengths": {n: int(p.size) for n, p, _, _ in
                           greedy + [hit_req, sampled]},
        "token_exact": not divergences, "divergences": divergences,
        "kernel_error_over_tolerance": kernel,
        "draft_accepted": drafted.get("draft_accepted"),
        "prefix_hits": hits, "retraces_after_warmup": retraces,
        "pallas_calls": stats["pallas_calls"],
        "fallback_calls": stats["fallback_calls"],
        "paged_attention_calls": stats["paged_attention_calls"],
        "kernels_in_decode_program": program.count("tpu_custom_call"),
        "kernels_in_draft_decode_program":
            spec_program.count("tpu_custom_call"),
        "smoke_reference_seconds_with_compile": reference_s,
        "smoke_warmup_seconds": warmup_s,
        "smoke_draft_warmup_seconds": spec_warmup_s,
        "smoke_peak_device_bytes": _peak_bytes(),
    }


# ---------------------------------------------------------------------------
# --chips 4: the sharded train step of the flagship transformer
# ---------------------------------------------------------------------------
def phase_sharded(tiny, seed, platform):
    import numpy as np
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from incubator_mxnet_tpu.models import transformer as tfm

    devices = jax.devices()[:4]
    require(len(devices) == 4, f"four devices, got {len(jax.devices())}")
    if tiny:
        width = dict(vocab_size=256, num_layers=2, d_model=64, num_heads=4,
                     d_ff=256, max_seq_len=64)
        batch, dtype = 4, "float32" if platform == "cpu" else "bfloat16"
    else:
        width = dict(vocab_size=32000, num_layers=4, d_model=2048,
                     num_heads=16, d_ff=8192, max_seq_len=2048)
        batch, dtype = 4, "bfloat16"
    seq = width["max_seq_len"]
    tokens = np.random.RandomState(seed).randint(
        0, width["vocab_size"], (batch, seq + 1)).astype(np.int32)
    steps = 3

    def run(devs, shape, ring):
        cfg = tfm.TransformerConfig(dtype=dtype, use_ring_attention=ring,
                                    **width)
        mesh = Mesh(np.array(devs).reshape(shape), ("dp", "sp", "tp"))
        with mesh:
            pspecs = tfm.param_shardings(cfg, mesh)
            params = jax.tree_util.tree_map(
                lambda p, s: jax.device_put(p, NamedSharding(mesh, s)),
                tfm.init_params(jax.random.PRNGKey(seed), cfg), pspecs,
                is_leaf=lambda x: not isinstance(x, (dict, list)))
            opt_state = tfm.init_opt_state(params)
            batch_ = {"tokens": jax.device_put(
                tokens, NamedSharding(mesh, P("dp", None)))}
            scalar = NamedSharding(mesh, P())
            step_fn = tfm.make_train_step(cfg, mesh)
            compiled = step_fn.lower(
                params, opt_state, batch_,
                jax.device_put(np.int32(0), scalar)).compile()
            qkv = params["layers"][0]["qkv"]       # P(None, 'tp')
            placement = {
                "devices": len({s.device for s in qkv.addressable_shards}),
                "shard_bytes": sorted({int(s.data.nbytes)
                                       for s in qkv.addressable_shards}),
                "bytes": int(qkv.nbytes)}
            losses = []
            for i in range(steps):
                params, opt_state, loss = compiled(
                    params, opt_state, batch_,
                    jax.device_put(np.int32(i), scalar))
                losses.append(float(loss))
        return losses, placement, compiled.as_text()

    ref_losses, _, _ = run(devices[:1], (1, 1, 1), False)
    require(all(np.isfinite(ref_losses)), f"one-device losses: {ref_losses}")
    out = {"config": dict(width, dtype=dtype), "batch": batch,
           "steps": steps, "one_device_losses": ref_losses,
           "loss_rtol": SHARDED_LOSS_RTOL}
    for name, shape, ring, collectives in (
            ("dp2_tp2", (2, 1, 2), False, ("all-reduce",)),
            ("sp2_tp2_ring", (1, 2, 2), True,
             ("all-reduce", "collective-permute"))):
        losses, placement, text = run(devices, shape, ring)
        tp = shape[2]
        require(np.allclose(losses, ref_losses, rtol=SHARDED_LOSS_RTOL),
                f"{name} losses {losses} vs one device {ref_losses}")
        require(placement["devices"] == 4 and placement["shard_bytes"]
                == [placement["bytes"] // tp],
                f"{name}: a tp-sharded weight sits on four devices, "
                f"1/tp of its bytes each: {placement}")
        missing = [c for c in collectives if c not in text]
        require(not missing, f"{name}: compiled step lacks {missing}")
        out[name] = {"losses": losses, "qkv_placement": placement,
                     "collectives": {c: text.count(c + "(") +
                                     text.count(c + "-start(")
                                     for c in ("all-reduce", "all-gather",
                                               "reduce-scatter",
                                               "collective-permute",
                                               "all-to-all")}}
    out["smoke_peak_device_bytes"] = _peak_bytes()
    return out


# ---------------------------------------------------------------------------
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="shrink every size (CPU rehearsal, tier-1 test); "
                         "the last line reports the platform really used")
    ap.add_argument("--seed", type=int, default=0,
                    help="weights and data are made from it")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded train step and what it "
                         "is compared with, on four chips")
    args = ap.parse_args(argv)

    if args.tiny and args.chips == 4 and \
            "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        # the CPU rehearsal of the four-chip path needs four CPU devices;
        # a TPU ignores the flag
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count=4"
                                   ).strip()
    sys.path.insert(0, HERE)
    import jax
    device = jax.devices()[0]
    platform = device.platform
    if platform != "tpu" and not args.tiny:
        print(f"chip_smoke: jax found no TPU (platform {platform!r}); "
              f"nothing was run. --tiny is the CPU rehearsal.",
              file=sys.stderr)
        return 2

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import deploy
    from incubator_mxnet_tpu.ops import fused
    deploy.default_compile_cache_to_checkout()
    deploy.maybe_enable_compile_cache()
    require(mx.current_device().jax_device.platform == platform,
            f"the default device is a {platform}")
    if platform != "tpu":
        fused.set_interpret(True)    # tiny CPU rehearsal reaches the kernels

    phases = ((("sharded", phase_sharded),) if args.chips == 4 else
              (("train", phase_train), ("serve", phase_serve)))
    for name, fn in phases:
        t0 = time.perf_counter()
        result = fn(args.tiny, args.seed, platform)
        emit(dict({"phase": name, "ok": True, "platform": platform,
                   "seconds": round(time.perf_counter() - t0, 1)}, **result))
    last = {"ok": True, "device": {"platform": platform,
                                   "kind": device.device_kind,
                                   "count": len(jax.devices())}}
    if args.tiny:
        last["tiny"] = True
    emit(last)
    return 0


if __name__ == "__main__":
    sys.exit(main())
