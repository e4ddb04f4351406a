#!/usr/bin/env python3
"""What `configs/ling3f_serve.json` `assumed.weights` rests on: at the
cell's WIDTHS, with three layers (a KDA layer with the dense feed-forward,
a KDA layer and the MLA layer with experts; `layers=7`: the cell's own),
`held=8` of the 64 experts a layer and a 2048-row vocabulary so that it
runs on the CPU, over one sequence of `T` positions (default 1536: one
chunk edge at the engine's window of 1024; the last 256 read as served
ones, the first decode step at T - 256):

  * each mixer's and feed-forward's term against the residual stream it is
    added to, by layer
  * the decay: per-channel half-lives ln 2 / -g at their quantiles, beta's
    quantiles, and how much of the state's content at the last position is
    older than 64, 256 and 1024 positions (what a fault at a chunk edge can
    be seen through)
  * the router: how often this chip's group is kept, the share of a
    token's choices that the bias and that the group limit change, the load
    of each held expert
  * how far the float32 reference's served-token gaps, and the KDA
    layers' states after the last position, move under each control and
    planted fault of `reference/ling_kda.py`

    JAX_PLATFORMS=cpu python3 chipbench/tests/probe_weights_ling.py [config] [seed] [key=value ...]

`key=value` overrides a scale of the `model` group (`o_std=0.02`,
`kda_bf_range=-10.5,-4.3`); `controls=a,b` reads only those; `layers=7`
keeps the cell's depth; `held=64` its experts; `T=2048`; `stats=0` skips
the layer-by-layer part."""
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

LAST = 256


def main(name, seed, over, controls=None, layers=3, held=8, T=1536,
         stats=True):
    import numpy as np
    import jax
    import jax.numpy as jnp
    from chipbench import harness, weights_ling
    from chipbench.reference import ling_kda
    from incubator_mxnet_tpu.models import delta_moe_decoder as dm
    from incubator_mxnet_tpu.models import sparse_moe_decoder as sm

    cfg = harness.Bench(ROOT).config(name)
    m = dict(cfg["model"])
    edge = cfg["engine"]["prefill_window"]
    if layers == 3:
        m.update(mixer_types=["kda", "kda", "mla"],
                 mlp_types=["dense", "sparse", "sparse"])
    m.update(vocab=2048, max_len=T, held_count=held, **over)
    params = weights_ling.ling_params(m, seed)
    tokens = np.random.default_rng([seed, 5]).integers(
        1, m["vocab"], size=T).astype(np.int32)
    out = {"scales": {k: v for k, v in m.items()
                      if k.endswith(("_std", "_range"))}}
    q = (0.01, 0.1, 0.5, 0.9, 0.99)

    # -- the stream and every term, the decay, the router, layer by layer --
    c = weights_ling.delta_moe_config(dict(m, dtype="float32"))
    f32 = {k: v.astype(jnp.float32) for k, v in params.items()}
    rms = lambda a: float(jnp.sqrt(jnp.mean(jnp.square(a))))  # noqa: E731
    with jax.default_matmul_precision("highest"):
        x = f32["emb"][jnp.asarray(tokens)][None]
        pos = jnp.arange(T)[None]
        live = jnp.arange(T)[None, None, :] <= pos[..., None]
        for l in range(c.layers if stats else 0):
            w = dm._weights(f32, c, l)
            h = sm.rms_norm(x, w["ln1_w"], c.norm_eps)
            here = {"stream_rms": rms(x)}
            if c.mixer_types[l] == "kda":
                own = h @ w["k_qkv"]
                taps = jnp.concatenate(
                    [jnp.zeros((1, c.conv_kernel - 1, own.shape[-1])), own], 1)
                qq, k, v, g, beta = dm.kda_project(w, c, h, taps)
                o, _ = dm.kda_chunk(qq, k, v, g, beta, jnp.zeros(
                    (1, c.heads, c.head_dim, c.head_dim)), c.sub_chunk)
                mix = dm.kda_output(w, c, h, o)
                half = np.log(2) / -np.asarray(g[0])       # (T, H, D)
                # what is left at the last position of a unit written n
                # positions earlier, channel by channel
                cum = np.cumsum(np.asarray(g[0])[::-1], 0)
                here.update(
                    half_life_quantiles=dict(zip(map(str, q), np.quantile(
                        half, q).round(1).tolist())),
                    beta_quantiles=dict(zip(map(str, q), np.quantile(
                        np.asarray(beta), q).round(3).tolist())),
                    left_after={str(n): float(np.exp(cum[n - 1]).mean())
                                for n in (64, 256, 1024) if n < T})
            else:
                _, q_nope, q_rope, ckr = sm.mla_project(w, c, h, pos)
                o = sm.mla_read_rebuilt(q_nope, q_rope, ckr, live,
                                        w["wkv_b"], c)
                mix = dm._head_gate(o, h, w, c) @ w["m_wo"]
            here["mixer_rms"] = rms(mix)
            x = x + mix
            ok = jnp.ones((T,), bool)
            if c.mlp_types[l] == "sparse":
                hh = sm.rms_norm(x[0], w["ln2_w"], c.norm_eps)
                idx, _, kept = sm.route(hh, w["r_w"], w["r_b"], c,
                                        with_kept=True)
                idx0, _ = sm.route(hh, w["r_w"], jnp.zeros_like(w["r_b"]), c)
                c1 = weights_ling.delta_moe_config(     # every group kept
                    dict(m, dtype="float32", topk_group=m["n_group"]))
                idx1, _ = sm.route(hh, w["r_w"], w["r_b"], c1)
                same = lambda a, b: float(np.mean([  # noqa: E731
                    len(set(i) & set(j)) / len(i)
                    for i, j in zip(np.asarray(a), np.asarray(b))]))
                loads = np.bincount(np.asarray(idx).ravel(),
                                    minlength=c.routed_experts)
                here.update(
                    group_kept_here=float(np.asarray(kept)[:, 0].mean()),
                    choices_the_bias_leaves=same(idx, idx0),
                    choices_the_group_limit_leaves=same(idx, idx1),
                    held_loads_min_median_max=[
                        int(loads[:held].min()),
                        float(np.median(loads[:held])),
                        int(loads[:held].max())],
                    even_load=T * c.experts_per_token / c.routed_experts)
            y, _ = sm._ffn(x[0], w, c, l, ok)
            here["ffn_rms"] = rms(y - x[0])
            x = y[None]
            out[f"layer{l}"] = here
        print(json.dumps(out, indent=1), flush=True)

    # -- what the served tokens' gaps see ----------------------------------
    exact = ling_kda.make_forward(m)
    padded = jnp.asarray(tokens)
    rows, states = exact[0](params, padded, T, T - LAST)
    rows = rows[-LAST:]
    logits = np.asarray(exact[1](params, rows))
    seen = {"logit_std": float(logits.std()),
            "top2_gap_median": float(np.median(
                np.sort(logits, -1)[:, -1] - np.sort(logits, -1)[:, -2]))}
    for other in controls or ling_kda.PRECISIONS[1:] + ling_kda.FAULTS:
        fwd = ling_kda.make_forward(m, other, edge=edge)
        low_rows, low_states = fwd[0](params, padded, T, T - LAST)
        got = np.asarray(fwd[1](params, low_rows[-LAST:]))
        first = got.argmax(-1)
        gaps = logits.max(-1) - logits[np.arange(LAST), first]
        seen[other] = {"logit_rms_moved": float(np.sqrt(np.mean(
            np.square(got - logits)))),
            "gap_p99": float(np.quantile(gaps, 0.99)),
            "gap_max": float(gaps.max()),
            "state_gaps": ling_kda.state_gaps(low_states, states)}
        print(other, json.dumps(seen[other]), flush=True)
    print(json.dumps(seen, indent=1))


if __name__ == "__main__":
    args = [a for a in sys.argv[1:] if "=" not in a]
    over = dict(a.split("=") for a in sys.argv[1:] if "=" in a)
    controls = over.pop("controls", None)
    opts = {k: int(over.pop(k)) for k in ("layers", "held", "T", "stats")
            if k in over}
    opts["stats"] = bool(opts.get("stats", 1))
    main(args[0] if args else "ling3f_serve",
         int(args[1]) if len(args) > 1 else 7,
         {k: [float(x) for x in v.split(",")] if "," in v else float(v)
          for k, v in over.items()},
         tuple(controls.split(",")) if controls else None, **opts)
