#!/usr/bin/env python3
"""What `configs/glm52_serve.json` `assumed.weights` rests on: at the cell's
WIDTHS, with two layers (the dense one with its `full` indexer and one
expert layer that shares its choice; `layers=5`: the cell's own five) and
a 2048-row vocabulary so that it runs on the CPU, over one sequence of
4096 positions (twice `index_topk`):

  * attention's term against the residual stream it is added to, by layer
  * how peaked the softmax over the chosen positions is (largest weight,
    effective number of positions 1 / sum p^2) at the last 256 queries
  * how far the float32 reference's logits move when the chosen positions
    are replaced by the newest 2048 (`newest_topk`), against how far they
    move under bfloat16 rounding alone (`bfloat16`): the comparison that
    decides `correct` can see the choice only if the first is many times
    the second
  * the router: the share of top-k choices that the bias changes, and the
    load of each held expert

    JAX_PLATFORMS=cpu python3 chipbench/tests/probe_weights_glm.py [config] [seed] [key=value ...]

`key=value` overrides a scale of the `model` group (e.g. `q_b_std=0.02`);
`controls=a,b` reads only those controls and faults; `layers=5` keeps the
cell's depth (the second `full` indexer then reads a stream that four
layers of rounding have moved, which is where bfloat16 costs most);
`stats=0` skips the layer-by-layer part."""
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

T, LAST = 4096, 256


def main(name, seed, over, controls=None, layers=2, stats=True):
    import numpy as np
    import jax
    import jax.numpy as jnp
    from chipbench import harness, weights_glm
    from chipbench.reference import glm_dsa
    from incubator_mxnet_tpu.models import sparse_moe_decoder as sm

    m = dict(harness.Bench(ROOT).config(name)["model"])
    if layers == 2:
        m.update(indexer_types=["full", "shared"],
                 mlp_types=["dense", "sparse"])
    m.update(vocab=2048, max_len=T, **over)
    params = weights_glm.glm_params(m, seed)
    tokens = np.random.default_rng([seed, 5]).integers(
        1, m["vocab"], size=T).astype(np.int32)
    out = {"scales": {k: v for k, v in m.items() if k.endswith("_std")}}

    # -- the stream, attention's term, the softmax, layer by layer --------
    c = weights_glm.sparse_moe_config(dict(m, dtype="float32"))
    f32 = {k: v.astype(jnp.float32) for k, v in params.items()}
    rms = lambda a: float(jnp.sqrt(jnp.mean(jnp.square(a))))  # noqa: E731
    with jax.default_matmul_precision("highest"):
        x = f32["emb"][jnp.asarray(tokens)][None]
        pos = jnp.arange(T)[None]
        live = jnp.arange(T)[None, None, :] <= pos[..., None]
        mask = live
        for l in range(c.layers if stats else 0):
            w = sm._weights(f32, c, l)
            h = sm.rms_norm(x, w["ln1_w"], c.norm_eps)
            cq, q_nope, q_rope, ckr = sm.mla_project(w, c, h, pos)
            if c.slots[l][0] is not None:
                qI, wI, kI = sm.index_project(w, c, h, cq, pos)
                mask = sm.select_mask(sm.index_scores(qI, wI, kI), live,
                                      c.index_topk)
            o = sm.mla_read_rebuilt(q_nope, q_rope, ckr, mask, w["wkv_b"],
                                    c) @ w["wo"]
            # the softmax of the last queries, head by head
            kv = (ckr[0, :, :c.kv_lora_rank] @ w["wkv_b"]).reshape(
                T, c.heads, -1)
            s = (jnp.einsum("whd,ehd->hwe", q_nope[0, -LAST:],
                            kv[..., :c.qk_nope_head_dim])
                 + jnp.einsum("whd,ed->hwe", q_rope[0, -LAST:],
                              ckr[0, :, c.kv_lora_rank:c.lat_width])) / 16.0
            p = jax.nn.softmax(jnp.where(mask[0, -LAST:][None], s, -1e30), -1)
            out[f"layer{l}"] = {
                "stream_rms": rms(x), "attention_rms": rms(o),
                "score_std": float(jnp.std(s[:, -1, :])),
                "softmax_largest_median": float(jnp.median(p.max(-1))),
                "softmax_effective_positions_median": float(
                    jnp.median(1.0 / jnp.sum(p * p, -1)))}
            x = x + o
            ok = jnp.ones((T,), bool)
            if c.mlp_types[l] == "sparse":
                hh = sm.rms_norm(x[0], w["ln2_w"], c.norm_eps)
                idx, _ = sm.route(hh, w["r_w"], w["r_b"], c)
                idx0, _ = sm.route(hh, w["r_w"], jnp.zeros_like(w["r_b"]), c)
                same = np.mean([len(set(a) & set(b)) / len(a) for a, b in
                                zip(np.asarray(idx), np.asarray(idx0))])
                loads = np.bincount(np.asarray(idx).ravel(),
                                    minlength=c.routed_experts)
                out[f"layer{l}"].update(
                    choices_the_bias_leaves=float(same),
                    held_loads=loads[:c.held_count].tolist(),
                    even_load=T * c.experts_per_token / c.routed_experts,
                    all_loads_min_max=[int(loads.min()), int(loads.max())])
            y, _ = sm._ffn(x[0], w, c, l, ok)
            out[f"layer{l}"]["ffn_rms"] = rms(y - x[0])
            x = y[None]
        print(json.dumps(out, indent=1), flush=True)

    # -- what the logits see ------------------------------------------------
    exact = glm_dsa.make_forward(m)
    rows = exact[0](params, jnp.asarray(tokens))[-LAST:]
    logits = np.asarray(exact[1](params, rows))
    seen = {"logit_std": float(logits.std()),
            "top2_gap_median": float(np.median(
                np.sort(logits, -1)[:, -1] - np.sort(logits, -1)[:, -2]))}
    for other in controls or ("bfloat16", "int8") + glm_dsa.FAULTS:
        fwd = glm_dsa.make_forward(m, other)
        got = np.asarray(fwd[1](params, fwd[0](params,
                                               jnp.asarray(tokens))[-LAST:]))
        first = got.argmax(-1)
        gaps = logits.max(-1) - logits[np.arange(LAST), first]
        seen[other] = {"logit_rms_moved": float(np.sqrt(np.mean(
            np.square(got - logits)))),
            "gap_p99": float(np.quantile(gaps, 0.99)),
            "gap_max": float(gaps.max())}
        print(other, json.dumps(seen[other]), flush=True)
    print(json.dumps(seen, indent=1))


if __name__ == "__main__":
    args = [a for a in sys.argv[1:] if "=" not in a]
    over = dict(a.split("=") for a in sys.argv[1:] if "=" in a)
    controls = over.pop("controls", None)
    layers, stats = int(over.pop("layers", 2)), int(over.pop("stats", 1))
    main(args[0] if args else "glm52_serve",
         int(args[1]) if len(args) > 1 else 7,
         {k: float(v) for k, v in over.items()},
         tuple(controls.split(",")) if controls else None, layers,
         bool(stats))
