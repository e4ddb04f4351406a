#!/usr/bin/env python3
"""What `configs/ouro26b_serve.json` `assumed.weights` rests on: at the
cell's WIDTHS, over one sequence of `T` positions (default 512: the
longest request, one chunk edge at the engine's window of 256; the last
`LAST` read as served ones), for each set of scales named on the command
line:

  * how peaked attention is (the largest softmax weight of a query, its
    median over heads and late positions, in the first and the last layer
    of the first pass) and how much of a position's stream is its own
    token's (the cosine between the streams of two sequences that differ
    in every token but share nothing else would be 1 for a stream that
    forgot its tokens: reported after each pass)
  * how far the float32 reference's served-token gaps move under each
    control and planted fault of `reference/ouro_loop.py`: the 99th
    percentile and the widest, as `checks.served` reads them

On the CPU it takes `layers=6` (default there: 0.6 GB of weights); on the
chip the cell's own 48 fit and a set of scales takes half a minute.

    python3 chipbench/tests/probe_weights_ouro.py [config] [seed] [layers=N] [T=N] [controls=a,b] emb_std=1.0,q_std=0.04 ...
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

LAST = 256


def peak_weight(m, params, x, layer):
    """Median over heads and the last LAST queries of the largest softmax
    weight, for the stream x (T, d) entering `layer` of a pass."""
    import math
    import jax
    import jax.numpy as jnp
    from chipbench.reference import ouro_loop
    H, D = m["heads"], m["head_dim"]
    T = x.shape[0]
    f32 = lambda n: params[n][layer].astype(jnp.float32)  # noqa: E731
    h = ouro_loop._rmsnorm(x, f32("n1"), m["norm_eps"])
    t = jnp.arange(T)
    q, k = (ouro_loop.rotary(
        jnp.matmul(h, f32(n), precision="highest").reshape(T, H, D), t,
        float(m["rope_theta"])) for n in ("wq", "wk"))
    sco = jnp.einsum("qhd,khd->hqk", q[-LAST:], k, precision="highest") \
        / math.sqrt(D)
    sees = t[-LAST:, None] >= t[None, :]
    p = jax.nn.softmax(jnp.where(sees[None], sco, -jnp.inf), -1)
    return float(jnp.median(p.max(-1)))


def main(name, seed, sets, layers, T, controls):
    import numpy as np
    import jax
    import jax.numpy as jnp
    from chipbench import checks, harness, weights_ouro
    from chipbench.reference import ouro_loop

    on_chip = jax.devices()[0].platform == "tpu"
    cfg = harness.Bench(ROOT).config(name)
    edge = cfg["engine"]["prefill_window"]
    rng = np.random.default_rng([seed, 5])
    for over in sets:
        m = dict(cfg["model"], **over)
        m["layers"] = layers or (m["layers"] if on_chip else 6)
        params = weights_ouro.ouro_params(m, seed)
        tokens, other = (rng.integers(1, m["vocab"], size=T).astype(np.int32)
                         for _ in range(2))
        exact = ouro_loop.make_forward(m)
        hs = exact.hidden(params, jnp.asarray(tokens), every_pass=True)
        hs2 = exact.hidden(params, jnp.asarray(other), every_pass=True)
        cos = [float(jnp.mean(jnp.sum(a[-LAST:] * b[-LAST:], -1)
                              / (jnp.linalg.norm(a[-LAST:], axis=-1)
                                 * jnp.linalg.norm(b[-LAST:], axis=-1))))
               for a, b in zip(hs, hs2)]
        x0 = params["emb"][jnp.asarray(tokens)].astype(jnp.float32)
        row = {"scales": over, "layers": m["layers"], "T": T,
               "cosine_of_unrelated_streams_by_pass": cos,
               "peak_softmax_weight": {
                   "pass1_layer0": peak_weight(m, params, x0, 0),
                   "pass2_layer0": peak_weight(m, params, hs[0], 0)},
               "exit_mass_by_pass": [float(v) for v in jnp.mean(
                   ouro_loop.exit_distribution(params, hs), 1)]}
        at = ouro_loop._head(exact, params, hs[-1][-LAST:])
        row["logit_std"] = float(jnp.std(at))
        for control in controls:
            kind = dict(precision=control) if control in \
                ouro_loop.PRECISIONS else dict(fault=control)
            judge = ouro_loop.make_forward(m, edge=edge, **kind)
            low = ouro_loop._head(judge, params, judge.hidden(
                params, jnp.asarray(tokens))[-LAST:])
            gaps = ouro_loop.gaps_below_best(at, jnp.argmax(low, -1))
            row[control] = {k: round(v, 4)
                            for k, v in checks.served(gaps).items()}
        print(json.dumps(row), flush=True)
        del params
    return 0


if __name__ == "__main__":
    from chipbench.reference import ouro_loop as _ref
    args = sys.argv[1:]
    words = [a for a in args if "=" not in a]
    named = ("layers", "T", "controls")
    opts = dict(a.split("=", 1) for a in args if a.split("=")[0] in named)
    sets = [{k: float(v) for k, v in (kv.split("=") for kv in a.split(","))}
            for a in args if "=" in a and a.split("=")[0] not in named]
    sys.exit(main(words[0] if words else "ouro26b_serve",
                  int(words[1]) if len(words) > 1 else 3700000001,
                  sets or [{}], int(opts.get("layers", 0)),
                  int(opts.get("T", 512)),
                  opts["controls"].split(",") if "controls" in opts
                  else ["bfloat16", "int8"] + list(_ref.FAULTS)))
