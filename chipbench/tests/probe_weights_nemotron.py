#!/usr/bin/env python3
"""Is the Mamba-2 state visible at the configuration's weights? At full
width, on the CPU, through the first layers of the configuration's own
pattern with its own scales (`weights_nemotron`), over one random prompt:
for each Mamba layer, the rms of the scan's term S_t C_t beside the skip
term D x_t of y_t, how far back the state reaches (the decay's half-lives,
in positions), and the rms of what each layer adds to the stream beside the
stream's. Uses the plain reference's layers to carry the stream from layer
to layer; nothing of the program.

    JAX_PLATFORMS=cpu python3 chipbench/tests/probe_weights_nemotron.py \
        [layers=8] [positions=512] [seed=7]
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT]


def main(layers=8, positions=512, seed=7):
    import numpy as np
    import jax
    import jax.numpy as jnp
    from chipbench import harness, weights_nemotron
    from chipbench.reference import nemotron_h
    from chipbench.reference.glm_dsa import _rmsnorm
    from chipbench.reference.sambay import _silu
    cfg = harness.Bench(ROOT).config("nemotron3nano_serve")
    m = dict(cfg["model"], pattern=cfg["model"]["pattern"][:layers],
             dtype="float32")
    params = weights_nemotron.nemotron_params(m, seed)
    forward = nemotron_h.make_forward(m)
    H, P, G, N = m["mamba_heads"], m["mamba_head_dim"], m["n_groups"], \
        m["ssm_state"]
    Di, GN = H * P, G * N
    tokens = np.random.default_rng(seed).integers(1, m["vocab"], positions)
    x = params["emb"][jnp.asarray(tokens)].astype(jnp.float32)

    @jax.jit
    def split(x, w):
        """(rms of S C, rms of D x, the dt of every position and head)."""
        T = x.shape[0]
        h = _rmsnorm(x, w["norm"], m["norm_eps"])
        zxd = jnp.matmul(h, w["m_in"], precision="highest")
        xbc = zxd[:, Di:2 * Di + 2 * GN]
        conv = w["m_conv_b"] + sum(
            jnp.concatenate([jnp.zeros((3 - i, xbc.shape[1])),
                             xbc[:T - 3 + i]], 0) * w["m_conv"][i]
            for i in range(4))
        xbc = _silu(conv)
        xs = xbc[:, :Di].reshape(T, H, P)
        group = jnp.arange(H) // (H // G)
        B = xbc[:, Di:Di + GN].reshape(T, G, N)[:, group]
        C = xbc[:, Di + GN:].reshape(T, G, N)[:, group]
        dt = jax.nn.softplus(zxd[:, 2 * Di + 2 * GN:] + w["m_dt_bias"])
        A = -jnp.exp(w["m_A_log"])

        def step(s, a):
            x_t, dt_t, B_t, C_t = a
            s = jnp.exp(dt_t * A)[:, None, None] * s \
                + (dt_t[:, None] * x_t)[..., None] * B_t[:, None, :]
            return s, jnp.sum(s * C_t[:, None, :], -1)

        _, y = jax.lax.scan(step, jnp.zeros((H, P, N)), (xs, dt, B, C))
        skip = w["m_D"][:, None] * xs
        return jnp.sqrt(jnp.mean(y * y)), jnp.sqrt(jnp.mean(skip * skip)), \
            dt * -A

    rows = []
    for l, (kind, i) in enumerate(nemotron_h.layer_slots(m)):
        before = x
        if kind == "moe":
            h, g, y = forward[2]["moe"](x, {
                "norm": params["norm"][l],
                **{n: params[n][i] for n in ("r_w", "r_b", "s_up", "s_down")}})
            for e in range(m["held_count"]):
                r = jnp.maximum(h @ params["e_up"][i, e].T, 0)
                y = y + g[:, e:e + 1] * ((r * r) @ params["e_down"][i, e])
            x = x + y
            row = {}
        else:
            w = {n: a[i] for n, a in params.items()
                 if n.startswith("m_" if kind == "mamba" else "a_")}
            w["norm"] = params["norm"][l]
            if kind == "mamba":
                scan, skip, rate = split(x, w)
                half = np.log(2) / np.asarray(rate)
                row = {"scan_rms": float(scan), "skip_rms": float(skip),
                       "scan_over_skip": float(scan / skip),
                       "half_life_p10_p50_p90": [
                           round(float(q), 2) for q in
                           np.percentile(half, [10, 50, 90])]}
                x = forward[2]["mamba"](x, w, positions, positions)[0]
            else:
                x = forward[2]["attn"](x, w)
                row = {}
        add = x - before
        row.update(layer=l, kind=kind,
                   adds_rms=float(jnp.sqrt(jnp.mean(add * add))),
                   stream_rms=float(jnp.sqrt(jnp.mean(before * before))))
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


if __name__ == "__main__":
    kw = dict(a.split("=") for a in sys.argv[1:])
    main(**{k: int(v) for k, v in kw.items()})
