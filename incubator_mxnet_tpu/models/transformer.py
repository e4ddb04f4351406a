"""Transformer language model — TPU-first flagship.

Pure functional JAX (params as a pytree) so the full training step compiles
to ONE XLA computation over a `jax.sharding.Mesh`. Parallelism follows the
scaling-book recipe: name mesh axes (dp/tp/sp), annotate parameter and
activation shardings, let GSPMD insert the collectives (all-gather along tp
for the attention/MLP matmuls, psum for gradient reduction along dp,
all-to-all/collective-permute along sp for sequence-parallel attention).

Reference contrast: MXNet's only attention kernels are the fused CUDA
interleaved_matmul ops (src/operator/contrib/transformer.cc:676-869) with NO
tensor/sequence parallelism anywhere (SURVEY §2.3). This module is the
green-field replacement: the same BERT-class capability, sharded natively.

Sharding plan (Megatron-style TP + sequence sharding):
  embedding  (V, D)    -> P('tp', None)       row-parallel vocab
  attn qkv   (D, 3D)   -> P(None, 'tp')       column parallel
  attn out   (D, D)    -> P('tp', None)       row parallel
  mlp in     (D, F)    -> P(None, 'tp')
  mlp out    (F, D)    -> P('tp', None)
  activations (B, T, D)-> P('dp', 'sp', None)
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as _np

__all__ = ["TransformerConfig", "init_params", "forward", "loss_fn",
           "make_train_step", "param_shardings", "TransformerLM",
           "stack_pipeline_params", "make_pipeline_train_step",
           "init_opt_state"]


@dataclass
class TransformerConfig:
    vocab_size: int = 32000
    num_layers: int = 12
    d_model: int = 768
    num_heads: int = 12
    d_ff: int = 3072
    max_seq_len: int = 2048
    dtype: str = "bfloat16"
    use_ring_attention: bool = False  # ring attention over 'sp' (shard_map)
    ring_flash: bool = False          # flash kernels per ring hop (TPU)
    tie_embeddings: bool = True
    # Mixture-of-experts FFN (0 = dense MLP). In a sharded step the experts
    # live one-per-rank along `ep_axis` (DeepSpeed-MoE style co-location on
    # the data-parallel axis), so num_experts must equal that axis size.
    num_experts: int = 0
    ep_axis: str = "dp"
    moe_capacity_factor: float = 2.0
    moe_aux_weight: float = 0.01


def _dtype(cfg):
    import jax.numpy as jnp
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[cfg.dtype]


def init_params(key, cfg: TransformerConfig):
    """Initialize the parameter pytree (all fp32 masters; cast at use)."""
    import jax
    import jax.numpy as jnp
    keys = jax.random.split(key, cfg.num_layers + 2)
    d, f, v = cfg.d_model, cfg.d_ff, cfg.vocab_size

    def dense_init(k, shape, scale=None):
        scale = scale or (1.0 / math.sqrt(shape[0]))
        return jax.random.normal(k, shape, jnp.float32) * scale

    params = {
        "embedding": dense_init(keys[0], (v, d), scale=0.02),
        "pos_embedding": dense_init(keys[1], (cfg.max_seq_len, d),
                                    scale=0.02),
        "final_ln_scale": jnp.ones((d,), jnp.float32),
        "layers": [],
    }
    for i in range(cfg.num_layers):
        lk = jax.random.split(keys[2 + i], 5)
        layer = {
            "ln1_scale": jnp.ones((d,), jnp.float32),
            "ln2_scale": jnp.ones((d,), jnp.float32),
            "qkv": dense_init(lk[0], (d, 3 * d)),
            "attn_out": dense_init(lk[1], (d, d),
                                   scale=1.0 / math.sqrt(d * 2 * cfg.num_layers)),
        }
        if cfg.num_experts > 0:
            E = cfg.num_experts
            out_scale = 1.0 / math.sqrt(f * 2 * cfg.num_layers)
            ek_in = jax.random.split(lk[2], E)
            ek_out = jax.random.split(lk[3], E)
            layer["gate"] = dense_init(lk[4], (d, E), scale=0.02)
            layer["mlp_in"] = jnp.stack(
                [dense_init(ek_in[e], (d, f)) for e in range(E)])
            layer["mlp_out"] = jnp.stack(
                [dense_init(ek_out[e], (f, d), scale=out_scale)
                 for e in range(E)])
        else:
            layer["mlp_in"] = dense_init(lk[2], (d, f))
            layer["mlp_out"] = dense_init(
                lk[3], (f, d), scale=1.0 / math.sqrt(f * 2 * cfg.num_layers))
        params["layers"].append(layer)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[1], (d, v), scale=0.02)
    return params


def param_shardings(cfg: TransformerConfig, mesh):
    """PartitionSpec pytree matching init_params (see module docstring)."""
    from jax.sharding import PartitionSpec as P
    layer = {
        "ln1_scale": P(), "ln2_scale": P(),
        "qkv": P(None, "tp"),
        "attn_out": P("tp", None),
    }
    if cfg.num_experts > 0:
        # one expert per ep_axis rank; expert FFN weights replicated over tp
        # (the MoE shard_map body keeps expert matmuls rank-local)
        layer["gate"] = P()
        layer["mlp_in"] = P(cfg.ep_axis, None, None)
        layer["mlp_out"] = P(cfg.ep_axis, None, None)
    else:
        layer["mlp_in"] = P(None, "tp")
        layer["mlp_out"] = P("tp", None)
    specs = {
        "embedding": P("tp", None),
        "pos_embedding": P(),
        "final_ln_scale": P(),
        "layers": [dict(layer) for _ in range(cfg.num_layers)],
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, "tp")
    return specs


def _rms_norm(x, scale, eps=1e-6):
    import jax.numpy as jnp
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax_rsqrt(var + eps)).astype(x.dtype) * scale


def jax_rsqrt(x):
    import jax
    return jax.lax.rsqrt(x)


def _use_ring(cfg, mesh):
    return (cfg.use_ring_attention and mesh is not None
            and "sp" in mesh.axis_names and mesh.shape["sp"] > 1)


def _attention(x, layer, cfg, mask=None, mesh=None):
    import jax
    import jax.numpy as jnp
    B, T, D = x.shape
    H = cfg.num_heads
    hd = D // H
    qkv = jnp.einsum("btd,de->bte", x, layer["qkv"].astype(x.dtype))
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, T, H, hd).transpose(0, 2, 1, 3)
    k = k.reshape(B, T, H, hd).transpose(0, 2, 1, 3)
    v = v.reshape(B, T, H, hd).transpose(0, 2, 1, 3)
    if _use_ring(cfg, mesh):
        # Sequence parallelism: the time axis stays sharded over 'sp'; k/v
        # shards rotate the ring via ppermute (ICI neighbor links) while each
        # rank accumulates online-softmax attention against its local q.
        # Heads ride 'tp' (column-parallel qkv), batch rides 'dp'.
        from jax.sharding import PartitionSpec as P
        from ..parallel import shard_map as _shard_map
        from ..parallel.ring import ring_attention

        spec = P("dp", "tp", "sp", None)
        o = _shard_map(
            lambda q_, k_, v_: ring_attention(
                q_, k_, v_, axis_name="sp", causal=True,
                use_flash=cfg.ring_flash),
            mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)(q, k, v)
    else:
        from ..ops import nn as _nn
        o = _nn.scaled_dot_product_attention(q, k, v, causal=True)
    o = o.transpose(0, 2, 1, 3).reshape(B, T, D)
    return jnp.einsum("btd,de->bte", o, layer["attn_out"].astype(x.dtype))


def _mlp(x, layer):
    import jax
    import jax.numpy as jnp
    h = jnp.einsum("btd,df->btf", x, layer["mlp_in"].astype(x.dtype))
    h = jax.nn.gelu(h)
    return jnp.einsum("btf,fd->btd", h, layer["mlp_out"].astype(x.dtype))


def _moe_mlp_dense(x, layer, cfg):
    """Single-device MoE reference: top-1 routing, no capacity drops.

    Numerically equals the sharded all-to-all dispatch whenever capacity is
    not exceeded (moe_dispatch's overflow rule passes tokens through).
    """
    import jax
    import jax.numpy as jnp
    probs = jax.nn.softmax(
        jnp.einsum("btd,de->bte", x.astype(jnp.float32),
                   layer["gate"].astype(jnp.float32)), axis=-1)
    eidx = jnp.argmax(probs, axis=-1)                       # (B, T)
    gate = jnp.take_along_axis(probs, eidx[..., None], -1)[..., 0]
    # every expert over every token, then select (fine at test scale; the
    # sharded path is the production one)
    h = jnp.einsum("btd,edf->betf", x, layer["mlp_in"].astype(x.dtype))
    h = jax.nn.gelu(h)
    y_all = jnp.einsum("betf,efd->betd", h, layer["mlp_out"].astype(x.dtype))
    onehot = jax.nn.one_hot(eidx, cfg.num_experts, dtype=x.dtype)  # (B,T,E)
    y = jnp.einsum("betd,bte->btd", y_all, onehot)
    E = cfg.num_experts
    frac_tokens = jnp.mean(
        jax.nn.one_hot(eidx, E, dtype=jnp.float32), axis=(0, 1))
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(frac_tokens * frac_probs)
    return gate[..., None].astype(x.dtype) * y, aux


def _moe_mlp(x, layer, cfg, mesh=None):
    """MoE FFN: all-to-all dispatch over `cfg.ep_axis` when sharded, dense
    reference path otherwise. Returns (y, aux_loss)."""
    import jax
    import jax.numpy as jnp

    if (mesh is None or cfg.ep_axis not in mesh.axis_names
            or mesh.shape[cfg.ep_axis] == 1):
        return _moe_mlp_dense(x, layer, cfg)

    E = cfg.num_experts
    if mesh.shape[cfg.ep_axis] != E:
        raise ValueError(
            f"num_experts={E} must equal mesh axis {cfg.ep_axis!r} size "
            f"{mesh.shape[cfg.ep_axis]} (one expert per rank)")
    from jax.sharding import PartitionSpec as P
    from ..parallel import shard_map as _shard_map
    from ..parallel.moe import moe_dispatch

    ep = cfg.ep_axis
    B, T, D = x.shape
    t_local = T // mesh.shape.get("sp", 1) if "sp" in mesh.axis_names else T
    b_local = B // mesh.shape[ep]
    cap = max(int(cfg.moe_capacity_factor * b_local * t_local / E), 1)

    def body(x_loc, gate_w, w_in, w_out):
        bl, tl, _ = x_loc.shape
        flat = x_loc.reshape(bl * tl, D)
        logits = flat.astype(jnp.float32) @ gate_w.astype(jnp.float32)
        w_in_l, w_out_l = w_in[0], w_out[0]   # this rank's expert

        def expert_fn(toks):
            h = jax.nn.gelu(toks @ w_in_l.astype(toks.dtype))
            return h @ w_out_l.astype(toks.dtype)

        # average the load fractions over every token-sharded axis (ep and
        # sp; tp holds replicas so it's a no-op) BEFORE the nonlinear aux
        # product -> the Switch eq.4 objective over the global batch, and
        # the scalar comes out replicated so out_spec P() is sound
        stats = tuple(ax for ax in mesh.axis_names)
        y, aux = moe_dispatch(flat, logits, expert_fn, axis_name=ep,
                              capacity=cap, stats_axes=stats)
        return y.reshape(bl, tl, D), aux

    act_spec = (P(ep, "sp", None) if "sp" in mesh.axis_names
                else P(ep, None, None))
    y, aux = _shard_map(
        body, mesh,
        in_specs=(act_spec, P(), P(ep, None, None), P(ep, None, None)),
        out_specs=(act_spec, P()), check_vma=False)(
            x, layer["gate"], layer["mlp_in"], layer["mlp_out"])
    return y, aux


def forward(params, tokens, cfg: TransformerConfig, mesh=None,
            return_aux=False):
    """tokens (B, T) int32 -> logits (B, T, V) [, moe aux loss scalar]."""
    import jax
    import jax.numpy as jnp
    mesh = getattr(mesh, "jax_mesh", mesh)  # accept parallel.Mesh or jax Mesh
    dt = _dtype(cfg)
    B, T = tokens.shape
    x = params["embedding"].astype(dt)[tokens]
    x = x + params["pos_embedding"].astype(dt)[:T][None]
    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        x = jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(mesh, P("dp", "sp", None)))
    aux_total = jnp.zeros((), jnp.float32)
    for layer in params["layers"]:
        h = _rms_norm(x, layer["ln1_scale"].astype(dt))
        x = x + _attention(h, layer, cfg, mesh=mesh)
        h = _rms_norm(x, layer["ln2_scale"].astype(dt))
        if cfg.num_experts > 0:
            y, aux = _moe_mlp(h, layer, cfg, mesh)
            aux_total = aux_total + aux.astype(jnp.float32)
            x = x + y
        else:
            x = x + _mlp(h, layer)
        if mesh is not None:
            from jax.sharding import PartitionSpec as P
            x = jax.lax.with_sharding_constraint(
                x, jax.sharding.NamedSharding(mesh, P("dp", "sp", None)))
    x = _rms_norm(x, params["final_ln_scale"].astype(dt))
    head = (params["embedding"].T if cfg.tie_embeddings
            else params["lm_head"]).astype(dt)
    logits = jnp.einsum("btd,dv->btv", x, head)
    if return_aux:
        return logits, aux_total
    return logits


def loss_fn(params, batch, cfg: TransformerConfig, mesh=None):
    """Next-token cross-entropy (+ MoE load-balance aux when configured).
    batch: {tokens (B,T+1)}."""
    import jax
    import jax.numpy as jnp
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    logits, aux = forward(params, inputs, cfg, mesh, return_aux=True)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    ce = jnp.mean(logz - gold)
    if cfg.num_experts > 0:
        return ce + cfg.moe_aux_weight * aux
    return ce


def _adamw_update(params, grads, opt_state, t, learning_rate, weight_decay,
                  b1, b2, eps):
    """Bias-corrected AdamW over a pytree (shared by both step builders)."""
    import jax
    import jax.numpy as jnp
    mu, nu = opt_state

    def upd(p, g, m, v):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mhat = m / (1 - b1 ** t.astype(jnp.float32))
        vhat = v / (1 - b2 ** t.astype(jnp.float32))
        p = p - learning_rate * (mhat / (jnp.sqrt(vhat) + eps)
                                 + weight_decay * p)
        return p, m, v

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    out = [upd(p, g, m, v) for p, g, m, v in
           zip(flat_p, jax.tree_util.tree_leaves(grads),
               jax.tree_util.tree_leaves(mu),
               jax.tree_util.tree_leaves(nu))]
    new_p = jax.tree_util.tree_unflatten(treedef, [o[0] for o in out])
    new_m = jax.tree_util.tree_unflatten(treedef, [o[1] for o in out])
    new_v = jax.tree_util.tree_unflatten(treedef, [o[2] for o in out])
    return new_p, (new_m, new_v)


def make_train_step(cfg: TransformerConfig, mesh=None, learning_rate=3e-4,
                    weight_decay=0.01, b1=0.9, b2=0.95, eps=1e-8):
    """Build a jitted AdamW train step: (params, opt_state, batch, step)
    -> (params, opt_state, loss). With a mesh, params/batch shardings are
    applied and gradient psum over dp is inserted by GSPMD automatically."""
    import jax

    def step_fn(params, opt_state, batch, step):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg, mesh))(params)
        new_p, new_opt = _adamw_update(params, grads, opt_state, step + 1,
                                       learning_rate, weight_decay, b1, b2,
                                       eps)
        return new_p, new_opt, loss

    if mesh is None:
        return jax.jit(step_fn, donate_argnums=(0, 1))

    from jax.sharding import NamedSharding, PartitionSpec as P
    pspecs = param_shardings(cfg, mesh)
    p_shard = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), pspecs,
        is_leaf=lambda x: isinstance(x, P))
    batch_shard = {"tokens": NamedSharding(mesh, P("dp", None))}
    step_shard = NamedSharding(mesh, P())
    return jax.jit(step_fn,
                   in_shardings=(p_shard, (p_shard, p_shard), batch_shard,
                                 step_shard),
                   out_shardings=(p_shard, (p_shard, p_shard), step_shard),
                   donate_argnums=(0, 1))


def stack_pipeline_params(params, cfg: TransformerConfig, num_stages):
    """Restack per-layer param dicts into stage-major stacked leaves.

    layers[i][k] of shape s  ->  stacked[k] of shape (S, L/S, *s), ready to
    shard P('pp', ...) so each pipeline rank holds its stage's L/S layers.
    Embedding/head/final-norm are copied (not aliased): the pipeline step
    donates its inputs, and a donated alias would silently invalidate the
    caller's original params.
    """
    import jax.numpy as jnp
    L = cfg.num_layers
    if L % num_stages:
        raise ValueError(f"num_layers={L} not divisible by pp={num_stages}")
    keys = params["layers"][0].keys()
    stacked = {k: jnp.stack([params["layers"][i][k] for i in range(L)])
               .reshape((num_stages, L // num_stages)
                        + params["layers"][0][k].shape)
               for k in keys}
    out = {k: jnp.array(v, copy=True) for k, v in params.items()
           if k != "layers"}
    out["layers"] = stacked
    return out


def make_pipeline_train_step(cfg: TransformerConfig, mesh, num_microbatches,
                             learning_rate=3e-4, weight_decay=0.01,
                             b1=0.9, b2=0.95, eps=1e-8):
    """GPipe pipeline-parallel AdamW train step over a ('pp','dp') mesh.

    Params must be in stacked form (stack_pipeline_params). Each pp rank
    holds L/S contiguous layers; microbatches stream around the ring via
    ppermute (parallel/pipeline.py) and the whole fwd+bwd+update compiles to
    one XLA program. Differentiable through the schedule: ppermute's
    transpose runs the reverse ring, so backward is pipelined too.

    Green-field vs the reference: MXNet has no pipeline parallelism at all
    (SURVEY §2.3); its closest analogue is manual per-device placement.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..parallel import shard_map as _shard_map
    from ..parallel.pipeline import pipeline_apply

    jmesh = getattr(mesh, "jax_mesh", mesh)
    S = jmesh.shape["pp"]
    dp = jmesh.shape["dp"]
    M = num_microbatches
    dt = _dtype(cfg)
    if cfg.num_experts > 0 or cfg.use_ring_attention:
        raise ValueError("pipeline step composes with dp only (attention/"
                         "FFN run rank-local inside each stage)")

    def stage_fn(stage_layers, x):
        # stage_layers leaves: (L/S, ...) — scan over this stage's layers
        def body(h, lp):
            h = h + _attention(_rms_norm(h, lp["ln1_scale"].astype(dt)),
                               lp, cfg)
            h = h + _mlp(_rms_norm(h, lp["ln2_scale"].astype(dt)), lp)
            return h, None
        x, _ = jax.lax.scan(body, x, stage_layers)
        return x

    def local_loss(params, tokens):
        # tokens: (B_local, T+1) — this dp rank's shard, replicated over pp
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        B, T = inputs.shape
        x = params["embedding"].astype(dt)[inputs]
        x = x + params["pos_embedding"].astype(dt)[:T][None]
        x = x.reshape((M, B // M, T, cfg.d_model))
        stage_layers = jax.tree_util.tree_map(lambda a: a[0],
                                              params["layers"])
        y = pipeline_apply(lambda w, h: stage_fn(w, h), stage_layers, x,
                           axis_name="pp")
        # outputs are banked on the last pp rank, zeros elsewhere -> psum
        # broadcasts them to every rank
        y = jax.lax.psum(y, "pp")
        x = _rms_norm(y.reshape(B, T, cfg.d_model),
                      params["final_ln_scale"].astype(dt))
        head = (params["embedding"].T if cfg.tie_embeddings
                else params["lm_head"]).astype(dt)
        logits = jnp.einsum("btd,dv->btv", x, head).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
        # pmean over 'pp' too: every pp rank recomputes the same head/loss
        # (redundant but tiny), and the 1/S in the pmean's transpose cancels
        # the S-way psum of cotangents into the replicated embedding/head —
        # without it those grads would be S× overcounted
        return jax.lax.pmean(jnp.mean(logz - gold), ("dp", "pp"))

    rep = P()  # replicated leaves (embedding/head/norm)
    stage = {k: P("pp") for k in ("ln1_scale", "ln2_scale", "qkv",
                                  "attn_out", "mlp_in", "mlp_out")}
    pspec = {"embedding": rep, "pos_embedding": rep, "final_ln_scale": rep,
             "layers": stage}
    if not cfg.tie_embeddings:
        pspec["lm_head"] = rep

    sharded_loss = _shard_map(
        local_loss, jmesh, in_specs=(pspec, P("dp", None)), out_specs=P(),
        check_vma=False)

    def step_fn(params, opt_state, batch, step):
        loss, grads = jax.value_and_grad(
            lambda p: sharded_loss(p, batch["tokens"]))(params)
        new_p, new_opt = _adamw_update(params, grads, opt_state, step + 1,
                                       learning_rate, weight_decay, b1, b2,
                                       eps)
        return new_p, new_opt, loss

    shard_of = jax.tree_util.tree_map(
        lambda s: NamedSharding(jmesh, s), pspec,
        is_leaf=lambda x: isinstance(x, P))
    batch_shard = {"tokens": NamedSharding(jmesh, P("dp", None))}
    scalar = NamedSharding(jmesh, P())
    return jax.jit(step_fn,
                   in_shardings=(shard_of, (shard_of, shard_of), batch_shard,
                                 scalar),
                   out_shardings=(shard_of, (shard_of, shard_of), scalar),
                   donate_argnums=(0, 1))


def init_opt_state(params):
    import jax
    import jax.numpy as jnp
    zeros = jax.tree_util.tree_map(lambda p: jnp.zeros_like(p), params)
    return (zeros, jax.tree_util.tree_map(lambda p: jnp.zeros_like(p), params))


class TransformerLM:
    """Object wrapper tying config+params together (gluon-style ergonomics
    over the functional core)."""

    def __init__(self, cfg: TransformerConfig = None, **kwargs):
        self.cfg = cfg or TransformerConfig(**kwargs)
        self.params = None

    def initialize(self, seed=0):
        import jax
        self.params = init_params(jax.random.PRNGKey(seed), self.cfg)
        return self

    def __call__(self, tokens):
        from ..ndarray import NDArray, _wrap
        raw = tokens._arr if isinstance(tokens, NDArray) else tokens
        return _wrap(forward(self.params, raw, self.cfg))
