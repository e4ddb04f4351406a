#!/usr/bin/env python3
"""`compile_v5e.py` for a `serve_mamba_moe` configuration: the serving
programs of the state-space, sparse-expert decoder compiled at their real
size for a described (not attached) v5e chip, with this sandbox's TPU
compiler. Nothing runs; what the compiler refuses here costs no chip time.
Prints per program: compile seconds, argument / temporary / aliased bytes,
its kernels, how many results the size of an expert's matrix and of a whole
state leaf it writes outside the loops (the grouped matmul must read its
expert's weights where they lie, and a decode step must rewrite a state
leaf in place, not through a copy), and its largest results. With `--hlo
<dir>` the optimised HLO of each program is kept there with what runs under
each scope (`compile_v5e_glm.scopes`).

    JAX_PLATFORMS=cpu python3 chipbench/tests/compile_v5e_nemotron.py [--hlo dir] [config] [program ...]

Programs: `decode`, `prefill`, `chunk_prefill` (one program whatever the
extent), `reference` (one layer of each kind of the plain reference at the
longest request: their temporaries must fit beside the weights)."""
import collections
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, HERE]

from compile_v5e_glm import scopes, top_level_ops  # noqa: E402


def serving_programs(cfg, aval):
    """{name: (function, arguments as shapes)} of the engine's programs at
    the configuration's own shapes. `aval` makes a shape on the described
    chip."""
    from incubator_mxnet_tpu.models import mamba_moe_decoder as mm
    from incubator_mxnet_tpu.models.sparse_moe_decoder import _make_decode
    from chipbench import weights_nemotron
    m, e = cfg["model"], cfg["engine"]
    config = weights_nemotron.mamba_moe_config(m)
    model = mm.MambaMoEDecoder(config, params={})
    params = {k: aval(shape, "float32" if k in mm.FLOAT32_LEAVES
                      else m["dtype"])
              for k, (shape, _) in weights_nemotron.nemotron_shapes(m).items()}
    S, W, P = e["max_slots"], e["prefill_window"], e["prefill_lanes"]
    cache = {leaf.name: aval((S + 1,) + tuple(leaf.shape), leaf.dtype)
             for leaf in model.cache_spec()}
    i32 = "int32"
    lanes = [aval((P, W), i32), aval((P,), i32), aval((P,), i32)]
    return {
        "decode": (_make_decode(config, e["decode_steps"], None,
                                mm._make_micro(config), model.counters),
                   [params, cache, aval((S,), i32), aval((S,), i32),
                    aval((S,), i32), aval((S,), "float32"), aval((S,), i32),
                    aval((S,), "float32"), aval((S, 2), "uint32")]),
        "prefill": (mm._make_chunk(config, W, W, True),
                    [params, cache] + lanes),
        "chunk_prefill": (mm._make_chunk(config, W, m["max_len"], False),
                          [params, cache] + lanes + [aval((P,), i32)]),
    }


def main(name, only, hlo_dir):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from chipbench import harness, traffic, weights_nemotron
    from chipbench.paths import serve_mamba_moe
    from chipbench.reference import nemotron_h
    from incubator_mxnet_tpu.ops import fused

    # code that asks for the platform sees this sandbox's CPU: steer the
    # paged reads onto the kernel path they take on the chip
    fused._on_tpu = lambda: True
    bench = harness.Bench(ROOT)
    cfg = bench.config(name)
    m, e = cfg["model"], cfg["engine"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=chip)

    programs = serving_programs(cfg, aval)
    expert_bytes = 2 * m["embed"] * m["expert_hidden"]
    state_dims = ",".join(str(n) for n in (
        e["max_slots"] + 1, m["mamba_heads"], m["mamba_head_dim"],
        m["ssm_state"]))
    out = {}
    for pname, (fn, args) in programs.items():
        if only and pname not in only:
            continue
        t0 = time.perf_counter()
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        ops = list(top_level_ops(text))
        out[pname] = {
            "compile_s": round(time.perf_counter() - t0, 1),
            "argument_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "kernels": text.count("tpu_custom_call"),
            "fallbacks": fused.fused_stats()["fallback_calls"],
            # an expert's weights written anew: there should be none
            "expert_sized_results": sum(
                1 for _, kind, _, dims, nbytes, _ in ops
                if nbytes >= expert_bytes // 2 and dims.endswith(
                    f"{m['expert_hidden']},{m['embed']}")),
            # results the shape of a whole state leaf, by kind: a `fusion`
            # a Mamba layer (the rewrite in place) and no `copy`
            "state_sized_results": sorted(collections.Counter(
                kind for _, kind, _, dims, _, _ in ops
                if dims == state_dims).items()),
            "largest_results_mb": sorted(
                {f"{kind} {dtype}[{dims}]": round(nbytes / 1e6)
                 for _, kind, dtype, dims, nbytes, _ in ops
                 if nbytes >= 64e6}.items(), key=lambda kv: -kv[1])[:12]}
        print(pname, json.dumps(out[pname]), flush=True)
        if hlo_dir:
            os.makedirs(hlo_dir, exist_ok=True)
            with open(os.path.join(hlo_dir, pname + ".hlo.txt"), "w") as f:
                f.write(text)
            with open(os.path.join(hlo_dir, pname + ".scopes.json"),
                      "w") as f:
                json.dump(scopes(text), f, indent=1)
    if not only or "reference" in only:
        # the plain reference's three layers over the longest request
        tr = traffic.load(bench.find("traffic", "chat"))
        T = serve_mamba_moe.pad_to(tr)
        shapes = weights_nemotron.nemotron_shapes(m)
        forward = nemotron_h.make_forward(m)
        x = aval((T, m["embed"]), "float32")
        for kind, prefix, extra in (
                ("mamba", "m_", (aval((), "int32"), aval((), "int32"))),
                ("attn", "a_", ()), ("moe", ("r_", "s_"), ())):
            w = {n: aval(s[1:], "float32" if n in ("r_b", "m_dt_bias",
                                                   "m_A_log", "m_D")
                         else m["dtype"])
                 for n, (s, _) in shapes.items() if n.startswith(prefix)}
            w["norm"] = aval((m["embed"],), m["dtype"])
            t0 = time.perf_counter()
            mem = forward[2][kind].lower(x, w, *extra).compile() \
                .memory_analysis()
            out[f"reference_{kind}"] = {
                "positions": T,
                "compile_s": round(time.perf_counter() - t0, 1),
                "argument_bytes": mem.argument_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes}
            print(f"reference_{kind}",
                  json.dumps(out[f"reference_{kind}"]), flush=True)
    return out


if __name__ == "__main__":
    argv = sys.argv[1:]
    hlo = None
    if argv[:1] == ["--hlo"]:
        hlo, argv = argv[1], argv[2:]
    main(argv[0] if argv else "nemotron3nano_serve", argv[1:], hlo)
