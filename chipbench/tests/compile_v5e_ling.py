#!/usr/bin/env python3
"""`compile_v5e.py` for a `serve_delta_moe` configuration: the serving
programs of the linear-attention, latent-attention, sparse-expert decoder
compiled at their real size for a described (not attached) v5e chip, with
this sandbox's TPU compiler. Nothing runs; what the compiler refuses here
costs no chip time. Prints per program: compile seconds, argument /
temporary / aliased bytes, its kernels, how many results the size of an
expert's matrix and of a whole state leaf it writes outside the loops
(the grouped matmul must read its expert's weights where they lie, and a
decode step must rewrite a state leaf in place, not through a copy), and
its largest results. With `--hlo <dir>` the optimised HLO of each program
is kept there with what runs under each scope, for the person who writes a
metric's name pattern (`compile_v5e_glm.scopes`).

    JAX_PLATFORMS=cpu python3 chipbench/tests/compile_v5e_ling.py [--hlo dir] [config] [program ...]

Programs: `decode`, `prefill`, `chunk_prefill@<extent>` (default: the
engine's extent ladder), `reference` (one KDA layer and the MLA layer of
the plain reference at the longest request: their temporaries must fit
beside the weights)."""
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
    the configuration's own shapes: `decode`, `prefill` and one
    `chunk_prefill@<extent>` a rung of the engine's extent ladder. `aval`
    makes a shape on the described chip."""
    from incubator_mxnet_tpu.models import delta_moe_decoder as dm
    from incubator_mxnet_tpu.models.sparse_moe_decoder import _make_decode
    from chipbench import weights_ling
    m, e = cfg["model"], cfg["engine"]
    config = weights_ling.delta_moe_config(m)
    model = dm.DeltaMoEDecoder(config, params={})
    params = {k: aval(shape, "float32" if k in dm.FLOAT32_LEAVES
                      else m["dtype"])
              for k, (shape, _) in weights_ling.ling_shapes(m).items()}
    S, W, P = e["max_slots"], e["prefill_window"], e["prefill_lanes"]
    cache = {leaf.name: aval((S + 1,) + tuple(leaf.shape), leaf.dtype)
             for leaf in model.cache_spec()}
    i32 = "int32"
    lanes = [aval((P, W), i32), aval((P,), i32), aval((P,), i32)]
    programs = {
        "decode": (_make_decode(config, e["decode_steps"], None,
                                dm._make_micro(config), model.counters),
                   [params, cache, aval((S,), i32), aval((S,), i32),
                    aval((S,), i32), aval((S,), "float32"), aval((S,), i32),
                    aval((S,), "float32"), aval((S, 2), "uint32")]),
        "prefill": (dm._make_chunk(config, W, W, True),
                    [params, cache] + lanes),
    }
    ext = 2 * W
    while True:
        ext = min(ext, m["max_len"])
        programs[f"chunk_prefill@{ext}"] = (
            dm._make_chunk(config, W, ext, False),
            [params, cache] + lanes + [aval((P,), i32)])
        if ext == m["max_len"]:
            return programs
        ext *= 2


def main(name, only, hlo_dir):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from chipbench import harness, traffic, weights_ling
    from chipbench.paths import serve_delta_moe
    from chipbench.reference import ling_kda
    from incubator_mxnet_tpu.ops import fused

    # code that asks for the platform sees this sandbox's CPU: steer the
    # dense latent read onto the kernel path it takes on the chip
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
    expert_bytes = 2 * m["embed"] * 2 * m["expert_hidden"]
    state_dims = ",".join(str(n) for n in (
        e["max_slots"] + 1, m["heads"], m["head_dim"], m["head_dim"]))
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
            # an expert's weights written anew (a slice or a layout change
            # that the matmul did not take in): there should be none
            "expert_sized_results": sum(
                1 for _, kind, _, dims, nbytes, _ in ops
                if nbytes >= expert_bytes // 2 and dims.endswith(
                    (f"{m['embed']},{2 * m['expert_hidden']}",
                     f"{m['expert_hidden']},{m['embed']}"))),
            # results the shape of a whole state leaf, by kind: a `fusion`
            # a KDA layer (the rewrite in place) and no `copy`
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
        # the plain reference's two mixers over the longest request
        tr = traffic.load(bench.find("traffic", "longgen"))
        T = serve_delta_moe.pad_to(tr)
        shapes = weights_ling.ling_shapes(m)
        forward = ling_kda.make_forward(m)
        for kind, prefix in (("kda", "k_"), ("mla", "m_")):
            w = {n: aval(s[1:], "float32" if n in ("k_A", "k_bf")
                         else m["dtype"])
                 for n, (s, _) in shapes.items() if n.startswith(prefix)}
            w["ln1_w"] = aval((m["embed"],), m["dtype"])
            x = aval((T, m["embed"]), "float32")
            t0 = time.perf_counter()
            layer = forward[2][kind]
            args = (x, w, aval((), "int32"), aval((), "int32")) \
                if kind == "kda" else (x, w)
            mem = layer.lower(*args).compile().memory_analysis()
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
    main(argv[0] if argv else "ling3f_serve", argv[1:], hlo)
