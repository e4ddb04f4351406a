#!/usr/bin/env python3
"""`compile_v5e.py` for a `serve_hybrid` configuration: the three serving
programs of the hybrid decoder compiled at their real size for a described
(not attached) v5e chip, with this sandbox's TPU compiler. Nothing runs;
what the compiler refuses here costs no chip time. Prints per program:
compile seconds, argument / temporary / aliased bytes, how many Pallas
kernels the program holds, and how many copies or slices it makes of a
whole ring (PR 27's first traced run found sixteen a micro-step; 0 since).

    JAX_PLATFORMS=cpu python3 chipbench/tests/compile_v5e_hybrid.py [config] [program ...]
"""
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(name, only):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from incubator_mxnet_tpu.models import hybrid_decoder as hd
    from incubator_mxnet_tpu.ops import fused
    from chipbench import harness, weights_sambay

    cfg = harness.Bench(ROOT).config(name)
    m, e = cfg["model"], cfg["engine"]
    # the program asks `tpu_platform_available()` and would take its CPU
    # branch here: steer it in this script, not through an option
    fused._on_tpu = lambda: True
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=chip)

    config = weights_sambay.hybrid_config(m)
    model = hd.HybridDecoder(config, params={})
    params = {k: aval(shape, m["dtype"])
              for k, (shape, _) in weights_sambay.sambay_shapes(m).items()}
    S, W, P = e["max_slots"], e["prefill_window"], e["prefill_lanes"]
    cache = {leaf.name: aval((S + 1,) + tuple(leaf.shape), leaf.dtype)
             for leaf in model.cache_spec()}
    i32 = "int32"
    programs = {
        "decode": (hd._make_decode(config, e["decode_steps"], None),
                   [params, cache, aval((S,), i32), aval((S,), i32),
                    aval((S,), i32), aval((S,), "float32"), aval((S,), i32),
                    aval((S,), "float32"), aval((S, 2), "uint32")]),
        "chunk_prefill": (hd._make_chunk(config, W, False),
                          [params, cache, aval((P, W), i32), aval((P,), i32),
                           aval((P,), i32), aval((P,), i32)]),
        "prefill": (hd._make_chunk(config, W, True),
                    [params, cache, aval((P, W), i32), aval((P,), i32),
                     aval((P,), i32)]),
    }
    out = {}
    for pname, (fn, args) in programs.items():
        if only and pname not in only:
            continue
        t0 = time.perf_counter()
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
        mem = compiled.memory_analysis()
        out[pname] = {
            "compile_s": round(time.perf_counter() - t0, 1),
            "argument_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "kernels": compiled.as_text().count("tpu_custom_call"),
            "ring_sized_copies": sum(
                line.count(" copy(") + line.count(" slice(")
                for line in compiled.as_text().splitlines()
                if f"[{S},{m['window']},{cache['shared_k'].shape[2]}]"
                in line.split("=")[0]),
            "fallbacks": fused.fused_stats()["fallback_calls"]}
        print(pname, json.dumps(out[pname]), flush=True)
    return out


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "phi4mf_serve", sys.argv[2:])
