#!/usr/bin/env python3
"""Rehearsal 3: the serving programs of a serve configuration compiled at
their real size for a described (not attached) v5e chip, with this
sandbox's TPU compiler. Nothing runs; what the compiler refuses here costs
no chip time. Prints per program: compile seconds, argument / temporary /
aliased bytes, and how many Pallas kernels the program holds.

    JAX_PLATFORMS=cpu python3 chipbench/tests/compile_v5e.py [config name]
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


def main(name):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from incubator_mxnet_tpu import serve
    from incubator_mxnet_tpu.ops import fused
    from incubator_mxnet_tpu.serve import continuous
    from chipbench import harness, weights

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

    dc = serve.DecoderConfig(vocab=m["vocab"], embed=m["embed"],
                             layers=m["layers"], heads=m["heads"],
                             head_dim=m["head_dim"],
                             mlp_hidden=m["mlp_hidden"],
                             max_len=m["max_len"], dtype=m["dtype"])
    params = {k: aval(shape, m["dtype"])
              for k, (shape, _) in weights.decoder_shapes(m).items()}
    S = e["max_slots"] + e["prefix_cache_slots"]
    slab = aval((S + 1, m["layers"], m["max_len"], m["heads"],
                 m["head_dim"]), e["kv_dtype"])
    W, P = e["prefill_window"], e["prefill_lanes"]
    i32 = "int32"
    programs = {
        "decode": (continuous._make_decode(dc, e["decode_steps"], None),
                   [params, slab, slab, aval((S,), i32), aval((S,), i32),
                    aval((S,), i32), aval((S,), "float32"), aval((S,), i32),
                    aval((S,), "float32"), aval((S, 2), "uint32")], (1, 2)),
        "chunk_prefill_full_extent": (
            continuous._make_chunk_prefill(dc, W, m["max_len"]),
            [params, slab, slab, aval((S, W), i32), aval((S,), i32),
             aval((S,), i32)], (1, 2)),
        "prefill": (continuous._make_prefill(dc, W),
                    [params, slab, slab, aval((P, W), i32), aval((P,), i32),
                     aval((P,), i32)], (1, 2)),
    }
    out = {}
    for pname, (fn, args, donate) in programs.items():
        t0 = time.perf_counter()
        compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
        mem = compiled.memory_analysis()
        out[pname] = {
            "compile_s": round(time.perf_counter() - t0, 1),
            "argument_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "kernels": compiled.as_text().count("tpu_custom_call")}
        print(pname, json.dumps(out[pname]), flush=True)
    return out


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "cgpt13b_serve")
