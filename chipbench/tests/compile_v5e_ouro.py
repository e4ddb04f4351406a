#!/usr/bin/env python3
"""`compile_v5e.py` for a `serve_looped` configuration: the three serving
programs of the looped decoder compiled at their real size for a described
(not attached) v5e chip, with this sandbox's TPU compiler. Nothing runs;
what the compiler refuses here costs no chip time. Prints per program:
compile seconds, argument / temporary / aliased bytes, how many Pallas
kernels the program holds (`layers`, not `layers * ut_steps`: the pass is a
loop in the program), how many `slice` or `copy` instructions give a
result as large as one layer's K or V leaf (PR 36's lesson: 0), and the
fallbacks counted while tracing.

    JAX_PLATFORMS=cpu python3 chipbench/tests/compile_v5e_ouro.py [config] [program ...] [--hlo DIR]
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


def serving_programs(cfg, aval):
    """{program: (function, [avals])} at the configuration's own shapes;
    `aval(shape, dtype)` places an argument (a described chip's, or a
    test's)."""
    from incubator_mxnet_tpu.models import looped_decoder as ld
    from incubator_mxnet_tpu.models import sparse_moe_decoder as sm
    from chipbench import weights_ouro
    m, e = cfg["model"], cfg["engine"]
    config = weights_ouro.looped_config(m)
    model = ld.LoopedDecoder(config, params={})
    params = {k: aval(shape, m["dtype"])
              for k, (shape, _) in weights_ouro.ouro_shapes(m).items()}
    S, W, P = e["max_slots"], e["prefill_window"], e["prefill_lanes"]
    cache = {leaf.name: aval((S + 1,) + tuple(leaf.shape), leaf.dtype)
             for leaf in model.cache_spec()}
    i32 = "int32"
    return {
        "decode": (sm._make_decode(config, e["decode_steps"], None,
                                   ld._make_micro(config), model.counters),
                   [params, cache, aval((S,), i32), aval((S,), i32),
                    aval((S,), i32), aval((S,), "float32"), aval((S,), i32),
                    aval((S,), "float32"), aval((S, 2), "uint32")]),
        "prefill": (ld._make_chunk(config, W, m["max_len"], True),
                    [params, cache, aval((P, W), i32), aval((P,), i32),
                     aval((P,), i32)]),
        "chunk_prefill": (ld._make_chunk(config, W, m["max_len"], False),
                          [params, cache, aval((P, W), i32), aval((P,), i32),
                           aval((P,), i32), aval((P,), i32)]),
    }


def leaf_sized(text, cache):
    """The `slice` and `copy` instructions of a compiled program whose
    result has as many elements as one cache leaf, or more."""
    import math
    import re
    least = min(math.prod(a.shape) for a in cache.values())
    out = []
    for name, dims, op in re.findall(
            r"^\s*(?:ROOT )?(\S+) = \w+\[([\d,]*)\]\S* (slice|copy)\(",
            text, re.M):
        if dims and math.prod(int(d) for d in dims.split(",")) >= least:
            out.append((name, dims, op))
    return out


def main(argv):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from incubator_mxnet_tpu.ops import fused
    from chipbench import harness

    hlo_dir = None
    if "--hlo" in argv:
        i = argv.index("--hlo")
        hlo_dir = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
        os.makedirs(hlo_dir, exist_ok=True)
    name = argv[0] if argv else "ouro26b_serve"
    only = argv[1:]
    cfg = harness.Bench(ROOT).config(name)
    # the program asks `tpu_platform_available()` and would take its CPU
    # branch here: steer it in this script, not through an option
    fused._on_tpu = lambda: True
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=chip)

    out = {}
    for pname, (fn, args) in serving_programs(cfg, aval).items():
        if only and pname not in only:
            continue
        t0 = time.perf_counter()
        calls = fused.fused_stats()["paged_attention_calls"]
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        out[pname] = {
            "compile_s": round(time.perf_counter() - t0, 1),
            "argument_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "paged_reads_traced":
                fused.fused_stats()["paged_attention_calls"] - calls,
            "kernels": text.count("tpu_custom_call"),
            "leaf_sized_slices_or_copies": len(leaf_sized(text, args[1])),
            "fallbacks": fused.fused_stats()["fallback_calls"]}
        if hlo_dir:
            with open(os.path.join(hlo_dir, pname + ".hlo.txt"), "w") as f:
                f.write(text)
        print(pname, json.dumps(out[pname]), flush=True)
    return out


if __name__ == "__main__":
    main(sys.argv[1:])
