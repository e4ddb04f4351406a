#!/usr/bin/env python3
"""`compile_v5e.py` for a `serve_sparse_moe` configuration: the serving
programs of the latent-attention, sparse-attention, sparse-expert decoder
compiled at their real size for a described (not attached) v5e chip, with
this sandbox's TPU compiler. Nothing runs; what the compiler refuses here
costs no chip time. Prints per program: compile seconds, argument /
temporary / aliased bytes, how many results the size of an expert's matrix
it writes (the grouped matmul must read its expert's weights where they
lie) and its largest results. With
`--hlo <dir>` the optimised HLO of each program is kept there, for the
person who writes a metric's name pattern (`scopes` lists, per scope of
the program, the kinds and result shapes of the operations under it).

    JAX_PLATFORMS=cpu python3 chipbench/tests/compile_v5e_glm.py [--hlo dir] [config] [program ...]

Programs: `decode`, `prefill`, `chunk_prefill@<extent>` (default: the
engine's extent ladder), `reference` (one attention layer of the plain
reference at the longest request: its temporaries must fit beside the
weights)."""
import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

_HEAD = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_OP = re.compile(r"^\s*(?:ROOT )?%(\S+) = \(?(\w+)\[([\d,]*)\]")
_KIND = re.compile(r"[\]})]\s([a-z][a-z0-9\-]*)\(")
_SCOPE = re.compile(r'op_name="[^"]*?(layer\d+/[a-z_]+|head|embed)')
_ITEM = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1, "s8": 1}


def top_level_ops(text):
    """(name, kind, dtype, dims, bytes, scope or None) of every operation
    of an optimised module that is NOT inside a fused computation: the
    operations that run, and that the trace names. A tuple's shape and
    bytes are its first element's."""
    comp = None
    for line in text.splitlines():
        head = _HEAD.match(line)
        if head:
            comp = head.group(1)
            continue
        op, kind = _OP.match(line), _KIND.search(line)
        if not op or not kind or comp is None \
                or "fused_computation" in comp:
            continue
        (name, dtype, dims), kind = op.groups(), kind.group(1)
        if kind in ("parameter", "get-tuple-element", "bitcast", "constant"):
            continue
        n = 1
        for d in dims.split(",") if dims else ():
            n *= int(d)
        scope = _SCOPE.search(line)
        yield (name, kind, dtype, dims, n * _ITEM.get(dtype, 4),
               scope.group(1) if scope else None)


def scopes(text):
    """{scope: {"<kind> <dtype>[<dims>]": count}}: what runs under each
    scope of the program, by the result's shape."""
    out = {}
    for _, kind, dtype, dims, _, scope in top_level_ops(text):
        per = out.setdefault(scope or "-", {})
        key = f"{kind} {dtype}[{dims}]"
        per[key] = per.get(key, 0) + 1
    return out


def serving_programs(cfg, aval):
    """{name: (function, arguments as shapes)} of the engine's programs at
    the configuration's own shapes: `decode`, `prefill` and one
    `chunk_prefill@<extent>` a rung of the engine's extent ladder. `aval`
    makes a shape on the described chip."""
    from incubator_mxnet_tpu.models import sparse_moe_decoder as sm
    from chipbench import weights_glm
    m, e = cfg["model"], cfg["engine"]
    config = weights_glm.sparse_moe_config(m)
    model = sm.SparseMoEDecoder(config, params={})
    params = {k: aval(shape, "float32" if k in sm.FLOAT32_LEAVES
                      else m["dtype"])
              for k, (shape, _) in weights_glm.glm_shapes(m).items()}
    S, W, P = e["max_slots"], e["prefill_window"], e["prefill_lanes"]
    cache = {leaf.name: aval((S + 1,) + tuple(leaf.shape), leaf.dtype)
             for leaf in model.cache_spec()}
    i32 = "int32"
    lanes = [aval((P, W), i32), aval((P,), i32), aval((P,), i32)]
    programs = {
        "decode": (sm._make_decode(config, e["decode_steps"], None),
                   [params, cache, aval((S,), i32), aval((S,), i32),
                    aval((S,), i32), aval((S,), "float32"), aval((S,), i32),
                    aval((S,), "float32"), aval((S, 2), "uint32")]),
        "prefill": (sm._make_chunk(config, W, W, True),
                    [params, cache] + lanes),
    }
    ext = W
    while True:
        ext = min(ext, m["max_len"])
        programs[f"chunk_prefill@{ext}"] = (
            sm._make_chunk(config, W, ext, False),
            [params, cache] + lanes + [aval((P,), i32)])
        if ext == m["max_len"]:
            return programs
        ext *= 2


def main(name, only, hlo_dir):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from chipbench import harness, traffic, weights_glm
    from chipbench.paths import serve_sparse_moe
    from chipbench.reference import glm_dsa

    bench = harness.Bench(ROOT)
    cfg = bench.config(name)
    m = cfg["model"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=chip)

    programs = serving_programs(cfg, aval)
    expert_bytes = 2 * m["embed"] * 2 * m["expert_hidden"]
    out = {}
    for pname, (fn, args) in programs.items():
        if only and pname not in only:
            continue
        t0 = time.perf_counter()
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        out[pname] = {
            "compile_s": round(time.perf_counter() - t0, 1),
            "argument_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "kernels": text.count("tpu_custom_call"),
            # an expert's weights written anew (a slice or a layout change
            # that the matmul did not take in): there should be none
            "expert_sized_results": sum(
                1 for _, kind, _, dims, nbytes, _ in top_level_ops(text)
                if nbytes >= expert_bytes // 2 and dims.endswith(
                    (f"{m['embed']},{2 * m['expert_hidden']}",
                     f"{m['expert_hidden']},{m['embed']}"))),
            "largest_results_mb": sorted(
                {f"{kind} {dtype}[{dims}]": round(nbytes / 1e6)
                 for _, kind, dtype, dims, nbytes, _ in top_level_ops(text)
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
        # one attention layer of the plain reference over the longest
        # request (a `full` indexer's: selection and read)
        tr = traffic.load(bench.find("traffic", "longctx"))
        T = serve_sparse_moe.pad_to(tr)
        shapes = weights_glm.glm_shapes(m)
        names = ("ln1_w", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm",
                 "wkv_b", "wo", "i_wq", "i_wk", "i_k_norm_w", "i_k_norm_b",
                 "i_ww")
        w = {n: aval(shapes[n][0][1:], m["dtype"]) for n in names}
        layer = glm_dsa.make_forward(m)[3]
        t0 = time.perf_counter()
        compiled = layer.lower(aval((T, m["embed"]), "float32"), w, None,
                               True).compile()
        mem = compiled.memory_analysis()
        out["reference"] = {
            "positions": T,
            "compile_s": round(time.perf_counter() - t0, 1),
            "argument_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes}
        print("reference", json.dumps(out["reference"]), flush=True)
    return out


if __name__ == "__main__":
    argv = sys.argv[1:]
    hlo = None
    if argv[:1] == ["--hlo"]:
        hlo, argv = argv[1], argv[2:]
    main(argv[0] if argv else "glm52_serve", argv[1:], hlo)
