"""Compiles for the chip, without the chip.

The TPU's compiler is installed here and compiles for a chip that is
described, not attached (`jax.experimental.topologies`). These cases hand it
the Pallas kernels of the two main paths at the widths `chip_smoke.py` runs —
what interpret mode cannot see: block shapes the lowering refuses, and more
VMEM than a kernel may allocate — and the programs that must hold none.
Each is a second or two and costs no chip time. Nothing runs, so nothing
here says anything about results or speed.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU's library, and every xdist worker imports
every test file. The compiles happen in the test's own process, with the
persistent compilation cache off (an entry written for a described device
cannot be read back without one).
"""
import math
import os

import pytest

SERVE = dict(lanes=8, heads=12, head_dim=64, max_len=1024, layers=12)
# `chipbench/configs/cgpt13b_serve.json`: 16 slots + 2 prefix-cache rows
CELL = dict(lanes=18, heads=16, head_dim=128, max_len=2048, layers=24)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    had = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs under /tmp
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:       # noqa: BLE001 — any failure to describe
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        if had is None:
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def chip(topo):
    """Shape factory for arguments placed on one described v5e chip."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    yield shape
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


def _compile(fn, *args):
    import jax
    return jax.jit(fn).lower(*args).compile().as_text()


def _holds_kernel(text):
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"


def _compile_paged_attention(chip, s, kv_dtype, chunk):
    from incubator_mxnet_tpu.ops import pallas_kernels as pk
    q = chip((s["lanes"], chunk, s["heads"], s["head_dim"]), "bfloat16")
    slab = chip((s["lanes"] + 1, s["layers"], s["max_len"], s["heads"],
                 s["head_dim"]), kv_dtype)
    lengths = chip((s["lanes"],), "int32")
    if kv_dtype == "int8":
        scale = chip((s["lanes"] + 1, s["layers"], s["max_len"]), "float32")
        text = _compile(
            lambda q, k, v, n, ks, vs: pk.paged_attention_fwd(
                q, k, v, n, 3, k_scale=ks, v_scale=vs),
            q, slab, slab, lengths, scale, scale)
    else:
        text = _compile(
            lambda q, k, v, n: pk.paged_attention_fwd(q, k, v, n, 3),
            q, slab, slab, lengths)
    _holds_kernel(text)


@pytest.mark.parametrize("kv_dtype,chunk", [
    ("bfloat16", 1), ("bfloat16", 5), ("int8", 1)],
    ids=["bf16-C1", "bf16-C5-verify", "int8-C1"])
def test_paged_attention_compiles_at_gpt2_small_widths(chip, kv_dtype, chunk):
    """The serve engine's decode attention at the smoke's shapes: plain
    decode, the speculative-verify chunk, and the int8 slab with its
    per-position scales. (12, 64) heads are no whole tile: the head-major
    body."""
    _compile_paged_attention(chip, SERVE, kv_dtype, chunk)


@pytest.mark.parametrize("kv_dtype,chunk", [
    ("bfloat16", 1), ("bfloat16", 128), ("int8", 1), ("float32", 5),
    ("bfloat16", 16)],
    ids=["bf16-C1", "bf16-C128-chunk-prefill-extent-2048", "int8-C1",
         "f32-slab-under-bf16-q-C5", "bf16-C16-flat-at-its-256-rows"])
def test_paged_attention_compiles_at_cgpt13b_widths(chip, kv_dtype, chunk):
    """The benchmark's serving cell (Cerebras-GPT-1.3B: 16 slots + 2
    prefix rows, (16, 128) heads, 2048 positions, 24 layers): plain
    decode (the flat body), a 128-wide chunk-prefill window over the
    whole extent and the int8 slab (both head-major), bf16 queries over
    a float32 slab (flat: 16 heads are a whole tile of either) and the
    widest chunk the flat body takes (256 query rows, a 64-wide block).
    A block size the v5e lowering refuses, or more VMEM than a kernel
    may hold, fails here."""
    _compile_paged_attention(chip, CELL, kv_dtype, chunk)


@pytest.mark.parametrize("lanes,rows", [(64, False), (4, True)],
                         ids=["decode-64-lanes", "prefill-4-lanes-rows-as-data"])
def test_shared_leaf_read_compiles_at_phi4mf_widths(chip, lanes, rows):
    """`chipbench/configs/phi4mf_serve.json`'s shared-cache read: 40
    zero-padded query rows of 128 over a (65, 4096, 1280) leaf (10 KV
    pairs of 128), float32 out; lane s reads row s in decode, a row that
    rides as data in prefill."""
    from incubator_mxnet_tpu.ops import pallas_kernels as pk
    q = chip((lanes, 1, 40, 128), "bfloat16")
    leaf = chip((65, 4096, 1280), "bfloat16")
    ints = chip((lanes,), "int32")
    if rows:
        text = _compile(
            lambda q, k, v, n, r: pk.paged_attention_fwd(
                q, k, v, n, None, scale=0.125, rows=r, out_dtype="float32"),
            q, leaf, leaf, ints, ints)
    else:
        text = _compile(
            lambda q, k, v, n: pk.paged_attention_fwd(
                q, k, v, n, None, scale=0.125, out_dtype="float32"),
            q, leaf, leaf, ints)
    _holds_kernel(text)


@pytest.mark.parametrize("widths", [SERVE, CELL],
                         ids=["gpt2-small", "cgpt13b"])
@pytest.mark.parametrize("kv_bytes,chunk", [
    (2, 1), (2, 5), (2, 16), (2, 128), (1, 1), (4, 1)],
    ids=["bf16-C1", "bf16-C5", "bf16-C16", "bf16-C128", "int8-C1", "f32-C1"])
def test_paged_blocks_divide_max_len_and_honour_int8_rule(widths, kv_bytes,
                                                          chunk):
    """`_paged_blocks` at the serving widths: a block that divides the
    slab's positions, is no wider than a lane width (a lane pays for
    whole blocks), and whose int8 scale block (L, bt) has a legal lane
    dim: a multiple of 128 or all of `max_len`."""
    from incubator_mxnet_tpu.ops import pallas_kernels as pk
    t = widths["max_len"]
    bt = pk._paged_blocks(t, chunk, widths["heads"], widths["head_dim"], 2,
                          kv_bytes, widths["layers"] if kv_bytes == 1 else 0)
    assert 0 < bt <= 128 and t % bt == 0
    if kv_bytes == 1:
        assert bt % 128 == 0 or bt == t


@pytest.mark.parametrize("shape", [(48, 1024, 64), (16, 4096, 128)],
                         ids=["gpt2-small-1k", "hd128-4k"])
@pytest.mark.parametrize("sweep", ["fwd", "bwd"])
def test_flash_attention_compiles(chip, shape, sweep):
    from incubator_mxnet_tpu.ops import pallas_attention as pa
    bh, t, d = shape
    bq, bk = pa._auto_blocks(t, t, d)
    scale = 1.0 / math.sqrt(d)
    x = chip(shape, "bfloat16")
    row = chip((bh, t, 1), "float32")
    if sweep == "fwd":
        text = _compile(
            lambda q, k, v: pa._flash_forward_lse(
                q, k, v, True, scale, bq, bk, False), x, x, x)
    else:
        text = _compile(
            lambda q, k, v, do, lse, delta: pa._flash_backward(
                q, k, v, do, lse, delta, True, scale, bq, bk, False),
            x, x, x, x, row, row)
    _holds_kernel(text)


@pytest.mark.parametrize("rows,channels", [(100352, 256), (1568, 2048)],
                         ids=["resnet50-stage1", "resnet50-stage4"])
def test_fused_apply_compiles_at_resnet50_shapes(chip, rows, channels):
    """scale/shift + residual + relu, ResNet-50 batch 32, first and last
    stage."""
    from incubator_mxnet_tpu.ops import pallas_kernels as pk
    x = chip((rows, channels), "bfloat16")
    vec = chip((channels,), "float32")
    text = _compile(
        lambda x, s, b, r: pk.apply_scale_shift_act(x, s, b, r, "relu"),
        x, vec, vec, x)
    _holds_kernel(text)


@pytest.mark.parametrize("sweep", ["fwd", "bwd"])
def test_global_avg_pool_compiles_at_resnet50_shape(chip, sweep):
    from incubator_mxnet_tpu.ops import pallas_kernels as pk
    n, h, w, c = 32, 7, 7, 2048
    if sweep == "fwd":
        text = _compile(lambda x: pk.avg_pool2d_fwd(x, h, w),
                        chip((n, h, w, c), "bfloat16"))
    else:
        text = _compile(lambda dy: pk.avg_pool2d_bwd(dy, h, w, h, w),
                        chip((n, 1, 1, c), "bfloat16"))
    _holds_kernel(text)


@pytest.mark.parametrize("hw,in_channels,channels,downsample", [
    (56, 64, 256, True), (7, 2048, 2048, False)],
    ids=["resnet50-stage1-downsample", "resnet50-stage4"])
def test_bottleneck_block_leaves_batch_norm_to_the_compiler(
        chip, monkeypatch, hw, in_channels, channels, downsample):
    """Forward and backward of one `BottleneckV1` under `fusion_scope` and
    bf16 AMP, batch 32, with the program told it is on a TPU: no Pallas
    call, and no top-level copy of a whole activation beyond the block's
    own argument and its cotangent crossing from the default layout. A
    Pallas call takes its (M, C) operand row-major where the compiler
    keeps convolution activations batch-minor: every batch norm routed
    through one cost two copies and a reshape (PERF.md §6, PR 32: 4 and 3
    custom calls, 5 and 4 such copies in these two blocks)."""
    import re
    import jax
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import amp, autograd
    from incubator_mxnet_tpu.gluon.model_zoo.vision import BottleneckV1
    from incubator_mxnet_tpu.ndarray import _wrap
    from incubator_mxnet_tpu.ops import fused
    batch = 32
    monkeypatch.setattr(fused, "_on_tpu", lambda: True)
    amp.init("bfloat16")
    try:
        block = BottleneckV1(channels, 1, downsample=downsample,
                             in_channels=in_channels, layout="NHWC")
        block.initialize()
        block(mx.np.zeros((1, hw, hw, in_channels), dtype="float32"))
        params = [p.data() for _, p in sorted(block.collect_params().items())]

        def loss(bufs, x):
            for p, buf in zip(params, bufs):     # the block dies with the test
                p._set_arr(buf)
            with fused.fusion_scope(True), autograd.train_mode():
                y = block(_wrap(x))._arr
            return jnp.sum(jnp.square(y.astype(jnp.float32)))

        text = _compile(
            jax.grad(loss, argnums=(0, 1)),
            [chip(p.shape, p.dtype) for p in params],
            chip((batch, hw, hw, in_channels), "bfloat16"))
    finally:
        amp.uninit()
    assert "tpu_custom_call" not in text
    entry = text[text.index("ENTRY"):]
    copied = re.findall(
        rf"^\s*(?:ROOT )?%copy\S* = \w+\[{batch},\d+,\d+,\d+\]\S* copy\(",
        entry, re.M)
    assert len(copied) <= 2, copied


@pytest.mark.parametrize("form,kv_dtype", [
    ("blocks", "bfloat16"), ("blocks", "int8"), ("whole_rows", "bfloat16")])
def test_prefix_copy_program_holds_pieces_not_rows(chip, form, kv_dtype):
    """The prefix cache's copy program at the benchmark cell's shape (19
    rows of 24 x 2048 x 16 x 128, 8 lanes): the TPU's compiler updates the
    donated slabs in place inside the loop, so no instruction makes a
    buffer of a row or more, the temporaries are under one 128-position
    block and no Pallas call appears (the benchmark reads every
    `tpu_custom_call` as attention). The whole-row gather it replaced is
    the control: 5.2 GB of temporaries."""
    import jax
    from hlo_branches import buffers_of_at_least
    from incubator_mxnet_tpu.serve import continuous
    s = CELL
    dims = (s["lanes"] + 1, s["layers"], s["max_len"], s["heads"],
            s["head_dim"])
    slab = chip(dims, kv_dtype)
    if kv_dtype == "int8":
        slab = (slab, chip(dims[:3], "float32"))
    lanes = chip((8,), "int32")
    if form == "blocks":
        fn, args = continuous._copy_slot_rows, (slab, slab, lanes, lanes,
                                                lanes)
    else:
        fn = lambda k, v, src, dst: (k.at[dst].set(k[src]),   # noqa: E731
                                     v.at[dst].set(v[src]))
        args = (slab, slab, lanes, lanes)
    compiled = jax.jit(fn, donate_argnums=(0, 1)).lower(*args).compile()
    row = math.prod(dims[1:])
    block_bytes = 2 * row // s["max_len"] * continuous._copy_block(
        s["max_len"])
    big = buffers_of_at_least(compiled, row)
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    if form == "blocks":
        assert big == [] and temporaries < block_bytes
        assert "tpu_custom_call" not in compiled.as_text()
    else:
        assert big and temporaries > 2 * row


def test_classic_chunk_program_copies_no_slab_below_the_full_extent(
        chip, monkeypatch):
    """`chipbench/configs/cgpt13b_serve.json`: the chunk program as the
    engine asks for it BELOW the full extent (window 128, extent 1024, 18
    pool lanes over 19 rows of 24 x 2048 x 16 x 128). The paged kernel's
    grid already follows each lane's live blocks, so the extent must not
    reach the program: a slice of the slab to bound the read was a copy
    of it, once a layer for K and once for V (48 of 1.9 GB, 3.88 GB of
    temporaries, where the full extent stated 25 MB)."""
    import json
    import re
    import jax
    from incubator_mxnet_tpu import serve
    from incubator_mxnet_tpu.ops import fused
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench/configs/cgpt13b_serve.json")) as f:
        cfg = json.load(f)
    m, e = cfg["model"], cfg["engine"]
    # the platform this process sees is the CPU: take the chip's branch
    monkeypatch.setattr(fused, "_on_tpu", lambda: True)
    dc = serve.DecoderConfig(vocab=m["vocab"], embed=m["embed"],
                             layers=m["layers"], heads=m["heads"],
                             head_dim=m["head_dim"],
                             mlp_hidden=m["mlp_hidden"], max_len=m["max_len"],
                             dtype=m["dtype"])
    params = jax.tree_util.tree_map(
        lambda a: chip(a.shape, a.dtype),
        jax.eval_shape(lambda: serve.init_decoder_params(dc)))
    model = serve.CachedDecoder(dc, params=params)
    rows = e["max_slots"] + e["prefix_cache_slots"]
    slab = chip((rows + 1, m["layers"], m["max_len"], m["heads"],
                 m["head_dim"]), e["kv_dtype"])
    W = e["prefill_window"]
    prog = model.chunk_prefill_program(W, extent=1024)
    assert prog is model.chunk_prefill_program(W, extent=m["max_len"])
    compiled = prog.lower(params, slab, slab, chip((rows, W), "int32"),
                          chip((rows,), "int32"),
                          chip((rows,), "int32")).compile()
    text = compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20
    assert text.count("tpu_custom_call") == m["layers"]
    slab_sized = re.findall(
        rf"^\s*(?:ROOT )?(\S+) = bf16\[{rows + 1},{m['layers']},\S* "
        r"(slice|copy)\(", text, re.M)
    assert not slab_sized, slab_sized[:4]


@pytest.mark.parametrize("program", ["decode", "prefill",
                                     "chunk_prefill@16384"])
def test_sparse_moe_programs_fit_the_chip_at_glm52_widths(chip, program):
    """`chipbench/configs/glm52_serve.json`: the decode program (32 lanes,
    4 micro-steps), the prefill at offset 0 and the chunk program at its
    longest extent (1 lane of 1024 over 16384 cached positions), whole,
    at every published width: arguments (3.88 B parameters in bfloat16, a
    33-row cache of five latent and two index-key leaves) and temporaries
    (the index scores of a chunk, the gathered latents of a decode step)
    under one chip's 15.75 GiB, the cache updated in place."""
    import json
    import sys
    import jax
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from chipbench.tests import compile_v5e_glm
    with open(os.path.join(root, "chipbench/configs/glm52_serve.json")) as f:
        cfg = json.load(f)
    fn, args = compile_v5e_glm.serving_programs(cfg, chip)[program]
    mem = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile() \
        .memory_analysis()
    cache = sum(math.prod(a.shape) * 2 for a in args[1].values())
    assert mem.alias_size_in_bytes >= cache          # no second cache
    # the outputs are the aliased cache and a few small arrays: arguments
    # and temporaries are what the program holds
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert held < 15.5 * 2 ** 30, held
    # no second copy of the held experts (4.8 GB) among the temporaries
    assert mem.temp_size_in_bytes < 2 * 2 ** 30, mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes > 11e9         # the cell's own size


def test_delta_moe_decode_fits_the_chip_at_ling3f_widths(chip, monkeypatch):
    """`chipbench/configs/ling3f_serve.json`: the decode program (128 lanes,
    4 micro-steps) whole, at every published width: arguments (2.87 B
    parameters in bfloat16, a 129-row cache of six float32 state leaves,
    six conv tails and one latent leaf: 10.1 GB) with the cache updated in
    place, the latent read in the paged kernel's leaf mode, and NO second
    copy of a state leaf among the temporaries (271 MB each: a decayed copy
    a layer was 290 MB more than the program needs)."""
    import json
    import sys
    import jax
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from chipbench.tests import compile_v5e_ling
    from incubator_mxnet_tpu.ops import fused
    # the platform this process sees is the CPU: take the chip's branch
    monkeypatch.setattr(fused, "_on_tpu", lambda: True)
    with open(os.path.join(root, "chipbench/configs/ling3f_serve.json")) as f:
        cfg = json.load(f)
    fn, args = compile_v5e_ling.serving_programs(cfg, chip)["decode"]
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    mem = compiled.memory_analysis()
    cache = sum(math.prod(a.shape) * a.dtype.itemsize
                for a in args[1].values())
    assert mem.alias_size_in_bytes >= cache          # no second cache
    assert 10.0e9 < mem.argument_size_in_bytes < 10.3e9   # the cell's size
    assert mem.temp_size_in_bytes < 0.5 * 2 ** 30, mem.temp_size_in_bytes
    assert compiled.as_text().count("tpu_custom_call") >= 1


@pytest.mark.parametrize("program", ["micro", "prefill", "chunk_prefill"])
def test_looped_programs_hold_the_stack_once_and_copy_no_leaf(
        chip, monkeypatch, program):
    """`chipbench/configs/ouro26b_serve.json`: the decode micro-step (8
    lanes), the dense prefill and the chunk program (1 lane of 256) at the
    cell's widths, window, lanes and `max_len`, over a 9-row cache of K and
    V leaves of (4 passes, 512, 2048), with the depth cut to 2 layers (all
    48 take a minute a program: `chipbench/tests/compile_v5e_ouro.py`). The
    pass is a loop in the program, so each holds ONE paged kernel a layer
    (the dense prefill none) whatever `ut_steps` is; the pass index reaches
    the leaf as a row number, so no `slice` or `copy` gives a result as
    large as a leaf (PR 36's lesson), no weight leaf is laid out anew
    (rotary on the flat axis: with the heads split first W_q and W_k were
    copied whole), nothing fell back, and the cache is updated in place.
    The micro-step stands for the decode program without the scan and the
    shared sampler, whose sort over 49,152 logits is 23 s of compile and
    no part of this model."""
    import json
    import re
    import sys
    import jax
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from chipbench import weights_ouro
    from chipbench.tests import compile_v5e_ouro
    from incubator_mxnet_tpu.models import looped_decoder as ld
    from incubator_mxnet_tpu.ops import fused
    # the platform this process sees is the CPU: take the chip's branch
    monkeypatch.setattr(fused, "_on_tpu", lambda: True)
    with open(os.path.join(root, "chipbench/configs/ouro26b_serve.json")) as f:
        cfg = json.load(f)
    cfg["model"] = dict(cfg["model"], layers=2)
    programs = compile_v5e_ouro.serving_programs(cfg, chip)
    if program == "micro":
        _, (params, cache, tokens, lengths, *_) = programs["decode"]
        fn = ld._make_micro(weights_ouro.looped_config(cfg["model"]))
        args = [params, cache, tokens, lengths, chip(tokens.shape, "bool")]
    else:
        fn, args = programs[program]
    before = fused.fused_stats()
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    after = fused.fused_stats()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    reads = 0 if program == "prefill" else 2
    assert after["paged_attention_calls"] - before["paged_attention_calls"] \
        == reads
    assert text.count("tpu_custom_call") == reads
    assert after["fallback_calls"] == before["fallback_calls"]
    assert compile_v5e_ouro.leaf_sized(text, args[1]) == []
    assert not re.findall(r" copy\(%params", text)
    leaves = sum(math.prod(a.shape) * 2 for a in args[1].values())
    assert leaves == 4 * 9 * 4 * 512 * 2048 * 2
    assert mem.alias_size_in_bytes >= leaves          # no second cache
    assert mem.temp_size_in_bytes < 64 * 2 ** 20, mem.temp_size_in_bytes


def test_hlo_parser_reads_a_tpu_compiled_module(chip):
    """`mx.inspect` on what the TPU's compiler prints: operands named
    without shapes, tiled layouts, a dot lowered to a convolution inside a
    fusion, a Pallas custom call. Every operand resolves to a shape and the
    matmul's flops are counted whole."""
    from incubator_mxnet_tpu.inspect import hlo, roofline
    from incubator_mxnet_tpu.ops import pallas_kernels as pk
    m, k, n = 256, 512, 128

    def fn(a, b, scale, shift):
        return pk.apply_scale_shift_act(a @ b, scale, shift, None, "relu")

    vec = chip((n,), "float32")
    text = _compile(fn, chip((m, k), "bfloat16"), chip((k, n), "bfloat16"),
                    vec, vec)
    module = hlo.parse_module(text)
    unresolved = [(ins.name, ins.opcode)
                  for comp in module.computations.values()
                  for ins in comp.instructions
                  if any(shape is None for shape in ins.operand_shapes)]
    assert not unresolved
    calib = {"peak_flops": 197e12, "peak_bytes_per_sec": 819e9}
    records, totals = roofline.analyze_module(module, calib=calib)
    assert totals["flops"] == 2.0 * m * k * n
    assert any(r["opcode"] == "custom-call" for r in records)
