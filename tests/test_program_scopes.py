"""The device's time is named by the program's own scopes:
`inspect.scope_table` (instruction -> scope path and pass, from a compiled
program's `op_name` metadata), `profiler.program_scopes()` (the registry
of the programs this process runs, answered after their engine is gone),
the scopes that `Block.__call__` enters while it is traced, and the share
of each served model's decode program and of a fused train step that runs
under a scope of the program's. Counts and names, never times."""
import collections
import gc
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, profiler, serve
from incubator_mxnet_tpu import optimizer as opt_mod
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.gluon.contrib import FusedTrainStep
from incubator_mxnet_tpu.inspect import hlo
from incubator_mxnet_tpu.inspect import scope_of, scope_table
from incubator_mxnet_tpu.models import delta_moe_decoder as dm
from incubator_mxnet_tpu.models import hybrid_decoder as hd
from incubator_mxnet_tpu.models import looped_decoder as ld
from incubator_mxnet_tpu.models import mamba_moe_decoder as mmd
from incubator_mxnet_tpu.models import sparse_moe_decoder as sm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# what carries no device work of its own
TRIVIAL = frozenset(("parameter", "constant", "get-tuple-element", "tuple",
                     "bitcast", "iota", "broadcast", "copy-start",
                     "copy-done"))


# -- the table ---------------------------------------------------------------
@pytest.mark.parametrize("op_name, want", [
    ("jit(decode)/while/body/closed_call/layer3/attn/dot_general",
     ("layer3/attn", "")),
    ("jit(step)/jvp(forward)/features/4/0/conv_general_dilated",
     ("forward/features/4/0", "fwd")),
    ("jit(step)/transpose(jvp(forward))/features/4/0/conv_general_dilated",
     ("forward/features/4/0", "bwd")),
    ("jit(step)/transpose(jvp(forward))/mul;jit(step)/jvp(forward)/add",
     ("forward", "bwd")),
    ("jit(step)/update/mul", ("update", "")),
    ("jit(decode)/while/body/closed_call/sampler/cond/branch_1_fun/sort",
     ("sampler", "")),
    ("jit(decode)/while/body/closed_call/layer0/attn/jit(_where)/select_n",
     ("layer0/attn", "")),
    # jax's own machinery, and what the compiler adds, is under no scope
    ("jit(decode)/while/body/dynamic_update_slice", ("", "")),
    ("jit(decode)/while/cond/lt", ("", "")),
    ("jit(decode)/while", ("", "")),
    ("jit(f)/jvp(jit(g))/mul", ("", "")),
    ("reduce_sum", ("", "")),
    ("", ("", "")),
    (None, ("", "")),
])
def test_scope_of_strips_what_jax_adds(op_name, want):
    assert scope_of(op_name) == want


def nested(x, w):
    def loss(w):
        with jax.named_scope("forward"):
            with jax.named_scope("stem"):
                h = jnp.tanh(x @ w)

            def body(c, _):
                with jax.named_scope("layer0/attn"):
                    c = jnp.sin(c @ w)
                return c, None
            h, _ = jax.lax.scan(body, h, None, length=3)
            return jnp.sum(jax.nn.gelu(h))
    value, grad = jax.value_and_grad(loss)(w)
    with jax.named_scope("update"):
        w = w - 0.1 * grad
    return value, w


@pytest.fixture(scope="module")
def nested_compiled():
    x = jnp.ones((8, 8))
    return jax.jit(nested).lower(x, x).compile()


def test_every_instruction_under_a_scope_maps_to_it(nested_compiled):
    module = hlo.parse_module(nested_compiled.as_text())
    table = scope_table(nested_compiled)
    seen = collections.Counter()
    for comp in module.computations.values():
        for ins in comp.instructions:
            # every instruction of every computation, the while's body too
            assert ins.name in table
            path, which = table[ins.name]
            if not ins.op_name or "/" not in ins.op_name:
                assert (path, which) == ("", "")
                continue
            for scope in ("stem", "layer0/attn", "update"):
                if f"/{scope}/" in ins.op_name.split(";")[0]:
                    assert path.endswith(scope), (ins.op_name, path)
                    seen[scope, which] += 1
            assert "jit(" not in path and "while" not in path \
                and "jvp" not in path and "transpose" not in path
    # forward and backward of one scope are told apart; the update is
    # under neither
    assert seen["stem", "fwd"] and seen["stem", "bwd"]
    assert seen["layer0/attn", "fwd"] and seen["layer0/attn", "bwd"]
    assert seen["update", ""] and not seen["update", "fwd"]
    assert table == scope_table(nested_compiled.as_text()) \
        == scope_table(module)


def test_the_parser_imports_no_jax():
    import subprocess
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, importlib.util as u; "
         f"s = u.spec_from_file_location('hlo', r'{hlo.__file__}'); "
         "m = u.module_from_spec(s); s.loader.exec_module(m); "
         "assert m.scope_table('HloModule m\\n') == {}; "
         "sys.exit(int('jax' in sys.modules))"], cwd=ROOT)
    assert out.returncode == 0


# -- who publishes -----------------------------------------------------------
def toy_engine(**kw):
    cfg = serve.DecoderConfig(vocab=64, embed=32, layers=2, heads=4,
                              head_dim=8, max_len=48)
    return serve.ContinuousEngine(
        serve.CachedDecoder(cfg, seed=11), max_slots=4,
        prefix_cache_slots=1, prefix_block=4, prefill_window=16,
        decode_steps=2, **kw)


ENGINE_PROGRAMS = {
    "prefill": "jit_prefill", "decode": "jit_decode",
    "sample_first": "jit_sample_tokens", "join_lanes": "jit_join_lanes",
    "advance_lanes": "jit_advance_lanes",
    # one program whatever the extent: the classic chunk's read follows
    # the live blocks, so the engine's three rungs are the one jit
    "chunk_prefill[16]": "jit_chunk_prefill",
    "copy": "jit__copy_slot_rows"}


def test_engine_registers_every_program_it_runs_and_no_buffer():
    eng = toy_engine().start()
    try:
        eng.generate([1, 2, 3], 4)
        low = eng.lowered_programs()
        assert set(low) == set(ENGINE_PROGRAMS)
        for name, lowered in low.items():
            # the module name a device trace prints
            text = lowered.as_text()
            assert f"@{ENGINE_PROGRAMS[name]} " in text \
                or f"@{ENGINE_PROGRAMS[name]}(" in text \
                or ENGINE_PROGRAMS[name] in text.splitlines()[0], name
        mine = {id(p) for p in eng._programs.values()}
        progs = [p for p in profiler._registered() if id(p) in mine]
        assert sorted(p.module for p in progs) \
            == sorted(ENGINE_PROGRAMS.values())
        for p in progs:
            leaves = jax.tree_util.tree_leaves(p.args)
            assert leaves and not any(isinstance(a, jax.Array)
                                      for a in leaves)
        # the plans are of the same list: every program that holds a model
        assert set(eng.memory_plans()) == {
            n for n in ENGINE_PROGRAMS
            if not n.startswith(("sample", "join", "advance"))}
        eng.assert_no_retraces()
    finally:
        eng.close()


def test_a_never_started_engine_describes_the_same_programs():
    cold, warm = toy_engine(), toy_engine().start()
    try:
        a, b = cold.lowered_programs(), warm.lowered_programs()
    finally:
        warm.close()
    assert list(a) == list(b)
    for name in a:
        assert a[name].in_avals == b[name].in_avals, name


def test_program_scopes_answers_after_the_engine_is_closed_and_gone():
    eng = toy_engine().start()
    out = eng.generate([1, 2, 3], 4)
    eng.close()
    del eng
    gc.collect()
    assert len(out) == 4
    tables = profiler.program_scopes(r"^jit_decode\(")
    assert list(tables) == ["jit_decode"]
    scopes = collections.Counter(p for p, _ in tables["jit_decode"].values())
    for want in ("embed", "layer0/attn", "layer0/mlp", "layer1/attn",
                 "layer1/mlp", "head", "sampler"):
        assert any(p == want or p.startswith(want + "/") for p in scopes), \
            (want, sorted(scopes))
    # a pattern is matched as a device trace prints the name
    assert profiler.program_scopes(r"^jit_decode$") == {}
    assert "jit_prefill" in profiler.program_scopes(r"prefill\(")
    # once: the second answer is the memoised table
    again = profiler.program_scopes(r"^jit_decode\(")["jit_decode"]
    assert again == tables["jit_decode"]


def test_rungs_of_one_name_share_a_table_without_their_disagreements():
    """A model whose chunk reads its cache densely is a program a rung
    (the sparse-expert decoder: extents 32 and 48 over a window of 16)."""
    eng = serve.ContinuousEngine(
        tiny_model("sparse_moe"), max_slots=2, prefill_window=16,
        prefix_cache_slots=0, draft_tokens=0, decode_steps=2).start()
    try:
        eng.generate([1, 2, 3], 4)
        rungs = [p for n, p in eng._programs.items()
                 if n.startswith("chunk_prefill")]
    finally:
        eng.close()
    merged = profiler.program_scopes(r"^jit_chunk_prefill\(")[
        "jit_chunk_prefill"]
    assert len(rungs) == 2 and all(p.table for p in rungs)
    for name, where in merged.items():
        assert all(p.table.get(name, where) == where for p in rungs)
    dropped = set().union(*(p.table for p in rungs)) - set(merged)
    for name in dropped:
        assert len({p.table[name] for p in rungs if name in p.table}) > 1


def test_the_engine_that_registered_last_answers_for_a_name():
    """Every served model's decode program is `jit_decode`: two engines
    in one process do not blur each other's tables."""
    kinds = {}
    for kind in ("hybrid", "cached"):
        eng = serve.ContinuousEngine(
            tiny_model(kind), max_slots=2, prefill_window=6,
            prefix_cache_slots=0, draft_tokens=0, decode_steps=2)
        eng.lowered_programs()              # registers, runs nothing
        table = profiler.program_scopes(r"^jit_decode\(")["jit_decode"]
        kinds[kind] = {p.split("/")[1] for p, _ in table.values()
                       if p.startswith("layer")}
    assert "mamba" in kinds["hybrid"] and "attn" not in kinds["hybrid"]
    assert kinds["cached"] == {"attn", "mlp"}


def test_the_registry_is_bounded_and_keeps_the_newest(monkeypatch):
    monkeypatch.setattr(profiler, "_programs", collections.OrderedDict())
    monkeypatch.setattr(profiler, "PROGRAMS_CAP", 3)
    def add(k):
        def add(x):
            return x + k
        return jax.jit(add)

    fns = [add(k) for k in range(5)]
    for f in fns:
        profiler.register_program(f, (jnp.ones((2,)),))
    profiler.register_program(fns[2], (jnp.ones((2,)),))
    kept = profiler._registered(r"^jit_add\(")
    assert [p.fn for p in kept] == [fns[3], fns[4], fns[2]]
    assert profiler._registered("^jit_ad$") == []


def test_a_table_is_this_source_s_even_when_the_cache_holds_another_s(
        tmp_path, monkeypatch):
    """jax's persistent cache keys a program without its `op_name`
    metadata: a program that differs from a cached one in its scopes
    alone LOADS the other's executable, scopes and all. The accessor
    compiles anew, past the cache, and leaves nothing in it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setattr(profiler, "_programs", collections.OrderedDict())
    before = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}

    def program(scope):
        def scoped(x, w):
            with jax.named_scope(scope):
                for _ in range(4):
                    x = jnp.tanh(x @ w)
            return x
        return jax.jit(scoped)

    x = jnp.ones((16, 16))
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        cc.reset_cache()
        program("alpha")(x, x).block_until_ready()
        beta = program("beta")
        beta(x, x).block_until_ready()
        running = scope_table(beta.lower(x, x).compile())
        profiler.register_program(beta, (x, x))
        held = sorted(os.listdir(tmp_path))
        table = profiler.program_scopes(r"^jit_scoped\(")["jit_scoped"]
        assert held and sorted(os.listdir(tmp_path)) == held
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()
    assert {p for p, _ in table.values()} == {"", "beta"}
    assert set(table) == set(running)       # the same instructions
    # (what the running executable says is jax's to decide: today it is
    # the cached `alpha`)
    assert {p for p, _ in running.values()} <= {"", "alpha", "beta"}


# -- scopes where the device's time had none ----------------------------------
def two_block_net():
    mx.seed(3)
    net = nn.HybridSequential()
    first, second = nn.HybridSequential(), nn.HybridSequential()
    first.add(nn.Dense(16, activation="relu", in_units=8))
    second.add(nn.Dense(16, activation="relu", in_units=16),
               nn.Dense(4, in_units=16))
    net.add(first, second)
    net.initialize()
    return net


def toy_step(net):
    loss_fn = gluon.loss.L2Loss()
    step = FusedTrainStep(net, lambda n, x, y: loss_fn(n(x), y).mean(),
                          opt_mod.create("sgd", learning_rate=0.1,
                                         momentum=0.9))
    rng = np.random.RandomState(0)
    return step, (rng.randn(8, 8).astype(np.float32),
                  rng.randn(8, 4).astype(np.float32))


def test_a_block_runs_under_its_registered_name_only_while_traced(
        monkeypatch):
    net = two_block_net()
    x = mx.np.array(np.ones((2, 8), np.float32))
    eager = net(x).asnumpy()

    def refuse(name):
        raise AssertionError(f"an eager call entered the scope {name!r}")

    from incubator_mxnet_tpu.gluon import block as block_mod
    asked = []

    def in_trace(args, real=block_mod._in_trace):
        asked.append(real(args))
        return asked[-1]

    with monkeypatch.context() as mp:
        mp.setattr(jax, "named_scope", refuse)
        mp.setattr(block_mod, "_in_trace", in_trace)
        assert np.array_equal(net(x).asnumpy(), eager)
    # an eager call asks once a block (the net, its two halves, three
    # layers), a `HybridBlock`'s own `__call__` included
    assert asked == [False] * 6
    step, batch = toy_step(net)
    text = step.lowered(*batch).as_text(debug_info=True)
    for scope in ("jvp(forward)/0/0/", "jvp(forward)/1/0/",
                  "jvp(forward)/1/1/", "transpose(jvp(forward))/1/1/"):
        assert scope in text, scope
    where = set(scope_table(step.lowered(*batch).compile()).values())
    assert {("forward/0/0", "fwd"), ("forward/1/0", "fwd"),
            ("forward/1/1", "fwd"), ("forward/0/0", "bwd"),
            ("forward/1/1", "bwd"), ("update", "")} <= where
    net.hybridize()
    assert np.allclose(net(x).asnumpy(), eager, atol=1e-6)


@pytest.mark.parametrize("first", ["lowered", "flops_per_call", "call"])
def test_a_step_is_registered_whichever_of_its_entries_comes_first(
        first, monkeypatch):
    """`lowered` and `flops_per_call` build the jitted step too (for
    callers that cost-count before training): the real call after them
    still registers, and `lowered` is the registered program's own."""
    monkeypatch.setattr(profiler, "_programs", collections.OrderedDict())
    step, batch = toy_step(two_block_net())
    if first == "lowered":
        assert "jvp(forward)/0/0/" in step.lowered(*batch).as_text(
            debug_info=True)
    elif first == "flops_per_call":
        assert step.flops_per_call(*batch) > 0
    float(step(*batch).asnumpy())
    progs = profiler._registered(r"^jit_step\(")
    # one program: the cost-counting entry and the call agree on shapes
    assert len(progs) == 1 and progs[0].fn is step._jit
    assert progs[0].lower().in_avals == step.lowered(*batch).in_avals
    where = set(profiler.program_scopes(r"^jit_step\(")["jit_step"].values())
    assert {("forward/0/0", "fwd"), ("forward/1/1", "bwd"),
            ("update", "")} <= where


def scoped_share(compiled):
    """(instructions of the program's own equations that carry a scope of
    the program's, all of them): fusions by their own `op_name`, what a
    fusion holds and what carries no device work left out."""
    module = hlo.parse_module(compiled.as_text())
    fused = {c for comp in module.computations.values()
             for ins in comp.instructions if ins.opcode == "fusion"
             for c in ins.called}
    scoped, total, bare = 0, 0, collections.Counter()
    for name, comp in module.computations.items():
        if name in fused:
            continue
        for ins in comp.instructions:
            # (an `op_name` without a `/` is a reducer's or comparator's)
            if ins.opcode in TRIVIAL or not ins.op_name \
                    or "/" not in ins.op_name:
                continue
            total += 1
            if scope_of(ins.op_name)[0]:
                scoped += 1
            else:
                bare[ins.op_name] += 1
    return scoped, total, bare


def tiny_model(kind):
    if kind == "cached":
        return serve.CachedDecoder(
            serve.DecoderConfig(vocab=64, embed=32, layers=2, heads=4,
                                head_dim=8, max_len=48), seed=11)
    if kind == "hybrid":
        c = hd.HybridConfig(vocab=128, embed=64, layers=8, heads=4,
                            kv_heads=2, head_dim=16, mlp_hidden=128,
                            window=8, d_state=4, d_conv=4, expand=2,
                            max_len=64, dtype="float32")
        return hd.HybridDecoder(c, params=hd.init_hybrid_params(c, 1))
    if kind == "sparse_moe":
        c = sm.SparseMoEConfig(
            vocab=96, embed=64, heads=4, index_topk=8,
            indexer_types=("full", "shared", "shared", "full"),
            mlp_types=("dense", "sparse", "sparse", "sparse"),
            routed_experts=16, experts_per_token=2, held_count=4,
            max_len=48)
        return sm.SparseMoEDecoder(
            c, sm.init_sparse_moe_params(c, 1, sm.INIT_SCALES))
    if kind == "looped":
        c = ld.LoopedConfig(vocab=96, embed=64, layers=3, heads=4,
                            head_dim=16, mlp_hidden=128, ut_steps=3,
                            max_len=48)
        return ld.LoopedDecoder(c, ld.init_looped_params(c, 1))
    if kind == "mamba_moe":
        c = mmd.MambaMoEConfig(vocab=96, embed=32, pattern="ME*MEM*E",
                               mamba_heads=8, mamba_head_dim=4, n_groups=2,
                               ssm_state=8, chunk_size=4, heads=4, kv_heads=2,
                               head_dim=8, held_count=4, max_len=48)
        return mmd.MambaMoEDecoder(c, mmd.init_mamba_moe_params(c, 1))
    c = dm.DeltaMoEConfig(
        vocab=96, embed=64, heads=4, kda_lower_bound=-20.0,
        mixer_types=("kda", "kda", "mla", "kda"),
        mlp_types=("dense", "sparse", "sparse", "sparse"),
        routed_experts=16, experts_per_token=2, n_group=4, topk_group=2,
        held_count=4, max_len=48)
    return dm.DeltaMoEDecoder(
        c, dm.init_delta_moe_params(c, 1, dm.INIT_SCALES))


@pytest.mark.parametrize("kind", ["cached", "hybrid", "sparse_moe",
                                  "delta_moe", "looped", "mamba_moe",
                                  "train_step"])
def test_nine_tenths_of_a_program_run_under_a_scope_of_its_own(kind):
    """A later PR cannot add nameless device work unnoticed: of the
    compiled decode program's (the fused step's) own instructions, those
    under no scope are the `lax.scan`'s machinery."""
    if kind == "train_step":
        step, batch = toy_step(two_block_net())
        compiled = step.lowered(*batch).compile()
    else:
        eng = serve.ContinuousEngine(
            tiny_model(kind), max_slots=2, prefill_window=6,
            prefix_cache_slots=0, draft_tokens=0, decode_steps=2)
        compiled = eng.lowered_programs()["decode"].compile()
    scoped, total, bare = scoped_share(compiled)
    assert total >= 30, total
    assert scoped >= 0.9 * total, (scoped, total, bare.most_common(12))
    if kind == "looped":
        # the pass is a loop inside the scan's body: what is left is the
        # machinery of the two loops, the program's own code never
        assert all(re.search(r"/while(/body|/cond)?(/[\w\-]+)?$", name)
                   for name in bare), bare
        paths = {p for p, _ in scope_table(compiled).values()}
        assert "head/loop_norm" in paths and "layer2/attn" in paths
    elif kind != "train_step":
        # nothing of the program's own code: what is left is the scan's
        assert all("/while" in name and "closed_call/" not in name
                   for name in bare), bare
    if kind == "mamba_moe":
        # the four parts of a Mamba-2 layer, each where the partition
        # metrics' mixer pattern takes it
        paths = {p for p, _ in scope_table(compiled).values()}
        assert {f"layer3/mamba/{part}" for part in
                ("proj", "conv", "state", "out")} <= paths, sorted(paths)
        assert {"layer2/attn", "layer1/router", "layer1/experts",
                "layer7/shared_expert", "head"} <= paths
