"""The held experts' grouped matmul (`ops.fused.grouped_matmul`): the Pallas
kernel in interpret mode against the XLA loop it replaced on the chip,
kept here as a test-local oracle, and the kernel's place in a program.

  * the kernel route of `routed_experts` gives the oracle's rows and
    loads for both expert bodies (SiLU-gated over (d, 2F), relu^2 over an
    up stack stored (F, d)), in bfloat16 and float32, at a decode-sized
    and a chunk-sized T, for loads of 0, 1, bm and bm + 1 on an expert,
    every pair on one expert and a random mix; some tokens are pads, the
    held experts are not the first, and the stack's rows start past 0;
  * a program of several expert layers traces the kernel ONCE and lowers
    it into one function that every layer calls
    (`fused_stats()["grouped_matmul_traces"]`), and another process lowers
    the same program to the same text (the persistent cache's key).
"""
import functools
import hashlib
import os
import subprocess
import sys
import zlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from incubator_mxnet_tpu.models import mamba_moe_decoder as mm
from incubator_mxnet_tpu.models import sparse_moe_decoder as sm
from incubator_mxnet_tpu.ops import fused
from incubator_mxnet_tpu.ops import pallas_kernels as pk

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BODIES = {"swiglu": sm.swiglu_up, "relu2": mm.relu2_up}
# F in whole lane tiles: the kernel's widths in interpret mode as on the chip
D, F, K, HELD, FIRST, ROW0, EXPERTS = 16, 256, 2, 4, 4, 2, 16


def oracle_routed_experts(h, idx, gates, token_ok, w_up, w_down, held_first,
                          held, row0, up):
    """`routed_experts` as it was before the kernel: one XLA `while_loop`
    trip a block of at most `bm` rows of one expert."""
    T, k = idx.shape
    d = h.shape[-1]
    M = T * k
    bm = min(sm.EXPERT_BLOCK, -(-T // 16) * 16)
    local = idx - held_first
    mine = (local >= 0) & (local < held) & token_ok[:, None]
    group = jnp.where(mine, local, held).reshape(M)
    order = jnp.argsort(group)
    s_group = group[order]
    s_token = (order // k).astype(jnp.int32)
    s_gate = gates.reshape(M)[order]
    loads = jnp.sum(group[:, None] == jnp.arange(held)[None, :], 0,
                    dtype=jnp.int32)
    ends = jnp.cumsum(loads)
    starts = ends - loads
    blocks = (loads + bm - 1) // bm
    block_ends = jnp.cumsum(blocks)

    def body(carry):
        i, out = carry
        e = jnp.sum(block_ends <= i).astype(jnp.int32)
        r0 = starts[e] + (i - (block_ends[e] - blocks[e])) * bm
        rows = r0 + jnp.arange(bm)
        ok = rows < ends[e]
        rows = jnp.minimum(rows, M - 1)
        x = h[s_token[rows]]
        y = jnp.dot(up(x, jax.lax.dynamic_index_in_dim(w_up, row0 + e, 0,
                                                        False)),
                    jax.lax.dynamic_index_in_dim(w_down, row0 + e, 0, False),
                    preferred_element_type=jnp.float32)
        y = y * jnp.where(ok, s_gate[rows], 0.0)[:, None]
        return i + 1, jax.lax.dynamic_update_slice_in_dim(
            out, y.astype(out.dtype), r0, 0)

    n_blocks = block_ends[-1]
    _, out = jax.lax.while_loop(
        lambda carry: carry[0] < n_blocks, body,
        (jnp.int32(0), jnp.zeros((M + bm, d), h.dtype)))
    place = jnp.zeros((M,), jnp.int32).at[order].set(
        jnp.arange(M, dtype=jnp.int32))
    pairs = jnp.where((s_group < held)[place][:, None], out[place], 0)
    y = jnp.sum(pairs.reshape(T, k, d).astype(jnp.float32), 1)
    return y.astype(h.dtype), loads


@pytest.fixture
def interpret():
    prev = fused.set_interpret(True)
    yield
    fused.set_interpret(prev)


def _weights(rng, body, dtype, f=F):
    n = ROW0 + HELD + 1                # rows on both sides of the share
    up_shape = (n, f, D) if BODIES[body].transposed else (n, D, 2 * f)
    scale = 1.2 / np.sqrt(f)
    w_up = jnp.asarray(rng.standard_normal(up_shape) * 0.3, dtype)
    w_dn = jnp.asarray(rng.standard_normal((n, f, D)) * scale, dtype)
    return w_up, w_dn


def _routing(rng, case, T, bm):
    """(idx (T, K), token_ok (T,)) with the case's loads on the held
    experts [FIRST, FIRST + HELD)."""
    elsewhere = rng.choice([e for e in range(EXPERTS)
                            if not FIRST <= e < FIRST + HELD], size=(T, K))
    idx = elsewhere.copy()
    ok = rng.random(T) < 0.9
    on = FIRST + 1
    flat = idx.reshape(-1)
    live = np.flatnonzero(np.repeat(ok, K))     # pairs of real tokens
    if case == "one":
        flat[live[0]] = on
    elif case in ("bm", "bm+1"):
        n = bm + (case == "bm+1")
        assert live.size >= n
        flat[live[rng.permutation(live.size)[:n]]] = on
    elif case == "all on one":
        flat[:] = on
    elif case == "mixed":
        idx = rng.integers(0, EXPERTS, size=(T, K))
    return jnp.asarray(idx, jnp.int32), jnp.asarray(ok)


def _blocks(idx, ok, bm):
    """The blocks the kernel walks: ceil(load / bm) a held expert."""
    local = np.asarray(idx) - FIRST
    mine = (local >= 0) & (local < HELD) & np.asarray(ok)[:, None]
    return sum(-(-int((mine & (local == e)).sum()) // bm)
               for e in range(HELD))


CASES = ("none", "one", "bm", "bm+1", "all on one", "mixed")


@functools.lru_cache(maxsize=None)
def _jitted(body):
    """(kernel route, oracle) of a body, jitted once: the cases of one
    shape and type are data to the same programs (traced under the
    `interpret` fixture, the kernel's route)."""
    up = BODIES[body]
    return (jax.jit(lambda *a: sm.routed_experts(*a, FIRST, HELD, row0=ROW0,
                                                 up=up)),
            jax.jit(lambda *a: oracle_routed_experts(*a, FIRST, HELD, ROW0,
                                                     up)))


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("T", [24, 1024], ids=["decode", "chunk"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("body", sorted(BODIES))
def test_kernel_matches_the_xla_loop(interpret, body, dtype, T, case):
    rng = np.random.default_rng(zlib.crc32(f"{body}{dtype}{T}{case}".encode()))
    bm = sm.expert_block(T)
    h = jnp.asarray(rng.standard_normal((T, D)), dtype)
    gates = jnp.asarray(rng.random((T, K)), jnp.float32)
    w_up, w_dn = _weights(rng, body, dtype)
    idx, ok = _routing(rng, case, T, bm)
    kernel, oracle = _jitted(body)
    y, loads, blocks = kernel(h, idx, gates, ok, w_up, w_dn)
    want, want_loads = oracle(h, idx, gates, ok, w_up, w_dn)
    np.testing.assert_array_equal(np.asarray(loads), np.asarray(want_loads))
    assert int(blocks) == _blocks(idx, ok, bm)
    want = np.asarray(want, np.float32)
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(y, np.float32), want,
                               atol=tol * max(1.0, np.abs(want).max()),
                               rtol=tol)
    if case == "none":
        assert not np.asarray(y, np.float32).any()


@pytest.mark.parametrize("body", sorted(BODIES))
def test_kernel_sums_the_hidden_width_over_its_tiles(interpret, monkeypatch,
                                                     body):
    """A step budget of a quarter expert cuts F into 4 lane tiles: the
    float32 accumulator sums them."""
    up = BODIES[body]
    f = 4 * 128
    monkeypatch.setattr(pk, "_EXPERT_STEP_BYTES",
                        (3 if up.gated else 2) * D * 4 * f // 4)
    assert pk.expert_tile(f, D, up.gated, 4) == f // 4
    fused._grouped_entry.cache_clear()
    try:
        rng = np.random.default_rng(7)
        T = 24
        h = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
        gates = jnp.asarray(rng.random((T, K)), jnp.float32)
        w_up, w_dn = _weights(rng, body, "float32", f)
        idx, ok = _routing(rng, "mixed", T, sm.expert_block(T))
        y, _, _ = sm.routed_experts(h, idx, gates, ok, w_up, w_dn, FIRST,
                                    HELD, row0=ROW0, up=up)
        want, _ = oracle_routed_experts(h, idx, gates, ok, w_up, w_dn, FIRST,
                                        HELD, ROW0, up)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)
    finally:
        fused._grouped_entry.cache_clear()


def test_the_route_follows_the_chips_tiling_rule(interpret):
    """Interpret mode takes the chip's route: the kernel where F has a
    tile of whole lane tiles (ling3f's 768 at 384, glm52's 2048 at 256), the
    XLA loop where it has none (nemotron's 1856), which is no fallback."""
    assert pk.expert_tile(768, 2560, True, 2) == 384
    assert pk.expert_tile(2048, 6144, True, 2) == 256
    assert pk.expert_tile(1856, 2688, False, 2) == 0
    assert pk.expert_tile(96, D, False, 4) == 0
    for f, kernel in ((96, False), (128, True)):
        rng = np.random.default_rng(f)
        T = 24
        h = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
        gates = jnp.asarray(rng.random((T, K)), jnp.float32)
        w_up, w_dn = _weights(rng, "relu2", "float32", f)
        idx, ok = _routing(rng, "mixed", T, sm.expert_block(T))
        before = fused.fused_stats()
        y, loads, blocks = jax.jit(lambda *a: sm.routed_experts(
            *a, FIRST, HELD, row0=ROW0, up=mm.relu2_up))(
                h, idx, gates, ok, w_up, w_dn)
        after = fused.fused_stats()
        assert after["pallas_calls"] - before["pallas_calls"] == int(kernel)
        assert after["fallback_calls"] == before["fallback_calls"]
        want, want_loads = oracle_routed_experts(
            h, idx, gates, ok, w_up, w_dn, FIRST, HELD, ROW0, mm.relu2_up)
        np.testing.assert_array_equal(np.asarray(loads), np.asarray(want_loads))
        # the loop walks blocks of the parent's size, the kernel the load's
        bm = sm.expert_block(T) if kernel else min(sm.EXPERT_BLOCK, 32)
        assert int(blocks) == _blocks(idx, ok, bm)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)


def test_tokens_beyond_the_kernels_vmem_fall_back_counted(interpret,
                                                           monkeypatch):
    """h and y stay whole in VMEM, so the footprint grows with the call's
    tokens: past `_EXPERT_VMEM` the call takes the loop and counts a
    fallback instead of failing to compile. Every expert program served
    (at most 1024 tokens a call) fits at its cell's widths; 2048 tokens at
    glm52's do not."""
    def fits(t, d, f, gated):
        tf = pk.expert_tile(f, d, gated, 2)
        b = pk.grouped_vmem_bytes(t, d, tf, sm.expert_block(t), gated, 2)
        return b + b // 4 <= pk._EXPERT_VMEM
    assert fits(1024, 6144, 2048, True) and fits(1024, 2560, 768, True)
    assert not fits(2048, 6144, 2048, True)
    rng = np.random.default_rng(11)
    T = 64
    h = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    gates = jnp.asarray(rng.random((T, K)), jnp.float32)
    w_up, w_dn = _weights(rng, "swiglu", "float32")
    idx, ok = _routing(rng, "mixed", T, sm.expert_block(T))
    monkeypatch.setattr(pk, "_EXPERT_VMEM", pk.grouped_vmem_bytes(
        T - 16, D, F, sm.expert_block(T), True, 4))
    before = fused.fused_stats()
    y, _, blocks = sm.routed_experts(h, idx, gates, ok, w_up, w_dn, FIRST,
                                     HELD, row0=ROW0, up=sm.swiglu_up)
    after = fused.fused_stats()
    assert after["fallback_calls"] == before["fallback_calls"] + 1
    assert after["pallas_calls"] == before["pallas_calls"]
    want, _ = oracle_routed_experts(h, idx, gates, ok, w_up, w_dn, FIRST,
                                    HELD, ROW0, sm.swiglu_up)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5,
                               rtol=1e-5)
    assert int(blocks) == _blocks(idx, ok, min(sm.EXPERT_BLOCK, T))


def test_blocks_of_one_expert_and_the_bound_repeats_the_last():
    """The layout: each expert's pairs from a block of their own, and the
    static bound's tail a copy of the last real block (nothing to fetch)."""
    loads = jnp.asarray([0, 5, 40, 0, 1], jnp.int32)
    ends = jnp.cumsum(loads)
    expert, n, shift = (np.asarray(a) for a in pk.grouped_blocks(
        ends - loads, ends, 16, 8))
    assert int(n[0]) == 5
    np.testing.assert_array_equal(expert, [1, 2, 2, 2, 4, 4, 4, 4])
    # rows [0, 5) from row 0, [5, 45) from block 1, [45, 46) from block 4
    np.testing.assert_array_equal(shift[[1, 2, 4]], [0, 11, 19])
    # nothing held this call: no real block
    zero = jnp.zeros((5,), jnp.int32)
    assert int(pk.grouped_blocks(zero, zero, 16, 8)[1][0]) == 0


def test_block_rows_follow_the_load_not_the_call():
    assert [sm.expert_block(t) for t in (24, 32, 64, 128, 1024, 4096)] \
        == [16, 16, 16, 16, 64, 128]


# ---------------------------------------------------------------------------
# the kernel's place in a program
# ---------------------------------------------------------------------------
def _tiny_decoders():
    """The three expert decoders at test size, their experts a lane tile
    wide (the kernel's widths): (name, model)."""
    from incubator_mxnet_tpu.models import delta_moe_decoder as dm
    sparse = sm.SparseMoEDecoder(sm.SparseMoEConfig(
        indexer_types=("full", "shared", "shared"),
        mlp_types=("dense", "sparse", "sparse"), expert_hidden=128,
        held_first=4, max_len=32))
    delta = dm.DeltaMoEDecoder(dm.DeltaMoEConfig(
        vocab=96, embed=64, heads=4, mixer_types=("kda", "mla", "kda"),
        mlp_types=("dense", "sparse", "sparse"), expert_hidden=128,
        routed_experts=16, experts_per_token=2, n_group=4, topk_group=2,
        held_first=4, held_count=4, max_len=32))
    mamba = mm.MambaMoEDecoder(mm.MambaMoEConfig(expert_hidden=128,
                                                 max_len=32))
    return [("sparse", sparse), ("delta", delta), ("mamba", mamba)]


def _lowered_decode(model):
    """The decode program of 2 steps over a 3-slot pool, lowered."""
    pool = model.new_pool(max_slots=3)
    (cache,) = pool.buffers()
    S = 3
    i32 = jnp.zeros((S,), jnp.int32)
    return model.decode_program(2).lower(
        model.params, cache, i32, i32, i32, jnp.zeros((S,), jnp.float32),
        i32, jnp.ones((S,), jnp.float32), jnp.zeros((S, 2), jnp.uint32))


def _expert_layers(model):
    c = model.config
    kinds = getattr(c, "mlp_types", None) or [mm.KINDS[p] for p in c.pattern]
    return sum(k in ("sparse", "moe") for k in kinds)


def test_a_program_traces_and_lowers_the_kernel_once(interpret):
    for name, model in _tiny_decoders():
        layers = _expert_layers(model)
        assert layers >= 2, name
        # (a jitted entry of its own: the decoders share shapes here)
        fused._grouped_entry.cache_clear()
        before = fused.fused_stats()["grouped_matmul_traces"]
        text = _lowered_decode(model).as_text()
        assert fused.fused_stats()["grouped_matmul_traces"] == before + 1, \
            name
        # one function holds the kernel; every expert layer calls it
        assert text.count("func.func private @entry") == 1, name
        assert text.count("call @entry(") == layers, name
        # a second program of the same shapes lowers no second trace
        again = fused.fused_stats()["grouped_matmul_traces"]
        model.decode_program(2, eos_id=0)
        _lowered_decode(model)
        assert fused.fused_stats()["grouped_matmul_traces"] == again, name


LOWER_IN_ANOTHER_PROCESS = """
import hashlib, sys
sys.path.insert(0, {root!r})
from incubator_mxnet_tpu.ops import fused
fused.set_interpret(True)
sys.path.insert(0, {tests!r})
import test_grouped_matmul as t
for name, model in t._tiny_decoders():
    text = t._lowered_decode(model).as_text()
    print(name, hashlib.sha256(text.encode()).hexdigest())
"""


def test_another_process_lowers_the_same_text(interpret):
    """Nothing of one process (a counter, an id, an order) reaches the
    lowered module: the persistent cache's key of every expert program
    (the module without its locations, which name the caller's frames) is
    the same in every process."""
    here = {name: hashlib.sha256(
        _lowered_decode(model).as_text().encode()).hexdigest()
        for name, model in _tiny_decoders()}
    done = subprocess.run(
        [sys.executable, "-c", LOWER_IN_ANOTHER_PROCESS.format(
            root=ROOT, tests=os.path.join(ROOT, "tests"))],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stderr[-2000:]
    there = dict(line.split() for line in done.stdout.strip().splitlines())
    assert there == here
