"""The paged decode-attention kernel (ops/paged_attention.py, ISSUE 33).

On the CPU the Pallas kernel runs in interpret mode against the path it
replaces in `decode_paged`: `gather_block_kv` + `cached_sdpa` over a
dense view.  Interpret mode cannot see a misaligned slice or too much
VMEM, so the three serve cells' real shapes are also compiled for a
described (not attached) TPU v5e, skipped where none can be described,
and so are the latent prefill kernel's (ops/mla_attention.py) at the
latent cell's chunk and the f32 view its check scores through, and the
latent decode kernel's two passes at both latent cells' shapes.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from singa_tpu.ops import kv_cache as kv_ops
from singa_tpu.ops.paged_attention import paged_attention, tiles

BS, MB, D = 8, 5, 64          # block size, blocks a table row, head size
MAX_LEN = BS * MB
WINDOW = 2 * BS + 3           # smaller than the longest length


def _case(H, K, dtype, seed):
    """Random pools, a shuffled table and one slot per length worth
    testing; the last slot is inactive: length 1 on the null block."""
    rng = np.random.RandomState(seed)
    lengths = np.asarray([1, BS, BS + 1, MAX_LEN, 2 * BS + 5, 1], np.int32)
    S = len(lengths)
    N = S * MB + 1
    pools = [jnp.asarray(rng.randn(N, BS, K, D), dtype) for _ in range(2)]
    q = jnp.asarray(rng.randn(S, H, D), dtype)
    tables = rng.permutation(np.arange(1, N)).reshape(S, MB).astype(np.int32)
    tables[-1] = 0
    return q, pools, tables, lengths


def _poisoned(pool, tables, lengths):
    """`pool` with NaN in every block of a table row past the slot's
    length: reading one of them shows in the output."""
    dead = [tables[s, i] for s in range(len(lengths))
            for i in range((lengths[s] - 1) // BS + 1, MB) if tables[s, i]]
    return pool.at[np.asarray(dead)].set(jnp.nan)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("window", [0, WINDOW])
@pytest.mark.parametrize("H,K", [(32, 8), (32, 4), (8, 2)])
def test_kernel_equals_the_gathered_view(H, K, window, dtype):
    dtype = jnp.dtype(dtype)
    q, (kp, vp), tables, lengths = _case(H, K, dtype, seed=H + K + window)
    dk, dv = kv_ops.gather_block_kv(kp, vp, tables)
    want = kv_ops.cached_sdpa(q[:, None], dk, dv, limit=lengths,
                              window=window or None)[:, 0]
    got = paged_attention(q, _poisoned(kp, tables, lengths),
                          _poisoned(vp, tables, lengths), tables, lengths,
                          window=window)
    assert got.shape == want.shape and got.dtype == want.dtype
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert np.isfinite(got).all()     # no block past a length was read
    # a few ulp of the dtype at the outputs' size (|o| is O(1))
    ulp = float(jnp.finfo(dtype).eps)
    np.testing.assert_allclose(got, want, rtol=4 * ulp, atol=4 * ulp)


def test_paged_caches_take_the_models_two_calls():
    """`update_cache` + `cached_sdpa` on `PagedKV` caches: the token
    lands in the pool where the tick says, and attention over the
    blocks equals the view path's with the token written into the
    view."""
    H, K = 8, 2
    q, (kp, vp), tables, lengths = _case(H, K, jnp.float32, seed=5)
    S = len(lengths)
    rng = np.random.RandomState(6)
    k_new, v_new = (jnp.asarray(rng.randn(S, 1, K, D), jnp.float32)
                    for _ in range(2))
    pos = lengths - 1
    block = tables[np.arange(S), pos // BS]
    off = pos % BS
    ck, cv = (kv_ops.PagedKV(p, tables, block, off) for p in (kp, vp))
    ck, cv = kv_ops.update_cache(ck, cv, k_new, v_new, pos)
    np.testing.assert_array_equal(np.asarray(ck.pool[block, off]),
                                  np.asarray(k_new[:, 0]))
    got = kv_ops.cached_sdpa(q[:, None], ck, cv, limit=pos + 1,
                             window=WINDOW)
    dk, dv = kv_ops.gather_block_kv(kp, vp, tables)
    dk, dv = kv_ops.update_cache(dk, dv, k_new, v_new, jnp.asarray(pos))
    want = kv_ops.cached_sdpa(q[:, None], dk, dv, limit=pos + 1,
                              window=WINDOW)
    assert got.shape == want.shape == (S, 1, H, D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("pool,kernel", [
    # the three cells' arenas; f32 of one KV head fills a sublane
    ((2049, 32, 8, 128), True), ((2049, 32, 4, 128), True),
    ((5121, 32, 2, 128), True), ((9, 16, 1, 128), "float32"),
    # a head that is no whole lane row, an odd number of KV heads
    ((9, 8, 4, 64), False), ((9, 8, 3, 128), False),
    # a block that is no whole (16, 128) tile
    ((9, 4, 2, 128), False),
])
def test_which_pools_read_blocks(monkeypatch, pool, kernel):
    """The path is chosen by what the pool is and by the platform: a
    plain bf16/f32 pool the kernel tiles, on a TPU; never here."""
    for dtype in ("bfloat16", "float32"):
        want = kernel is True or kernel == dtype
        assert tiles(pool, dtype) is want
        c = jnp.zeros((2,) + pool[1:], dtype)
        assert kv_ops.reads_blocks(c) is False          # the CPU
        monkeypatch.setattr(kv_ops, "on_tpu", lambda: True)
        assert kv_ops.reads_blocks(c) is want
        q = kv_ops.QuantKV(jnp.zeros(c.shape, jnp.int8),
                           jnp.ones(c.shape[:2] + (1, 1), jnp.float32))
        assert kv_ops.reads_blocks(q) is False
        monkeypatch.undo()


# -- the real shapes, compiled for a TPU v5e that is described, not attached --

@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled(fn, *args):
    """`fn` compiled for the described chip.  Such a compile is written
    to the persistent cache and cannot be read back without a chip:
    keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(fn).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("S,H,K,max_blocks,window,dtype", [
    (32, 32, 8, 64, 0, "bfloat16"),        # serve-chat-closed
    (32, 32, 4, 64, 1024, "bfloat16"),     # serve-code-closed, sliding
    (32, 32, 4, 64, 0, "bfloat16"),        # serve-code-closed, full
    (64, 8, 2, 80, 0, "bfloat16"),         # serve-reason-closed
    (32, 32, 8, 64, 0, "float32"),         # an f32 arena on the chip
])
def test_the_cells_shapes_compile_for_a_v5e(one_chip, S, H, K, max_blocks,
                                            window, dtype):
    """Mosaic takes the kernel at the benchmark's shapes: block copies
    aligned to the pools' tiling, the chunk buffers inside VMEM."""
    bs, d = 32, 128
    pool = (S * max_blocks + 1, bs, K, d)
    assert tiles(pool, dtype)

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    def attend(q, kp, vp, tables, lengths):
        return paged_attention(q, kp, vp, tables, lengths, window=window,
                               interpret=False)

    text = _compiled(
        attend, arg((S, H, d), dtype), arg(pool, dtype), arg(pool, dtype),
        arg((S, max_blocks), "int32"), arg((S,), "int32")).as_text()
    assert text.count("tpu_custom_call") == 1
    # the pools go in as they are: a bitcast to (N, bs·K, D), and no
    # copy or relayout of an arena under either shape
    arena = (f"[{pool[0]},{bs},{K},{d}]", f"[{pool[0]},{bs * K},{d}]")
    ops = [m.group(2) for m in re.finditer(
        r"= \w+(\[[\d,]*\])(?:\{[^}]*\})? ([\w-]+)\(", text)
        if m.group(1) in arena]
    assert sorted(set(ops)) == ["bitcast", "parameter"], ops


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_latent_prefill_compiles_for_a_v5e(one_chip, dtype):
    """Mosaic takes the latent prefill kernel at `serve-longdoc-closed`'s
    chunk, 256 rows x 20 heads over an 18,432-row view of 512 + 128
    lanes, and at `serve-think-closed`'s, 256 rows x 32 heads over a
    12,544-row view (24 and a half KV tiles, the last padded with zero
    rows to 12,800 inside the call's program), in bf16 (the timed
    program) and in f32 at HIGHEST (the check's scorer), its tiles
    inside VMEM; one call, the view its operand as the engine gathers
    it."""
    from singa_tpu.ops.mla_attention import (mla_prefill_attention,
                                             prefill_tiles)

    def arg(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    def attend(ql, qr, view, rope_view, limit):
        return mla_prefill_attention(ql, qr, view, rope_view, limit,
                                     scale=1 / 16, interpret=False)

    T, r, dr = 256, 512, 64
    for H, S, tiled in ((20, 18432, 18432), (32, 12544, 12800)):
        assert prefill_tiles((1, S, r), dtype)
        text = _compiled(attend, arg((1, T, H, r)), arg((1, T, H, dr)),
                         arg((1, S, r)), arg((1, S, 128)),
                         arg((), "int32")).as_text()
        calls = [line for line in text.splitlines()
                 if "tpu_custom_call" in line]
        assert len(calls) == 1 and "mla_prefill_attention" in calls[0]
        short = {"bfloat16": "bf16", "float32": "f32"}[dtype]
        assert f"{short}[1,{tiled},{r}]" in calls[0]



@pytest.mark.parametrize("S,H,max_blocks", [
    (32, 20, 576),          # serve-longdoc-closed
    (64, 32, 384),          # serve-think-closed
])
def test_the_latent_decode_compiles_for_a_v5e(one_chip, S, H, max_blocks):
    """Mosaic takes both passes of the latent decode kernel at the two
    latent cells' shapes, bf16 pools of 32-row blocks: the groups' shared
    pass (their members' heads stacked as the MXU's rows) and the
    slots' own pass, with the plan handed in and made in the program."""
    from singa_tpu.ops.mla_attention import mla_decode_attention

    def arg(shape, dt="bfloat16"):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    def attend(ql, qr, lat, rop, tables, lengths, plan=None):
        return mla_decode_attention(ql, qr, lat, rop, tables, lengths, plan,
                                    scale=1 / 16, interpret=False)

    pools = (arg((S * max_blocks + 1, 32, 512)),
             arg((S * max_blocks + 1, 32, 128)))
    for plan in ((), (arg((2, S), "int32"),)):
        text = _compiled(attend, arg((S, H, 512)), arg((S, H, 128)), *pools,
                         arg((S, max_blocks), "int32"), arg((S,), "int32"),
                         *plan).as_text()
        calls = [line for line in text.splitlines()
                 if "tpu_custom_call" in line]
        assert len(calls) == 2
        assert any("mla_decode_attention_shared" in c for c in calls)
