"""The flash kernels' two new axes (ops/pallas/flash_attention.py):
key/value heads picked by the index map for a GROUP of query heads, and a
causal WINDOW whose grid steps and strips behind the band are skipped —
forward and all three gradients against _sdpa_reference, the bounds
functions against a count over the mask itself, and what a call counts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nn.functional.attention import _sdpa_reference
from paddle_tpu.ops.pallas import attention_core as core
from paddle_tpu.ops.pallas import flash_attention as fa


def _out_and_grads(f, q, k, v):
    out, vjp = jax.vjp(f, q, k, v)
    return [np.asarray(x.astype(jnp.float32))
            for x in (out, *vjp(jnp.cos(out).astype(out.dtype)))]


@pytest.mark.parametrize("T,H,KVH,D,window,dtype", [
    # 7 : 1, one lone block, a window that is no multiple of the strip
    (384, 7, 1, 128, 100, jnp.float32),
    (384, 7, 1, 128, 100, jnp.bfloat16),
    # 1 : 1 over 2 x 4 grid blocks of 1024 x 512: both edges cross blocks
    # at four distances, none lies wholly inside
    (2048, 1, 1, 128, 600, jnp.float32),
    # 2 : 1, T no multiple of 1024: 3 x 3 square blocks of 512, one of
    # them wholly behind the band
    (1536, 2, 1, 128, 700, jnp.float32),
    # full causal attention, grouped: no band at all
    (512, 4, 2, 128, None, jnp.float32),
    # a head dim no lane block holds a group of: k, v repeated, two
    # heads a block, the window all the same
    (1024, 4, 2, 64, 300, jnp.float32),
], ids=["g7_lone_f32", "g7_lone_bf16", "g1_blocks", "g2_t1536",
        "g2_causal", "d64_repeat"])
def test_forward_and_gradients_match_the_reference(T, H, KVH, D, window,
                                                   dtype):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, T, H, D)), dtype)
    k, v = (jnp.asarray(rng.standard_normal((1, T, KVH, D)), dtype)
            for _ in range(2))
    flash = lambda q, k, v: fa.flash_attention_arrays(
        q, k, v, causal=True, window=window, interpret=True)
    ref = lambda q, k, v: _sdpa_reference(
        *(x.astype(jnp.float32) for x in (q, k, v)), is_causal=True,
        window=window)
    got = _out_and_grads(flash, q, k, v)
    want = _out_and_grads(ref, q, k, v)
    f32 = dtype == jnp.float32
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(
            a, b, atol=(5e-6 if f32 else 2.0 ** -6) * max(
                float(np.abs(b).max()), 1.0), err_msg=name)


def test_reference_takes_groups_and_a_window():
    """_sdpa_reference itself, against the definition: query head i on
    key/value head i // group, key j visible iff j <= t and t - j < w."""
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((2, 12, 6, 8)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((2, 12, 2, 8)), jnp.float32)
            for _ in range(2))
    got = np.asarray(_sdpa_reference(q, k, v, is_causal=True, window=5))
    for h in range(6):
        s = np.einsum("bqd,bkd->bqk", q[:, :, h], k[:, :, h // 3]) / 8 ** .5
        t, j = np.arange(12)[:, None], np.arange(12)[None]
        s = np.where((j <= t) & (t - j < 5), s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        np.testing.assert_allclose(
            got[:, :, h], np.einsum("bqk,bkd->bqd", p, v[:, :, h // 3]),
            atol=1e-5)


@pytest.mark.parametrize("t,tiles,window", [
    (1024, (256, 256), 300), (1024, (128, 128), 512), (2048, (256, 128), 700),
    (1024, (128, 256), 1), (512, (128, 128), 511), (96, (96, 96), 40),
])
def test_band_bounds_are_the_brute_force_count(t, tiles, window):
    """window_kv_tiles / window_q_tiles beside their causal twins against
    the band's own mask, tile by tile; visited_tile_share counts what
    they visit."""
    tq, tk = tiles
    nq, nk = t // tq, t // tk
    ahead = np.arange(t)[:, None] - np.arange(t)[None]
    keep = (ahead >= 0) & (ahead < window)
    behind = ahead >= window        # past the trailing edge
    visited = 0
    for i in range(nq):
        first, first_full = core.window_kv_tiles(i * tq, tq, tk, nk, window)
        _, n_visit = core.causal_kv_tiles(i * tq, tq, tk, nk)
        for j in range(nk):
            tile = (slice(i * tq, (i + 1) * tq), slice(j * tk, (j + 1) * tk))
            assert (j < first) == bool(behind[tile].all()), (i, j)
            assert (j >= first_full) == (not behind[tile].any()), (i, j)
            if first <= j < n_visit:
                visited += 1
            else:
                assert not keep[tile].any()
    assert core.visited_tile_share(t, t, tiles, True, window) == \
        visited / (nq * nk)
    for j in range(nk):
        n_full, n_visit = core.window_q_tiles(j * tk, tk, tq, nq, window)
        for i in range(nq):
            tile = (slice(i * tq, (i + 1) * tq), slice(j * tk, (j + 1) * tk))
            assert (i < n_full) == (not behind[tile].any()), (i, j)
            assert (i >= n_visit) == bool(behind[tile].all()), (i, j)


@pytest.mark.parametrize("t,d,window", [(4096, 128, 1024), (2048, 128, 600),
                                        (4096, 64, 1024), (1536, 128, 700)])
def test_grid_steps_behind_the_band_hold_their_block_index(t, d, window):
    """A grid step wholly behind the band runs nothing and moves
    nothing: its index is held at the nearest block the band reaches,
    below as above the diagonal; the distances at which an edge crosses
    a block are the ones the kernels build bodies for."""
    b = core.choose_flash_blocks(t, t, d)
    bq, bk = b.block_q, b.block_k
    nq, nk = t // bq, t // bk
    ahead = np.arange(t)[:, None] - np.arange(t)[None]
    keep = (ahead >= 0) & (ahead < window)
    block = lambda i, j: keep[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk]
    crossed, inside = core.band_offsets(t, t, bq, bk, window)
    assert set(crossed) == {i * bq - j * bk for i in range(nq)
                            for j in range(nk)
                            if block(i, j).any() and not block(i, j).all()}
    assert inside == any(block(i, j).all() for i in range(nq)
                         for j in range(nk))
    i32 = np.int32
    _, col, _ = fa._index_maps(3, True, b, nq, window=window)
    row, _, _ = fa._index_maps(3, True, b, nq, kv_major=True, window=window)
    for i in range(0, nq, max(nq // 4, 1)):
        seen = [j for j in range(nk) if block(i, j).any()]
        got = [int(col(i32(0), i32(1), i32(i), i32(j))[1]) for j in range(nk)]
        assert got == [min(max(j, seen[0]), seen[-1]) for j in range(nk)]
    for j in range(0, nk, max(nk // 4, 1)):
        seen = [i for i in range(nq) if block(i, j).any()]
        got = [int(row(i32(0), i32(1), i32(j), i32(i))[1]) for i in range(nq)]
        assert got == [min(max(i, seen[0]), seen[-1]) for i in range(nq)]


def test_group_index_maps_pick_the_key_value_head():
    """Forward and dq walk the query heads and read key/value head
    h // group; dkv walks the key/value heads with the group's members
    on an axis of their own."""
    b = core.choose_flash_blocks(2048, 2048, 128)
    i32 = np.int32
    row, col, stat = fa._index_maps(28, True, b, 2, group=7)
    for h in (0, 6, 7, 27):
        assert int(row(i32(0), i32(h), i32(1), i32(0))[2]) == h
        assert int(col(i32(0), i32(h), i32(1), i32(0))[2]) == h // 7
        assert int(stat(i32(1), i32(h), i32(1), i32(0))[0]) == 28 + h
    row, col, stat = fa._index_maps(28, True, b, 2, kv_major=True, group=7)
    for kvh, r in ((0, 0), (1, 3), (3, 6)):
        at = (i32(0), i32(kvh), i32(0), i32(r), i32(1))
        assert int(row(*at)[2]) == kvh * 7 + r
        assert int(col(*at)[2]) == kvh
        assert int(stat(*at)[0]) == kvh * 7 + r
        # no i64 in a map: Mosaic takes none
        assert "i64" not in str(jax.make_jaxpr(row)(*at))


def test_visited_share_and_counters_of_a_traced_call():
    from paddle_tpu.profiler import monitor
    monitor.reset_metrics()
    share = core.window_visited_share(16384, 128, 4096)
    # the pairs alone give 43.75%; the band's walk is by strips, the
    # causal walk at head dim 128 by 1024 x 512 blocks
    assert 0.40 < share < 0.46
    assert core.window_visited_share(2048, 64, 2048) == pytest.approx(1.0)
    q = jax.ShapeDtypeStruct((1, 2048, 4, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 2048, 2, 128), jnp.bfloat16)
    call = lambda w: jax.eval_shape(lambda q, k, v: fa.flash_attention_arrays(
        q, k, v, causal=True, window=w, interpret=True), q, kv, kv)
    call(512)
    call(None)
    call(4096)      # the sequence fits in the window: no band
    snap = monitor.metrics_snapshot()
    assert snap["flash.calls.gqa"] == 3 and snap["flash.calls.window"] == 1
    assert snap["flash.calls.direct.g1"] == 3
    assert snap["flash.window.visited_share"]["avg"] == pytest.approx(
        100 * core.window_visited_share(2048, 128, 512))
    # the scope a trace and the compile record's `kernels` field show
    lowered = jax.jit(lambda q, k, v: fa.flash_attention_arrays(
        q, k, v, causal=True, window=512, interpret=True)).lower(q, kv, kv)
    assert "flash.direct.kv2.w512" in lowered.as_text(debug_info=True)
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention_arrays(*(jnp.zeros((1, 8, 1, 8)),) * 3,
                                  causal=False, window=4)
    with pytest.raises(ValueError, match="key/value heads"):
        fa.flash_attention_arrays(jnp.zeros((1, 8, 3, 8)),
                                  *(jnp.zeros((1, 8, 2, 8)),) * 2)


def test_no_window_and_equal_heads_trace_to_the_kernels_as_they_were():
    """Cells 1 and 2's calls: a 4-D dkv grid, no body per distance, the
    causal positions' two bodies — what tests/test_attention_blocking.py
    holds op for op; here, that the new arguments' defaults change
    nothing in the traced kernel."""
    x = jax.ShapeDtypeStruct((1, 2048, 2, 128), jnp.bfloat16)
    f = lambda w: str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(fa.flash_attention_arrays(
            q, k, v, causal=True, window=w, interpret=True)
            .astype(jnp.float32)), argnums=(0, 1, 2)))(x, x, x))
    plain, fits = f(None), f(2048)
    assert plain == fits
    banded = f(512)
    assert banded != plain and banded.count("cond[") > plain.count("cond[")
