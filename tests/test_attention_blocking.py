"""MXU-shaped attention blocking: the shared q-block core, the blocked
serving kernel, the flash training kernel, and the dot-shape gate.

The contract under test (ISSUE 16 / attention_core.py): every score
dot either kernel emits is [M, D] x [D, Bk] with M >= MIN_DOT_ROWS,
reached by q-token blocking plus head folding (grouped-query models) —
WITHOUT changing the numbers:

- blocked serving kernel vs the dense per-token reference across q-block
  remainders, GQA folds, multi-block token counts, and pad rows (whose
  measured work stays exactly zero)
- the host (numpy) and traced (jnp) block-plan builders agree slot for
  slot, so the serving scheduler's precomputed plan is the plan the
  eager/jit fallback derives
- flash training kernel forward AND gradients vs a jnp.einsum reference
  (causal and full), through the shared online-softmax core
- the serving planner floors token buckets at MIN_Q_TOKENS, so the
  q-blocks the engine dispatches reach the MXU sublane tile
- tools/check_dot_shapes.py (the ratchet form of all of the above) runs
  green from tier-1
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import attention_core as core
from paddle_tpu.ops.pallas.paged_attention import (
    build_block_plan, ragged_paged_attention, ragged_work_plan)
from paddle_tpu.ops.pallas.paged_attention import _block_plan_jnp

pytestmark = pytest.mark.heavy  # interpret-mode kernels compile slowly

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _dense_ref(q, k_pages, v_pages, pt, seq, bd):
    """Per-token dense reference with grouped-query head mapping."""
    T, H, D = q.shape
    KVH = k_pages.shape[2]
    fold = H // KVH
    out = np.zeros((T, H, D), np.float32)
    for t in range(T):
        b = int(bd[t])
        if b <= 0:
            continue
        ks = k_pages[pt[seq[t]]].reshape(-1, KVH, D)[:b]
        vs = v_pages[pt[seq[t]]].reshape(-1, KVH, D)[:b]
        for h in range(H):
            s = ks[:, h // fold] @ q[t, h] / np.sqrt(D)
            e = np.exp(s - s.max())
            out[t, h] = (e / e.sum()) @ vs[:, h // fold]
    return out


def _random_case(rng, T, H, KVH, B, W, P=4, D=8, n_pages=12):
    q = rng.standard_normal((T, H, D)).astype(np.float32)
    kp = rng.standard_normal((n_pages, P, KVH, D)).astype(np.float32)
    vp = rng.standard_normal((n_pages, P, KVH, D)).astype(np.float32)
    # distinct non-zero pages per row: page 0 is the reserved pad page
    pt = (1 + rng.permutation(n_pages - 1)[:B * W]).reshape(B, W)
    pt = pt.astype(np.int32)
    seq = rng.integers(0, B, T).astype(np.int32)
    bd = rng.integers(0, P * W + 1, T).astype(np.int32)
    return q, kp, vp, pt, seq, bd


class TestBlockedKernelEquality:
    @pytest.mark.parametrize("T,H,KVH,B,W", [
        (8, 2, 2, 2, 3),    # fold 1: M comes from the token block
        (5, 4, 2, 2, 3),    # odd T: one 5-row block, fold 2
        (12, 6, 3, 3, 2),   # fold 2 over 3 kv heads
        (16, 8, 1, 2, 4),   # MQA: fold 8
    ])
    def test_matches_dense_reference(self, T, H, KVH, B, W):
        rng = np.random.default_rng(T * 100 + H)
        q, kp, vp, pt, seq, bd = _random_case(rng, T, H, KVH, B, W)
        bd[T // 2] = 0  # at least one pad row in every case
        out = ragged_paged_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(pt), jnp.asarray(seq), jnp.asarray(bd),
            interpret=True)
        ref = _dense_ref(q, kp, vp, pt, seq, bd)
        np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5)

    def test_small_q_block_splits_tokens_into_blocks(self):
        """Force multiple q-blocks (q_block < T) — block boundaries
        must not change the numbers, and the host plan for that block
        size must agree with the in-trace derivation."""
        rng = np.random.default_rng(7)
        T, H, KVH, B, W, P = 16, 2, 2, 2, 3, 4
        q, kp, vp, pt, seq, bd = _random_case(rng, T, H, KVH, B, W, P=P)
        ref = _dense_ref(q, kp, vp, pt, seq, bd)
        for q_block in (4, 8, 16):
            plan = build_block_plan(pt, seq, bd, P, q_block)
            out = ragged_paged_attention(
                jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                jnp.asarray(pt), jnp.asarray(seq), jnp.asarray(bd),
                interpret=True, q_block=q_block, block_plan=plan)
            np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5,
                                       err_msg=f"q_block={q_block}")

    def test_pad_rows_compute_zero_blocks(self):
        """A q-block of pure pads has blk_n == 0 — the DMA loop never
        starts — and the measured work counter stays the host formula
        (ceil(bound/P), 0 for pads) under any blocking."""
        rng = np.random.default_rng(3)
        T, P = 16, 4
        q, kp, vp, pt, seq, bd = _random_case(
            rng, T, 2, 2, 2, 3, P=P)
        bd[8:] = 0  # the whole second half pads: q-block 8..15 is empty
        seq[8:] = 0
        plan = build_block_plan(pt, seq, bd, P, 8)
        assert int(plan[3][1]) == 0  # second q-block: zero slots
        out, work = ragged_paged_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(pt), jnp.asarray(seq), jnp.asarray(bd),
            interpret=True, q_block=8, block_plan=plan,
            return_work=True)
        np.testing.assert_array_equal(np.asarray(work),
                                      ragged_work_plan(bd, P))
        assert np.asarray(out)[8:].any() == False  # noqa: E712
        np.testing.assert_allclose(
            np.asarray(out), _dense_ref(q, kp, vp, pt, seq, bd),
            atol=2e-5)

    def test_host_and_traced_block_plans_agree(self):
        """plan_ragged ships the numpy plan; eager/jit callers derive
        the jnp twin. Same slots, same order, same counts — or the
        serving path and the test-path kernels silently diverge."""
        rng = np.random.default_rng(11)
        for T, B, W, q_block in [(8, 2, 3, 8), (16, 3, 2, 4),
                                 (12, 2, 4, 12), (8, 1, 1, 8)]:
            P = 4
            pt = rng.integers(0, 10, (B, W)).astype(np.int32)
            seq = rng.integers(0, B, T).astype(np.int32)
            bd = rng.integers(0, P * W + 1, T).astype(np.int32)
            host = build_block_plan(pt, seq, bd, P, q_block)
            traced = _block_plan_jnp(jnp.asarray(pt), jnp.asarray(seq),
                                     jnp.asarray(bd), P, q_block)
            for name, h, t in zip(
                    ("blk_pages", "blk_seq", "blk_start", "blk_n"),
                    host, traced):
                # entries past blk_n are never read: compare the live
                # prefix per q-block, plus the counts exactly
                if name == "blk_n":
                    np.testing.assert_array_equal(h, np.asarray(t))
                    continue
                ta = np.asarray(t)
                for qb, n in enumerate(host[3]):
                    np.testing.assert_array_equal(
                        h[qb, :n], ta[qb, :n],
                        err_msg=f"{name}[{qb}] T={T} B={B} W={W}")

    def test_choose_q_block_respects_fold_cap(self):
        assert core.choose_q_block(256) == 128
        assert core.choose_q_block(256, cap=core.MXU_ROWS // 4) == 32
        assert core.choose_q_block(8) == 8
        assert core.choose_q_block(5) == 5      # odd: one block
        assert core.choose_q_block(1) == 1      # eager single token


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (list, tuple)) else (v,)):
            x = getattr(x, "jaxpr", x)          # ClosedJaxpr -> Jaxpr
            if hasattr(x, "eqns"):
                yield x


def _count(jaxpr, name):
    """How often primitive `name` stands under `jaxpr`, nested calls
    (jnp.where is one) included."""
    return sum((eqn.primitive.name == name)
               + sum(_count(sub, name) for sub in _sub_jaxprs(eqn))
               for eqn in jaxpr.eqns)


def _kernels(jaxpr):
    """{kernel name: its body's jaxpr} of every pallas_call below."""
    found = {}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] = eqn.params["jaxpr"]
        else:
            for sub in _sub_jaxprs(eqn):
                found.update(_kernels(sub))
    return found


def _kernel_operands(jaxpr):
    """{kernel name: its operands' shapes} of every pallas_call below."""
    found = {}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] = [tuple(v.aval.shape)
                                         for v in eqn.invars]
        else:
            for sub in _sub_jaxprs(eqn):
                found.update(_kernel_operands(sub))
    return found


class TestFlashKernel:
    def _ref(self, q, k, v, causal):
        D = q.shape[-1]
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
        if causal:
            mask = np.tril(np.ones(s.shape[-2:], bool))
            s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    @staticmethod
    def _loss(f):
        return lambda q, k, v: jnp.sum(f(q, k, v) * jnp.cos(f(q, k, v)))

    @staticmethod
    def _flash(causal):
        from paddle_tpu.ops.pallas.flash_attention import \
            flash_attention_arrays
        return lambda q, k, v: flash_attention_arrays(
            q, k, v, causal=causal, interpret=True).astype(jnp.float32)

    def _check(self, B, Tq, Tk, H, D, causal, dtype):
        rng = np.random.default_rng(0)
        q, k, v = (jnp.asarray(rng.standard_normal((B, T, H, D)), dtype)
                   for T in (Tq, Tk, Tk))
        flash = self._flash(causal)
        ref = lambda q, k, v: self._ref(q, k, v, causal)
        loss = self._loss
        # a bf16-sized tolerance: four roundings (2^-8) of the largest value
        f32 = dtype == jnp.float32
        out = ref(q, k, v)
        np.testing.assert_allclose(
            np.asarray(flash(q, k, v)), np.asarray(out),
            atol=2e-5 if f32 else 2.0 ** -6 * float(jnp.abs(out).max()))
        g_flash = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", g_flash, g_ref):
            assert a.dtype == dtype
            np.testing.assert_allclose(
                np.asarray(a.astype(jnp.float32)), np.asarray(b),
                atol=3e-4 if f32 else 2.0 ** -6 * float(jnp.abs(b).max()),
                err_msg=f"d{name}")

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("T", [32, 512, 2048],
                             ids=["one_tile", "sub_tiles", "grid_blocks"])
    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_and_grad_match_einsum(self, causal, T, dtype):
        b = core.choose_flash_blocks(T, T, 8)
        strips = max(b.block_q // b.fwd[0], b.block_q // b.dq[0],
                     b.block_k // b.dkv[1])
        assert T // b.block_q == {32: 1, 512: 1, 2048: 2}[T]
        assert (strips > 1) == (T > 32)
        self._check(2 if T == 32 else 1, T, T, 2 if T == 32 else 1, 8,
                    causal, dtype)

    @pytest.mark.parametrize("Tq,Tk,D,causal", [
        (256, 512, 8, False),    # Tq != Tk: a strip sees two kv tiles
        (512, 256, 8, False),
        (384, 384, 8, True),     # 3 x 128: a strip count that is odd
        (96, 96, 8, True),       # no multiple of 128 divides it: one tile
        (2048, 2048, 256, True),  # head dim 256: 1024 x 256 grid blocks,
                                  # the crossed ones whole under the mask
        (64, 1024, 8, True),     # one q block of 64 rows; most columns
                                 # see no row and get dk = dv = 0
        (512, 1024, 8, True),    # the same inside ONE 512 x 1024 block
        (1024, 2048, 8, True),   # a kv GRID block wholly above the
                                 # diagonal, with one q block: its
                                 # dk, dv are zeros all the same
        (2048, 1024, 8, True),   # q blocks wholly below the last kv block
    ])
    def test_unequal_and_odd_lengths(self, Tq, Tk, D, causal):
        dtype = jnp.bfloat16 if D == 256 else jnp.float32
        self._check(1, Tq, Tk, 1, D, causal, dtype)

    @staticmethod
    def _out_and_grads(f, q, k, v):
        """(out, dq, dk, dv), float32, under a cotangent that differs
        everywhere."""
        out, vjp = jax.vjp(f, q, k, v)
        return [np.asarray(x.astype(jnp.float32))
                for x in (out, *vjp(jnp.cos(out)))]

    @pytest.mark.parametrize("B,Tq,Tk,H,D,g,causal,dtype", [
        # GPT-medium's 16 heads of 64: 8 lane blocks of two
        (1, 256, 256, 16, 64, 2, True, jnp.bfloat16),
        (1, 128, 128, 16, 64, 2, False, jnp.float32),
        # gpt_small's 12: 6 blocks, and two batch rows
        (2, 128, 128, 12, 64, 2, True, jnp.float32),
        (2, 128, 128, 12, 64, 2, False, jnp.bfloat16),
        # gpt_1p3b's head dim: a lane block a head
        (1, 256, 256, 2, 128, 1, True, jnp.bfloat16),
        (1, 128, 128, 2, 128, 1, False, jnp.float32),
        # latent attention's: a head is two lane tiles
        (1, 128, 128, 2, 256, 1, True, jnp.float32),
        (1, 128, 128, 2, 256, 1, False, jnp.bfloat16),
        # Tk = 2 Tq
        (1, 128, 256, 4, 64, 2, True, jnp.float32),
        (1, 128, 256, 4, 64, 2, False, jnp.bfloat16),
        # four heads of 32 to a block
        (1, 128, 128, 4, 32, 4, True, jnp.float32),
        # two kv GRID blocks: the carries, one set a head of the block
        (1, 2048, 2048, 2, 64, 2, True, jnp.float32),
    ], ids=["d64_h16_causal_bf16", "d64_h16_f32", "d64_h12_causal_f32",
            "d64_h12_bf16", "d128_causal_bf16", "d128_f32",
            "d256_causal_f32", "d256_bf16", "tk_2tq_causal_f32",
            "tk_2tq_bf16", "d32_g4", "d64_carries"])
    def test_heads_picked_by_the_block_index(self, monkeypatch, B, Tq, Tk,
                                             H, D, g, causal, dtype):
        """The kernels on the model's own [B, T, H*D] arrays, g heads to
        a lane block: forward and gradients against the einsum reference
        and against the SAME kernels with the heads folded into the
        batch (what every shape got before PR 30)."""
        from paddle_tpu.ops.pallas import flash_attention as fa
        from paddle_tpu.profiler import monitor
        assert core.heads_per_block(H, D) == g
        f32 = dtype == jnp.float32
        rng = np.random.default_rng(1)
        q, k, v = (jnp.asarray(rng.standard_normal((B, T, H, D)), dtype)
                   for T in (Tq, Tk, Tk))
        flash = self._flash(causal)
        run = lambda: self._out_and_grads(flash, q, k, v)
        operands = lambda: _kernel_operands(jax.make_jaxpr(
            lambda *a: jax.vjp(flash, *a)[1](jnp.ones(
                (B, Tq, H, D), jnp.float32)))(q, k, v).jaxpr)
        calls = monitor.counter("flash.calls.direct").value
        got = run()
        assert monitor.counter("flash.calls.direct").value == calls + 1
        assert monitor.counter(f"flash.calls.direct.g{g}").value > 0
        assert set(operands()["flash_attention_dq"][:5]) == {
            (B, Tq, H * D), (B, Tk, H * D)}
        want = self._out_and_grads(
            lambda q, k, v: self._ref(q, k, v, causal), q, k, v)
        monkeypatch.setattr(fa.core, "heads_per_block", lambda h, d: None)
        jax.clear_caches()      # the custom_vjp's traces are cached
        folded = run()
        assert set(operands()["flash_attention_dq"][:5]) == {
            (B * H, Tq, D), (B * H, Tk, D)}
        for name, a, b, c in zip(("out", "dq", "dk", "dv"), got, want,
                                 folded):
            top = float(np.abs(b).max())
            np.testing.assert_allclose(
                a, b, atol=(3e-6 if f32 else 2.0 ** -6) * max(top, 1.0),
                err_msg=name)
            # the same dots over the same extents: only delta's lanes
            # are summed in another order
            np.testing.assert_allclose(
                a, c, atol=(2e-6 if f32 else 2.0 ** -7) * max(top, 1.0),
                err_msg=f"{name} against the folded call")

    @pytest.mark.parametrize("H,D", [(2, 96), (5, 64), (2, 80)],
                             ids=["d96", "d64_h5", "d80"])
    def test_shapes_no_lane_block_fits_fold_the_heads(self, H, D):
        """Head dims that neither divide 128 nor are its multiple, and a
        head count the heads of a block do not divide: the kernels get
        [B*H, T, D], as every shape did, and the counter says so."""
        from paddle_tpu.profiler import monitor
        assert core.heads_per_block(H, D) is None
        folded = monitor.counter("flash.calls.folded").value
        direct = monitor.counter("flash.calls.direct").value
        self._check(1, 128, 128, H, D, True, jnp.float32)
        assert monitor.counter("flash.calls.folded").value > folded
        assert monitor.counter("flash.calls.direct").value == direct
        x = jax.ShapeDtypeStruct((1, 128, H, D), jnp.float32)
        operands = _kernel_operands(jax.make_jaxpr(self._flash(True))(
            x, x, x).jaxpr)
        assert operands["flash_attention_fwd"] == [(H, 128, D)] * 3

    @pytest.mark.parametrize("t_q,t_k,d", [
        (4096, 4096, 256),    # cell 2: 4 x 16 blocks of 1024 x 256
        (2048, 2048, 128),    # gpt_1p3b: 2 x 4 of 1024 x 512
        (2048, 2048, 64),     # square blocks
        (1024, 2048, 64),     # kv blocks no q block sees
        (2048, 1024, 64),
    ])
    def test_skipped_grid_steps_hold_their_block_index(self, t_q, t_k, d):
        """Under the causal mask a grid step wholly above the diagonal
        runs nothing; its block index along the grid's inner axis is
        held at the nearest block that is computed, so the pipeline
        copies nothing for it. Computed steps get their own blocks."""
        from paddle_tpu.ops.pallas.flash_attention import _index_maps
        b = core.choose_flash_blocks(t_q, t_k, d)
        bq, bk = b.block_q, b.block_k
        nq, nk = t_q // bq, t_k // bk
        runs = lambda i, j: i * bq - j * bk > -bq     # the kernels' own test
        i32 = np.int32
        _, col, _ = _index_maps(3, True, b, nq)
        row, _, stat = _index_maps(3, True, b, nq, kv_major=True)
        for i in range(nq):
            js = [int(col(i32(0), i32(1), i32(i), i32(j))[1])
                  for j in range(nk)]
            last = max(j for j in range(nk) if runs(i, j))
            assert js == [min(j, last) for j in range(nk)]
        for j in range(nk):
            got = [(int(row(i32(0), i32(1), i32(j), i32(i))[1]),
                    int(stat(i32(0), i32(1), i32(j), i32(i))[2]))
                   for i in range(nq)]
            seen = [i for i in range(nq) if runs(i, j)]
            first = min(seen) if seen else nq - 1
            assert got == [(max(i, first),) * 2 for i in range(nq)]
        # no mask: every step its own blocks
        _, col, _ = _index_maps(3, False, b, nq)
        assert int(col(i32(0), i32(1), i32(0), i32(nk - 1))[1]) == nk - 1

    def test_heads_per_block_is_a_function_of_the_shape(self):
        assert [core.heads_per_block(16, d) for d in (64, 128, 256)] == \
            [2, 1, 1]
        assert core.heads_per_block(12, 64) == 2
        assert core.heads_per_block(8, 32) == 4
        assert core.heads_per_block(1, 8) == 1      # one head: its block
        assert core.heads_per_block(20, 256) == 1   # is the whole width
        for heads, d in ((5, 64), (16, 80), (16, 96), (2, 8), (6, 32)):
            assert core.heads_per_block(heads, d) is None

    @pytest.mark.parametrize("t_q,t_k,tiles", [
        (1024, 1024, (256, 256)), (1024, 1024, (128, 128)),
        (1024, 1024, (256, 512)), (1024, 1024, (512, 128)),
        (2048, 2048, (256, 256)), (512, 1024, (128, 256)),
        (1024, 512, (256, 128)), (96, 96, (96, 96)),
    ])
    def test_visited_share_is_the_brute_force_count(self, t_q, t_k, tiles):
        """The extents the kernels run ARE causal_kv_tiles /
        causal_q_tiles: hold both to a count over the mask itself, tile
        by tile."""
        tq, tk = tiles
        nq, nk = t_q // tq, t_k // tk
        keep = np.tril(np.ones((t_q, t_k), bool))
        kinds = np.zeros((nq, nk), int)                 # 0 / 1 / 2
        for i in range(nq):
            for j in range(nk):
                t = keep[i * tq:(i + 1) * tq, j * tk:(j + 1) * tk]
                kinds[i, j] = 2 if t.all() else int(t.any())
        assert core.visited_tile_share(t_q, t_k, tiles, True) == \
            (kinds > 0).mean()
        assert core.visited_tile_share(t_q, t_k, tiles, False) == 1.0
        for i in range(nq):
            full, visit = core.causal_kv_tiles(i * tq, tq, tk, nk)
            assert list(kinds[i]) == ([2] * full + [1] * (visit - full)
                                      + [0] * (nk - visit))
        for j in range(nk):
            first, first_full = core.causal_q_tiles(j * tk, tk, tq, nq)
            assert list(kinds[:, j]) == ([0] * first
                                         + [1] * (first_full - first)
                                         + [2] * (nq - first_full))
        if tq == tk and t_q == t_k:
            assert (kinds > 0).mean() == (nq + 1) / (2 * nq)

    @pytest.mark.parametrize("T,D", [(1024, 8), (2048, 8), (2048, 128)],
                             ids=["lone_block", "square_blocks",
                                  "d128_blocks"])
    @pytest.mark.parametrize("causal", [False, True])
    def test_only_diagonal_tiles_are_masked(self, causal, T, D):
        """What each kernel lowers to is what the bounds functions say:
        per grid-block position (wholly below the diagonal, or crossed
        by it) a body with one set of dots per strip — and ONE select
        per strip of a crossed block: over the crossed part alone where
        the block is square, over the strip where it is not. Without a
        mask there is one body and no select or iota at all."""
        from paddle_tpu.ops.pallas.flash_attention import \
            flash_attention_arrays
        x = jax.ShapeDtypeStruct((1, T, 1, D), jnp.bfloat16)
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda q, k, v: jnp.sum(flash_attention_arrays(
                q, k, v, causal=causal, interpret=True)
                .astype(jnp.float32)), argnums=(0, 1, 2)))(x, x, x)
        b = core.choose_flash_blocks(T, T, D)
        square = D == 8
        assert (b.block_q, b.block_k) == ((1024, 1024) if square
                                          else (1024, 512))
        dots = {"flash_attention_fwd": 2, "flash_attention_dq": 3,
                "flash_attention_dkv": 4}
        kernels = _kernels(jaxpr.jaxpr)
        assert sorted(kernels) == sorted(dots)
        for name, body in kernels.items():
            tq, tk = getattr(b, name[len("flash_attention_"):])
            n = b.block_k // tk if name.endswith("dkv") else b.block_q // tq
            assert n > 1
            if not causal:
                assert _count(body, "dot_general") == dots[name] * n
                assert _count(body, "select_n") == 0
                assert _count(body, "iota") == 0
                continue
            if T == b.block_q:
                # a lone block stands on the diagonal: that body alone,
                # under no condition
                assert _count(body, "cond") == 0
                assert (_count(body, "dot_general"),
                        _count(body, "select_n")) == (dots[name] * n, n)
                continue
            got = sorted(
                (_count(br.jaxpr, "dot_general"), _count(br.jaxpr, "select_n"))
                for eqn in body.eqns if eqn.primitive.name == "cond"
                for br in eqn.params["branches"]
                if _count(br.jaxpr, "dot_general"))
            # wholly below the diagonal: no select; crossed: one a strip
            assert got == [(dots[name] * n, 0), (dots[name] * n, n)], name
        if square:
            # and what a strip on the diagonal sees is the bounds'
            t = b.dq[0]
            assert b.dq == (t, t)
            n = b.block_q // t
            seen = [core.causal_kv_tiles(i * t, t, t, n) for i in range(n)]
            assert seen == [(i, i + 1) for i in range(n)]

    def test_blocks_share_the_core_policy(self):
        # one source of truth: the kernel module re-exports nothing of
        # its own — grid block, sub-tile and the MXU floor live in the
        # core, as a function of shapes alone
        from paddle_tpu.ops.pallas import flash_attention as fa
        assert fa.core is core
        b = core.choose_flash_blocks(1024, 1024, 64)
        assert (b.block_q, b.block_k) == (1024, 1024)  # one grid step
        for name in ("fwd", "dq", "dkv"):
            tq, tk = getattr(b, name)
            assert (tq, tk) == core.SUB_TILE_CAPS[name]
            assert 128 <= tq <= 512 and 128 <= tk <= 512
            assert tq % core.MXU_ROWS == 0 and tk % core.MXU_ROWS == 0
            assert core.visited_tile_share(1024, 1024, (tq, tk), True) \
                <= 0.75
        b = core.choose_flash_blocks(2048, 2048, 64)
        assert (b.block_q, b.block_k) == (1024, 1024)
        # wider heads scale the k, v blocks down; such
        # blocks are skipped whole or computed whole
        b = core.choose_flash_blocks(2048, 2048, 128)
        assert (b.block_q, b.block_k) == (1024, 512)
        assert core.visited_tile_share(
            2048, 2048, (b.block_q, b.block_k), True) == 0.75
        assert core.choose_flash_blocks(2048, 2048, 256).block_k == 256
        assert b.block_k % b.dkv[1] == 0 and b.block_q % b.dkv[0] == 0
        # lengths no multiple of 128 divides run as one tile, as before
        assert core.choose_flash_blocks(32, 32, 8).fwd == (32, 32)
        assert core.choose_flash_blocks(1000, 1000, 64).dq == (1000, 1000)
        assert core.choose_flash_blocks(768, 768, 64).fwd == (256, 256)
        assert core.sub_tile(384, 256) == 128

    def test_softmax_carry_forms(self):
        # the serving kernel's [M] statistics and the training kernel's
        # [M, 1]: one constructor, and the update keeps either form
        for column in (False, True):
            m, l, acc = core.softmax_carry(8, 4, column=column)
            assert m.shape == l.shape == ((8, 1) if column else (8,))
            assert acc.shape == (8, 4) and float(m[0].max()) < -1e29
            s = jnp.arange(32, dtype=jnp.float32).reshape(8, 4)
            m2, l2, acc2 = core.softmax_update(m, l, acc, s, jnp.eye(4))
            assert m2.shape == m.shape and l2.shape == l.shape
            out, lse = core.softmax_finalize(m2, l2, acc2)
            np.testing.assert_allclose(np.asarray(out),
                                       np.asarray(jax.nn.softmax(s, -1)),
                                       atol=1e-6)
            assert lse.shape == m.shape


class TestServingBucketFloor:
    def test_pad_floor_constant_reaches_min_dot_rows(self):
        assert core.MIN_Q_TOKENS >= core.MIN_DOT_ROWS

    def test_warm_schedule_floors_and_collapses_token_buckets(self):
        """Every signature warm_async emits has T >= MIN_Q_TOKENS —
        the schedule _ragged_step's pad_t floor then lands on — and
        the floor COLLAPSES the sub-8 buckets (prefill chunk, its
        halved remainders, the decode step) onto one signature."""
        import paddle_tpu as paddle
        from paddle_tpu.jit import warm as jwarm
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
        from paddle_tpu.inference import GenerationEngine
        paddle.seed(0)
        cfg = GPTConfig(vocab_size=64, hidden_size=16, num_layers=1,
                        num_heads=2, max_position_embeddings=64)
        m = GPTForCausalLM(cfg)
        m.eval()
        eng = GenerationEngine(m, n_pages=16, page_size=4, max_batch=2,
                               max_new_tokens=3, name="floor_probe")
        try:
            jwarm.join(eng.warm_async(5, 3))
            sigs = {s[:3] for s in m._ragged_exec}
            # prompt 5 at page_size 4: chunk T=5->8, remainders
            # 4/2/1->8, decode 1->8; widths stay 2 — ONE signature
            assert sigs == {(8, 1, 2)}, sigs
            # the engine's inspection view of the same executables
            texts = eng.compiled_texts()
            assert {s[:3] for s in texts} == sigs
            assert all("HloModule" in t for t in texts.values())
        finally:
            eng.shutdown()


class TestDotShapeGate:
    def test_gate_green(self):
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "check_dot_shapes.py")],
            capture_output=True, text=True, timeout=240,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode == 0, f"{out.stdout}{out.stderr}"
        assert "OK:" in out.stdout

    def test_gate_red_on_narrow_dot(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "check_dot_shapes",
            os.path.join(REPO, "tools", "check_dot_shapes.py"))
        g = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(g)
        text = ("%5 = stablehlo.dot_general %3, %4 : "
                "(tensor<1x16xf32>, tensor<16x16xf32>) "
                "-> tensor<1x16xf32>")
        v, n = g.check_module("probe", text, 8)
        assert n == 1 and v and "M=1" in v[0]
        v, n = g.check_module("probe", "no dots here", 8)
        assert v and "vacuously" in v[0]
