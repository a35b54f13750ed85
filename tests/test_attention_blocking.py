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


class TestFlashKernel:
    def _ref(self, q, k, v, causal):
        B, T, H, D = q.shape
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
        if causal:
            mask = np.tril(np.ones((T, T), bool))
            s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_and_grad_match_einsum(self, causal):
        from paddle_tpu.ops.pallas.flash_attention import \
            flash_attention_arrays
        rng = np.random.default_rng(0)
        B, T, H, D = 2, 32, 2, 8
        q, k, v = (jnp.asarray(
            rng.standard_normal((B, T, H, D)).astype(np.float32))
            for _ in range(3))

        def loss_flash(q, k, v):
            out = flash_attention_arrays(q, k, v, causal=causal,
                                         interpret=True)
            return jnp.sum(out * jnp.cos(out))

        def loss_ref(q, k, v):
            out = self._ref(q, k, v, causal)
            return jnp.sum(out * jnp.cos(out))

        np.testing.assert_allclose(
            np.asarray(flash_attention_arrays(q, k, v, causal=causal,
                                              interpret=True)),
            np.asarray(self._ref(q, k, v, causal)), atol=2e-5)
        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", g_flash, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=3e-4, err_msg=f"d{name}")

    def test_blocks_share_the_core_policy(self):
        # one source of truth: the kernel module re-exports nothing of
        # its own — block choice and the MXU floor live in the core
        from paddle_tpu.ops.pallas import flash_attention as fa
        assert fa.core is core
        bq, bk = core.choose_flash_blocks(2048, 2048, 64)
        assert bq == 1024 and bk == 1024
        bq, bk = core.choose_flash_blocks(2048, 2048, 128)
        assert bk == 512  # head dim scales the VMEM budget down


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
