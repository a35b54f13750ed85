"""AOT compiles of the main path's Pallas kernels for a DESCRIBED TPU v5e
(no chip attached): what interpret mode cannot see — block shapes the
tiling refuses, i64 operands Mosaic cannot legalize, SMEM/VMEM budgets —
the chip's own compiler says here, at real widths, for no chip time
(/opt/skills/guides/on-chip-measurement, section 2, third rehearsal).

The topology is described inside a module-scoped fixture (only one
process at a time may load the TPU's library, and every xdist worker
imports every test file): never at import, in a skipif, in parametrize or
in conftest.py. All these tests live in THIS one file so that one worker
loads the library. A compile that passes is not a chip run:
`python chip_smoke.py` on the chip is.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compile_for_chip(topo):
    """compile(fn, *shape_dtype_pairs) -> the compiled executable for
    device 0 of the described topology, with the persistent compile
    cache off around it (an entry written for a described device cannot
    be read back without the chip, and warns)."""
    from jax.experimental.compilation_cache import compilation_cache
    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def sds(spec):
        return jax.ShapeDtypeStruct(spec[0], spec[1], sharding=one_chip)

    def compile_(fn, *specs):
        args = jax.tree.map(sds, list(specs),
                            is_leaf=lambda x: isinstance(x, tuple))
        return jax.jit(fn).lower(*args).compile()

    compile_.sds = sds
    yield compile_
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _custom_calls(compiled, name):
    return sum(1 for line in compiled.as_text().splitlines()
               if "tpu_custom_call" in line and name in line)


def _flash_kernel_counts(compiled):
    """(forward, dq, dkv) Mosaic calls in the module."""
    return tuple(_custom_calls(compiled, f"flash_attention_{k}")
                 for k in ("fwd", "dq", "dkv"))


def _copied_shapes(compiled):
    """The result dims, as "8,1024,3072", of every `copy` instruction of
    the module, a leading stack axis of 1 dropped. The slices and
    concatenations a model asks for are fusions under other names."""
    return [m.group(1) for m in re.finditer(
        r"= \w+\[(?:1,)?([\d,]+)\]\{[^}]*\} copy\(", compiled.as_text())]


def _flash_operands(compiled):
    """{kernel: its operand_layout_constraints text} of the flash calls."""
    found = {}
    for line in compiled.as_text().splitlines():
        m = re.search(r"flash_attention_(fwd|dq|dkv)[.\d]* = .*"
                      r"operand_layout_constraints=\{(.*?\})\}", line)
        if m and "tpu_custom_call" in line:
            found[m.group(1)] = m.group(2)
    return found


def test_flash_attention_fwd_bwd_gpt_medium(compile_for_chip):
    """8 x 1024 x 16 heads x 64, bf16, causal: forward and both
    backward kernels."""
    from paddle_tpu.ops.pallas.flash_attention import \
        flash_attention_arrays
    qkv = ((8, 1024, 16, 64), BF16)

    def loss(q, k, v):
        out = flash_attention_arrays(q, k, v, causal=True,
                                     interpret=False)
        return jnp.sum(out.astype(F32))

    c = compile_for_chip(jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv)
    assert _flash_kernel_counts(c) == (1, 1, 1)


@pytest.mark.parametrize("shape,t_k,causal,dtype", [
    ((2, 2048, 16, 128), 2048, True, BF16),  # gpt_1p3b: 2 x 4 grid blocks
                                             # a head, 1024 x 512, crossed
                                             # ones whole under the mask
    ((8, 1024, 16, 64), 1024, False, BF16),  # nn.MultiHeadAttention: the
                                             # full square, no mask at all
    ((2, 768, 12, 64), 768, True, BF16),     # gpt_small width at 3 x 256:
                                             # an odd count of strips
    ((2, 1024, 16, 64), 1024, True, F32),    # float32 inputs stay float32:
                                             # the same blocks, twice the
                                             # bytes
    ((2, 1024, 16, 64), 2048, True, BF16),   # causal with Tk > Tq: dkv
                                             # keeps its sums, to write
                                             # the zeros of unseen columns
    ((2, 4096, 20, 256), 4096, True, BF16),  # latent attention's core in
                                             # glm-4.7-flash-ep8's cell:
                                             # 1024 x 256 blocks, 4 x 16
                                             # a head
], ids=["d128_t2048", "non_causal", "t768", "f32", "tk_2tq", "d256_t4096"])
def test_flash_attention_shapes_without_a_cell(compile_for_chip, shape, t_k,
                                               causal, dtype):
    """The strips' VMEM and slices, for the configurations that share
    the kernels with the benchmark's cell and have none."""
    from paddle_tpu.ops.pallas.flash_attention import \
        flash_attention_arrays
    b, _, h, d = shape
    q, kv = (shape, dtype), ((b, t_k, h, d), dtype)

    def loss(q, k, v):
        out = flash_attention_arrays(q, k, v, causal=causal,
                                     interpret=False)
        return jnp.sum(out.astype(F32))

    c = compile_for_chip(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert _flash_kernel_counts(c) == (1, 1, 1)


@pytest.fixture
def chip_branches(monkeypatch):
    """The program's backend switches take their chip branch while a
    test traces: attention goes to the flash kernels, not interpreted."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _gpt_two_blocks(hidden=1024, batch=8, seq=1024):
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    return GPTForCausalLM(GPTConfig(
        vocab_size=512, hidden_size=hidden, num_layers=2, num_heads=16,
        max_position_embeddings=seq, dropout=0.0,
        scan_remat="names")), (batch, seq)


def _gpt_1p3b_two_blocks():
    return _gpt_two_blocks(hidden=2048, batch=2, seq=2048)


def _glm_two_expert_layers():
    from paddle_tpu.models.decoder import (DecoderForCausalLM,
                                           glm_4_7_flash_ep8)
    return DecoderForCausalLM(glm_4_7_flash_ep8(
        vocab_size=512, num_hidden_layers=2,
        first_k_dense_replace=0)), (2, 4096)


# per stack: the kernels' operand [B, T, H*D]; the attention activations
# that NO copy instruction may produce any more (the kernels' old
# [B, H, T, D] fold — 16 such copies a GPT layer, 13 a decoder layer
# before PR 30 — and the whole of qkv, which the compiler moved through a
# T-minor layout while models/gpt.py cut q, k, v out of a 5-D view); and
# how many copies of the operand's own size may stay
_STACKS = {
    "gpt_names_8x1024x16x64": (
        _gpt_two_blocks, "bf16[8,1024,1024]",
        ("8,16,1024,64", "8,1024,16,64", "8,1024,3072"),
        # the re-read of the saved `out` a layer; two outside the scan
        ("8,1024,1024", 3)),
    "gpt_1p3b_2x2048x16x128": (
        _gpt_1p3b_two_blocks, "bf16[2,2048,2048]",
        ("2,16,2048,128", "2,2048,16,128", "2,2048,6144"),
        ("2,2048,2048", 3)),
    "decoder_2x4096x20x256": (
        _glm_two_expert_layers, "bf16[2,4096,5120]",
        ("2,20,4096,256", "2,4096,20,256"),
        # NOT the kernels': the compiler holds the latent projections'
        # q, k, v (and dq, dk, dv) T-minor, because the model cuts the
        # head dim at 192 | 64 | 32, and transposes them for any
        # row-major consumer: 3 forward, 3 recomputed, 3 gradients a
        # layer, before PR 30 as after (PERF.md section 7)
        ("2,4096,5120", 9)),
}


@pytest.mark.parametrize("stack", list(_STACKS))
def test_scanned_stack_gradient_runs_flash_forward_once(
        compile_for_chip, chip_branches, stack):
    """The gradient of a two-layer scanned stack under the stack's own
    remat policy, at the benchmark cells' attention shapes: the policy
    saves the kernel's named out and lse, so the module holds ONE forward
    kernel beside one dq and one dkv (two forwards before PR 28). And the
    kernels take and give the model's own [B, T, H*D] arrays: no copy of
    an attention activation in the kernels' old layout is left."""
    build, operand, gone, (own, at_most) = _STACKS[stack]
    import paddle_tpu as paddle
    from paddle_tpu.jit.api import functional_call, state_arrays
    paddle.seed(0)
    model, ids_shape = build()
    model.bfloat16()
    params, buffers = state_arrays(model)

    def loss(p, ids):
        logits = functional_call(model, p, buffers, (ids,), training=True)
        return jnp.mean(logits.astype(F32))

    c = compile_for_chip(
        jax.grad(loss), {k: (v.shape, v.dtype) for k, v in params.items()},
        (ids_shape, I32))
    assert _flash_kernel_counts(c) == (1, 1, 1)
    operands = _flash_operands(c)
    assert sorted(operands) == ["dkv", "dq", "fwd"]
    for kernel, text in operands.items():
        # q, k, v (and dout, out): all [B, T, H*D], row-major
        assert text.count(operand + "{2,1,0}") >= 3, (kernel, text)
    copied = _copied_shapes(c)
    assert not [x for x in copied if x in gone], copied
    assert copied.count(own) <= at_most, copied


def _compiled_train_step(compile_for_chip, config, ids_shape):
    """(compiled TrainStep, bytes the compiler says it needs) of a
    decoder preset as the benchmark's train runner builds it: bf16,
    AdamW with float32 masters, the fused update."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as Fn
    from paddle_tpu import optimizer as opt
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.decoder import DecoderForCausalLM
    paddle.seed(0)
    model = DecoderForCausalLM(config)
    model.bfloat16()
    step = TrainStep(
        model, lambda logits, y: Fn.cross_entropy(
            logits.reshape([-1, logits.shape[-1]]), y.reshape([-1])),
        opt.AdamW(learning_rate=1e-4, weight_decay=0.01,
                  parameters=model.parameters(), multi_precision=True))
    ids = paddle.to_tensor(np.zeros(ids_shape, np.int32))
    _, args = step._prep((ids, ids), 1)
    specs = jax.tree.map(
        lambda a: compile_for_chip.sds((jnp.shape(a), jnp.result_type(a))),
        args)
    c = step._jitted.lower(*specs).compile()
    m = c.memory_analysis()
    return c, (m.argument_size_in_bytes + m.output_size_in_bytes
               + m.temp_size_in_bytes - m.alias_size_in_bytes)


def test_glm_flash_ep8_step_fits_the_chip(compile_for_chip, chip_branches):
    """The whole TrainStep of glm-4.7-flash-ep8's cell (2 x 4096 ids,
    AdamW with float32 masters, the fused update): what the compiler
    says it needs stays under the 15.75 GB a v5e leaves a program, with
    the flash residuals saved; one forward kernel for the leading layer
    and one for the scan, as many as dq and dkv."""
    from paddle_tpu.models.decoder import glm_4_7_flash_ep8
    c, needs = _compiled_train_step(compile_for_chip, glm_4_7_flash_ep8(),
                                    (2, 4096))
    assert needs < 15.75e9, needs
    assert _flash_kernel_counts(c) == (2, 2, 2)


def test_smallthinker_ep8_step_fits_the_chip(compile_for_chip,
                                             chip_branches):
    """The whole TrainStep of smallthinker-21b-ep8's cell (1 x 16,384
    ids): under the 15.75 GB; the full layer's kernels and the window
    scan's, each once; and k, v reach the kernels at their own 4 heads —
    nothing in the module has them at the 28 query heads' width."""
    from paddle_tpu.models.decoder import smallthinker_21b_ep8
    c, needs = _compiled_train_step(compile_for_chip, smallthinker_21b_ep8(),
                                    (1, 16384))
    assert needs < 15.75e9, needs
    assert _flash_kernel_counts(c) == (2, 2, 2)
    operands = _flash_operands(c)
    for kernel, text in operands.items():
        # q (dout, out) at 28 x 128 lanes, k and v at 4 x 128
        assert text.count("bf16[1,16384,3584]{2,1,0}") >= 1, (kernel, text)
        assert text.count("bf16[1,16384,512]{2,1,0}") == 2, (kernel, text)
    assert not re.search(r"bf16\[1,16384,4,7,128\]", c.as_text())


@pytest.mark.parametrize("window", [None, 4096], ids=["full", "window"])
def test_flash_attention_grouped_at_16k(compile_for_chip, window):
    """smallthinker-21b-ep8's two attention cores, [1, 16384, 28 on 4,
    128]: 16 x 32 grid blocks of 1024 x 512 a head; inside the window a
    body per distance at which an edge crosses a block (-512, 0, 3584,
    4096) and one for the blocks between; dk, dv written at 4 heads,
    the group's 7 query heads summed inside dkv."""
    from paddle_tpu.ops.pallas.flash_attention import \
        flash_attention_arrays
    q, kv = ((1, 16384, 28, 128), BF16), ((1, 16384, 4, 128), BF16)

    def loss(q, k, v):
        out = flash_attention_arrays(q, k, v, causal=True, window=window,
                                     interpret=False)
        return jnp.sum(out.astype(F32))

    c = compile_for_chip(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert _flash_kernel_counts(c) == (1, 1, 1)
    assert _flash_operands(c)["dkv"].count("bf16[1,16384,512]{2,1,0}") == 2


@pytest.mark.parametrize("k,n", [(2048, 1536), (1536, 2048)],
                         ids=["gate_up", "down"])
def test_ragged_dot_fwd_bwd_expert_shapes(compile_for_chip, k, n):
    """The dropless expert layer's products in glm-4.7-flash-ep8's cell:
    8 groups in the worst-case buffer of 8,192 tokens x top-4, bf16,
    forward and both backward products of the compiler's own op."""
    def loss(lhs, rhs, sizes):
        return jnp.sum(jax.lax.ragged_dot(lhs, rhs, sizes).astype(F32))

    compile_for_chip(jax.value_and_grad(loss, argnums=(0, 1)),
                     ((8192 * 4, k), BF16), ((8, k, n), BF16), ((8,), I32))


@pytest.mark.parametrize("heads,kv_heads,head_dim", [
    (16, 16, 64),     # GPT-medium: fold 1
    (32, 8, 128),     # grouped-query: fold 4
    (20, 1, 128),     # multi-query, a head count that is no power of 2
], ids=["fold1", "fold4", "mqa20"])
def test_ragged_paged_attention_head_groupings(compile_for_chip, heads,
                                               kv_heads, head_dim):
    """256 tokens over 8 sequences x 64 pages of a 2048-page pool."""
    from paddle_tpu.ops.pallas.paged_attention import \
        ragged_paged_attention
    T, B, W, n_pages, P = 256, 8, 64, 2048, 16
    pool = ((n_pages, P, kv_heads, head_dim), BF16)
    c = compile_for_chip(
        lambda q, k, v, pt, seq, bd: ragged_paged_attention(
            q, k, v, pt, seq, bd, interpret=False),
        ((T, heads, head_dim), BF16), pool, pool, ((B, W), I32),
        ((T,), I32), ((T,), I32))
    assert _custom_calls(c, "ragged_paged_attention") == 1


def test_ragged_block_plan_is_windowed_in_smem(compile_for_chip):
    """64 sequences x 256 pages x 16 q-blocks: the whole plan would be
    3 x 1 MB of SMEM (the chip has 1 MB); windowed per q-block it is
    2 x 256 KB and compiles."""
    from paddle_tpu.ops.pallas.paged_attention import \
        ragged_paged_attention
    T, B, W, n_pages, P = 2048, 64, 256, 16384, 16
    pool = ((n_pages, P, 16, 64), BF16)
    compile_for_chip(
        lambda q, k, v, pt, seq, bd: ragged_paged_attention(
            q, k, v, pt, seq, bd, interpret=False),
        ((T, 16, 64), BF16), pool, pool, ((B, W), I32), ((T,), I32),
        ((T,), I32))


@pytest.mark.parametrize("d_inner", [1536, 5120])
def test_ssm_scan_default_and_wide(compile_for_chip, d_inner):
    """SSMConfig()'s own d_inner (1536 — halving used to land on 192)
    and 5120 (160), with 33 state rows over 256 tokens."""
    from paddle_tpu.ops.pallas.ssm_scan import choose_d_block, ssm_scan
    T, R, N = 256, 33, 16
    assert choose_d_block(d_inner, R, T, N) % 128 == 0
    tok, coef = ((T, d_inner), F32), ((T, N), F32)
    c = compile_for_chip(
        lambda *a: ssm_scan(*a, interpret=False),
        tok, tok, coef, coef, ((d_inner, N), F32),
        ((R, d_inner, N), F32), ((T,), I32))
    assert _custom_calls(c, "ssm_scan") == 1


def _gpt_medium_buckets():
    """GPT-medium's parameter tree by shape (24 layers, hidden 1024,
    vocab 50,304): the bucket sizes the fused update sweeps."""
    h, L, V = 1024, 24, 50304
    shapes = {"wte.weight": (V, h), "wpe.weight": (1024, h),
              "ln_f.weight": (h,), "ln_f.bias": (h,)}
    for i in range(L):
        for name, shape in (("ln_1.weight", (h,)), ("ln_1.bias", (h,)),
                            ("qkv_proj.weight", (h, 3 * h)),
                            ("qkv_proj.bias", (3 * h,)),
                            ("out_proj.weight", (h, h)),
                            ("out_proj.bias", (h,)),
                            ("ln_2.weight", (h,)), ("ln_2.bias", (h,)),
                            ("fc_in.weight", (h, 4 * h)),
                            ("fc_in.bias", (4 * h,)),
                            ("fc_out.weight", (4 * h, h)),
                            ("fc_out.bias", (h,))):
            shapes[f"h.{i}.{name}"] = shape
    return shapes


def test_fused_update_passes_gpt_medium_layout(compile_for_chip):
    """Both passes, mode "pallas", over every bucket of a 355M-parameter
    bf16 layout with f32 moments and masters, loss scaling and health
    sums on: one kernel per bucket per pass."""
    from paddle_tpu import optimizer as opt
    from paddle_tpu.ops.pallas import fused_update as fu
    shapes = _gpt_medium_buckets()
    assert sum(int(np.prod(s)) for s in shapes.values()) > 350e6
    lay = fu.BucketLayout([(k, s, BF16) for k, s in shapes.items()])
    spec = opt.AdamW(learning_rate=1e-4).fused_spec()
    n = len(lay.buckets)
    grads = {k: (lay.bucket_shape(k), BF16) for k in lay.buckets}
    state = {k: (lay.bucket_shape(k), F32) for k in lay.buckets}

    c1 = compile_for_chip(
        lambda g, inv: fu._run_pass1(lay, g, inv, True, "pallas"),
        grads, ((), F32))
    assert _custom_calls(c1, "fused_update_pass1") == n
    c2 = compile_for_chip(
        lambda g, p, m, v, mw, sc: fu._run_pass2(
            lay, spec, g, p, [m, v], mw, sc, True, True, None, "pallas"),
        grads, grads, state, state, state, ((4,), F32))
    assert _custom_calls(c2, "fused_update_pass2") == n


def test_fused_update_tail_block_and_per_shard_layout(compile_for_chip):
    """A bucket that is neither a multiple of 128 nor of the row block
    (the masked tail), and mp=2 / sharding=2 shards of GPT-medium's
    leaves as HybridTrainStep packs them."""
    from paddle_tpu import optimizer as opt
    from paddle_tpu.ops.pallas import fused_update as fu
    shapes = {"odd": (3000, 1333), "h.0.qkv_proj.weight": (512, 1536),
              "h.1.qkv_proj.weight": (512, 1536), "wte": (12576, 1024)}
    lay = fu.BucketLayout([(k, s, BF16) for k, s in shapes.items()])
    spec = opt.AdamW(learning_rate=1e-4).fused_spec()
    grads = {k: (lay.bucket_shape(k), BF16) for k in lay.buckets}
    state = {k: (lay.bucket_shape(k), F32) for k in lay.buckets}
    compile_for_chip(
        lambda g, inv: fu._run_pass1(lay, g, inv, False, "pallas"),
        grads, ((), F32))
    compile_for_chip(
        lambda g, p, m, v, mw, sc: fu._run_pass2(
            lay, spec, g, p, [m, v], mw, sc, False, False, None,
            "pallas"),
        grads, grads, state, state, state, ((4,), F32))
