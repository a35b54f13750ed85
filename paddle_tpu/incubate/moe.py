"""Expert-parallel Mixture-of-Experts layer.

Beyond-parity (the ~2.3 reference has no MoE): a GShard-style MoE FFN
designed TPU-first — token routing is expressed as dense one-hot
dispatch/combine einsums with a fixed per-expert capacity (static
shapes, MXU-friendly), and the expert weight stack [E, ...] is sharded
over the 'ep' mesh axis so GSPMD partitions the expert einsums across
devices and inserts the token all-to-alls automatically. No dynamic
shapes, no host routing: the whole layer jits into one program.

    moe = incubate.nn.MoELayer(d_model=512, d_hidden=2048,
                               num_experts=8, top_k=2)
    y = moe(x)           # [B, T, D] -> [B, T, D]
    loss = task_loss + 0.01 * moe.aux_loss()   # load-balancing loss

`DroplessMoE` beside it is the expert layer of the DeepSeek-V3 / GLM-4.x
form for ONE member of an expert-parallel group: it is told which
experts it holds (`local_experts`), routes over all of them, drops
nothing, and computes its own experts' part of the result as grouped
matmuls over the assignments sorted by expert (jax.lax.ragged_dot).
"""
import functools
import math

import jax
import jax.numpy as jnp

from ..framework.core import Tensor, apply_op
from .. import nn

__all__ = ["MoELayer", "DroplessMoE"]


def _moe_forward(x2d, gate_w, w1, b1, w2, b2, *, top_k, capacity,
                 activation):
    """x2d: [N, D]; gate_w: [D, E]; w1: [E, D, H]; w2: [E, H, D].
    Returns (y [N, D], aux_loss scalar)."""
    N, D = x2d.shape
    E = gate_w.shape[1]
    xf = x2d.astype(jnp.float32)
    logits = xf @ gate_w.astype(jnp.float32)            # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)

    # iterative top-k routing with per-expert capacity positions
    remaining = probs
    counts = jnp.zeros((E,), jnp.float32)               # slots used
    dispatch = jnp.zeros((N, E, capacity), jnp.float32)
    combine = jnp.zeros((N, E, capacity), jnp.float32)
    gate_sum = jnp.zeros((N,), jnp.float32)
    frac_tokens = jnp.zeros((E,), jnp.float32)
    for _ in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)            # [N]
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)
        # position of each token inside its expert's capacity buffer
        pos = (jnp.cumsum(onehot, axis=0) - 1.0) + counts[None, :]
        pos = jnp.sum(pos * onehot, axis=-1)            # [N]
        keep = (pos < capacity).astype(jnp.float32)
        pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                                dtype=jnp.float32)      # [N, C]
        d_k = onehot[:, :, None] * pos_oh[:, None, :] * \
            keep[:, None, None]                          # [N, E, C]
        g = jnp.sum(probs * onehot, axis=-1) * keep      # chosen gate
        dispatch = dispatch + d_k
        combine = combine + d_k * g[:, None, None]
        gate_sum = gate_sum + g
        counts = counts + jnp.sum(onehot * keep[:, None], axis=0)
        frac_tokens = frac_tokens + jnp.mean(onehot, axis=0)
        remaining = remaining * (1.0 - onehot)
    # normalize combine weights over the chosen experts (GShard)
    combine = combine / jnp.maximum(gate_sum, 1e-9)[:, None, None]

    expert_in = jnp.einsum("nec,nd->ecd", dispatch,
                           xf).astype(w1.dtype)          # [E, C, D]
    h = jnp.einsum("ecd,edh->ech", expert_in, w1) + b1[:, None, :]
    h = activation(h)
    out_e = jnp.einsum("ech,ehd->ecd", h, w2) + b2[:, None, :]
    y = jnp.einsum("nec,ecd->nd", combine,
                   out_e.astype(jnp.float32))            # [N, D]

    # load-balancing aux loss (Switch/GShard): E * sum(f_e * p_e)
    mean_prob = jnp.mean(probs, axis=0)
    aux = E * jnp.sum((frac_tokens / top_k) * mean_prob)
    return y.astype(x2d.dtype), aux


class MoELayer(nn.Layer):
    """Expert-parallel MoE FFN. Expert weights shard over 'ep' (announced
    via sharding_spec(), consumed by fleet's HybridTrainStep); with no
    'ep' axis in the mesh the layer still runs (experts replicated)."""

    def __init__(self, d_model, d_hidden, num_experts, top_k=2,
                 capacity_factor=1.25, activation="gelu",
                 aux_loss_weight=0.01, name=None):
        super().__init__()
        if top_k < 1 or top_k > num_experts:
            raise ValueError(f"top_k={top_k} out of range for "
                             f"{num_experts} experts")
        self.d_model = d_model
        self.d_hidden = d_hidden
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = float(capacity_factor)
        # exact (erf) gelu — jax.nn.gelu defaults to the tanh
        # approximation, which diverges from paddle's gelu semantics
        if activation == "gelu":
            self._act = functools.partial(jax.nn.gelu, approximate=False)
        else:
            self._act = getattr(jax.nn, activation)
        # consumed by TrainStep/HybridTrainStep: aux_loss_weight *
        # load-balancing loss is added to the task loss inside the
        # jitted step (user adds aux_loss() manually in eager loops)
        self.aux_loss_weight = float(aux_loss_weight)
        s = 0.02
        self.gate_weight = self.create_parameter(
            [d_model, num_experts],
            default_initializer=nn.initializer.Normal(0.0, s))
        self.w1 = self.create_parameter(
            [num_experts, d_model, d_hidden],
            default_initializer=nn.initializer.Normal(0.0, s))
        self.b1 = self.create_parameter(
            [num_experts, d_hidden],
            default_initializer=nn.initializer.Constant(0.0))
        self.w2 = self.create_parameter(
            [num_experts, d_hidden, d_model],
            default_initializer=nn.initializer.Normal(0.0, s))
        self.b2 = self.create_parameter(
            [num_experts, d_model],
            default_initializer=nn.initializer.Constant(0.0))
        self._last_aux = None

    def sharding_spec(self):
        from jax.sharding import PartitionSpec as P
        return {"w1": P("ep", None, None), "b1": P("ep", None),
                "w2": P("ep", None, None), "b2": P("ep", None),
                "gate_weight": P()}

    def capacity(self, n_tokens):
        cap = int(math.ceil(self.top_k * n_tokens * self.capacity_factor
                            / self.num_experts))
        return max(cap, self.top_k)

    def forward(self, x):
        B, T, D = x.shape
        cap = self.capacity(B * T)

        def fn(xa, gw, w1, b1, w2, b2):
            y, aux = _moe_forward(
                xa.reshape(-1, D), gw, w1, b1, w2, b2,
                top_k=self.top_k, capacity=cap, activation=self._act)
            return y.reshape(B, T, D), aux

        out, aux = apply_op(fn, x, self.gate_weight, self.w1, self.b1,
                            self.w2, self.b2, n_outputs=2)
        self._last_aux = aux
        return out

    def aux_loss(self):
        """Load-balancing loss of the most recent EAGER forward (add it
        to the task loss manually). Under TrainStep / fleet's
        build_train_step the aux loss is added to the task loss inside
        the jitted step automatically (weight = aux_loss_weight), so
        this accessor is eager-only."""
        if self._last_aux is None:
            raise RuntimeError("aux_loss() before any forward()")
        val = self._last_aux.value if hasattr(self._last_aux, "value") \
            else self._last_aux
        if isinstance(val, jax.core.Tracer):
            raise RuntimeError(
                "aux_loss() after a jitted step: the load-balancing loss "
                "was already added inside the compiled program "
                "(aux_loss_weight); call aux_loss() only in eager loops")
        return self._last_aux


# ---------------------------------------------------------------------
# the dropless expert layer
# ---------------------------------------------------------------------
STEP_COUNTERS = ("moe.assignments", "moe.local_assignments",
                 "moe.expert_load_max", "moe.dropped", "moe.buffer_rows")


SCORINGS = ("sigmoid", "softmax_topk")
GATE_ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def route_tokens(x, router_w, bias, top_k, scale, norm_topk_prob,
                 scoring="sigmoid"):
    """Top-k routing over ALL experts, in float32. `scoring`:
    "sigmoid" (DeepSeek-V3, "auxiliary-loss-free" balancing): s =
    sigmoid(x Wr); chosen = top-k of s + bias; weight = s[chosen],
    renormalised over the chosen (+1e-20) where `norm_topk_prob`.
    "softmax_topk" (SmallThinker's primary router): s = x Wr; chosen =
    top-k of s + bias; weight = softmax over the CHOSEN logits — the
    softmax over all experts renormalised over the chosen — or, without
    `norm_topk_prob`, the chosen entries of the softmax over all.
    Either way the bias decides who is chosen and never enters a weight,
    and the weights are times `scale`. Returns (chosen [N, k] int32,
    weights [N, k] float32)."""
    s = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
    if scoring == "sigmoid":
        s = jax.nn.sigmoid(s)
    elif scoring == "softmax_topk" and not norm_topk_prob:
        s = jax.nn.softmax(s, axis=-1)
    _, chosen = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if scoring == "softmax_topk" and norm_topk_prob:
        picked = jax.nn.softmax(picked, axis=-1)
    elif norm_topk_prob:
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), picked * scale


ROW_TILE = 128     # a rung is a whole number of the chip's 128-row tiles
LOW_RUNG = 1.25    # the lowest rung over the uniform count (PERF.md, PR 34)


def buffer_rungs(N, k, n_local, num_experts):
    """The static row counts the sorted buffer may take, ascending, from
    shapes alone: LOW_RUNG times what the held experts get of N * k
    assignments under uniform routing, rounded up to ROW_TILE, and the
    worst case N * min(k, n_local), which holds any routing. Where the
    first would not be smaller (every expert held, tiny shapes) the
    worst case stands alone."""
    worst = N * min(k, n_local)
    uniform = N * k * n_local / num_experts
    low = math.ceil(LOW_RUNG * uniform / ROW_TILE) * ROW_TILE
    return (low, worst) if low < worst else (worst,)


def dispatch_plan(chosen, first, n_local):
    """Where every assignment to a HELD expert stands in the sorted
    buffer: a stable sort of the held assignments by expert, the groups
    contiguous from row 0 (what jax.lax.ragged_dot takes). The plan is
    made at the worst case's N * min(k, n_local) rows and serves every
    rung of `buffer_rungs`: a buffer of R rows takes src[:R], and `pos`
    marks "not held" with the worst case's length, which no rung's
    rows reach. chosen [N, k]. Returns
      sizes [n_local]   assignments to each held expert
      pos [N, k]        the row of each assignment (n_rows: not held)
      src [n_rows]      the assignment (n * k + j) of each row (N * k:
                        the row holds none)."""
    N, k = chosen.shape
    A, n_rows = N * k, N * min(k, n_local)
    flat = chosen.reshape(A)
    held = (flat >= first) & (flat < first + n_local)
    key = jnp.where(held, flat - first, n_local).astype(jnp.int32)
    onehot = key[:, None] == jnp.arange(n_local, dtype=jnp.int32)[None]
    sizes = onehot.sum(0).astype(jnp.int32)
    starts = jnp.cumsum(sizes, dtype=jnp.int32) - sizes
    # rank among the assignments of its expert, in assignment order
    own = jnp.minimum(key, n_local - 1)
    rank = jnp.take_along_axis(jnp.cumsum(onehot, axis=0, dtype=jnp.int32),
                               own[:, None], axis=1)[:, 0] - 1
    pos = jnp.where(held, starts[own] + rank, n_rows).reshape(N, k)
    # the stable sort puts the held assignments first, by expert, in order
    order = jnp.argsort(key, stable=True).astype(jnp.int32)[:n_rows]
    src = jnp.where(jnp.arange(n_rows, dtype=jnp.int32) < sizes.sum(),
                    order, A)
    return sizes, pos, src


def _gathered(rows, pos):
    """[rows[pos[:, j]] for each of a token's k assignments j], float32
    [N, D] each, a row past the buffer (`pos` >= its length: not held)
    read as the last one — the caller SELECTS it away (that row may hold
    anything, see `grouped_matmul`: a product with a zero weight would
    keep its NaN). An assignment at a time: one [N, k, D] gather has its
    k (4, 6) padded to the chip's 8-row tile, is written and read back
    in float32 and laid out anew on the way to its sum (PERF.md section
    6, PR 31)."""
    last = rows.shape[0] - 1
    return [rows[jnp.minimum(pos[:, j], last)].astype(jnp.float32)
            for j in range(pos.shape[1])]


@jax.custom_vjp
def _dispatch(x, src, pos):
    """xs [rows, D]: row r holds token src[r] // k of x [N, D], zero
    where it holds none. Backward: a gather by `pos`, no scatter."""
    N, k = pos.shape
    tok = jnp.minimum(src, N * k - 1) // k
    return jnp.where((src < N * k)[:, None], x[tok], jnp.zeros((), x.dtype))


def _dispatch_fwd(x, src, pos):
    return _dispatch(x, src, pos), (src, pos)


def _dispatch_bwd(res, dxs):
    src, pos = res
    held = pos < dxs.shape[0]
    dx = sum(jnp.where(held[:, j, None], got, 0.0)
             for j, got in enumerate(_gathered(dxs, pos)))
    return dx.astype(dxs.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(ys, w, src, pos):
    """y [N, D] = sum over a token's held assignments of w[n, j] *
    ys[pos[n, j]] (float32 sum). Backward: a gather by `src`."""
    held = pos < ys.shape[0]
    return sum(jnp.where(held[:, j, None], got * w[:, j, None], 0.0)
               for j, got in enumerate(_gathered(ys, pos))).astype(ys.dtype)


def _combine_fwd(ys, w, src, pos):
    return _combine(ys, w, src, pos), (ys, w, src, pos)


def _combine_bwd(res, dy):
    ys, w, src, pos = res
    N, k = pos.shape
    a = jnp.minimum(src, N * k - 1)
    w_row = jnp.where(src < N * k, w.reshape(-1)[a], 0.0)
    dys = (dy[a // k].astype(jnp.float32) * w_row[:, None]).astype(ys.dtype)
    dy32 = dy.astype(jnp.float32)
    dw = jnp.stack([(got * dy32).sum(-1) for got in _gathered(ys, pos)], 1)
    return dys, jnp.where(pos < ys.shape[0], dw, 0.0).astype(w.dtype), \
        None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def grouped_matmul(rows, w, sizes):
    """rows [R, K] x w [G, K, M], the first sizes[g] rows after the
    groups before it by w[g]: the compiler's jax.lax.ragged_dot. On the
    chip the rows PAST the groups come back undefined, in the product
    and in the gradient of `rows` alike (PERF.md section 6, PR 32's
    probe: non-zero, and NaN where the memory held NaN); the gradient of
    `w` contracts over the groups only."""
    return jax.lax.ragged_dot(rows, w.astype(rows.dtype), sizes)


def _experts_at(n_rows, activation, plan, x, w, w_gate, w_up, w_down):
    """The held experts' part computed over a sorted buffer of the STATIC
    length n_rows >= sizes.sum(): everything of the layer whose cost goes
    with the rows. Rows at or past sizes.sum() are zero in xs and
    undefined from the first product on; nothing reads them: `_combine`
    and `_dispatch_bwd` gather by `pos` and select the not-held away,
    `_combine_bwd` writes them as zeros, the experts' weight gradients
    contract over the groups."""
    sizes, pos, src = plan
    src = src[:n_rows]
    with jax.named_scope("moe.experts"):
        xs = _dispatch(x, src, pos)
        h = activation(grouped_matmul(xs, w_gate, sizes)) \
            * grouped_matmul(xs, w_up, sizes)
        ys = grouped_matmul(h, w_down, sizes)
    with jax.named_scope("moe.combine"):
        return _combine(ys, w, src, pos)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _on_ladder(rungs, activation, rung, plan, ops):
    """`_experts_at` on the rung `rung` (a traced index) of the static
    row counts `rungs`, ops = (x, w, w_gate, w_up, w_down): one
    lax.switch. Its backward is one switch too, each branch the vjp of
    its own rung, so that no branch makes (as zeros) the residuals of
    the other rungs."""
    def at(plan, ops, r):
        return _experts_at(r, activation, plan, *ops)

    return jax.lax.switch(
        rung, [functools.partial(at, r=r) for r in rungs], plan, ops)


def _on_ladder_fwd(rungs, activation, rung, plan, ops):
    return _on_ladder(rungs, activation, rung, plan, ops), (rung, plan, ops)


def _on_ladder_bwd(rungs, activation, res, dy):
    rung, plan, ops = res

    def pull(plan, ops, dy, r):
        return jax.vjp(functools.partial(_experts_at, r, activation, plan),
                       *ops)[1](dy)

    return None, None, jax.lax.switch(
        rung, [functools.partial(pull, r=r) for r in rungs], plan, ops, dy)


_on_ladder.defvjp(_on_ladder_fwd, _on_ladder_bwd)


def dropless_experts(x, chosen, weights, w_gate, w_up, w_down, first,
                     activation=jax.nn.silu, num_experts=None):
    """The held experts' part of the layer's result, for tokens x [N, D]
    routed as (chosen, weights) [N, k]: stable sort of the held
    assignments by expert -> gather -> grouped matmul x3 (gated by
    `activation`) -> weight -> sum per token. Every assignment to a held
    expert (indices first .. first + E_local - 1 of `num_experts`, by
    default the held ones) is computed, however they fall: the sorted
    buffer takes the smallest of `buffer_rungs`' static row counts that
    holds the COUNTED assignments to held experts, picked in the graph
    by one lax.switch, and the last rung is the worst case. Each rung
    gives the worst case's bits (`_experts_at` says why the rows past
    the groups, which the chip leaves undefined, reach nothing). What a
    Pallas kernel gave against jax.lax.ragged_dot on the chip is in
    PERF.md section 6 (PR 27). Returns (y [N, D], counters [5] int32:
    STEP_COUNTERS)."""
    N, k = chosen.shape
    n_local = w_gate.shape[0]
    rungs = buffer_rungs(N, k, n_local, num_experts or n_local)
    with jax.named_scope("moe.route"):
        plan = sizes, _, _ = dispatch_plan(chosen, first, n_local)
        local = sizes.sum()
        rung = (local > jnp.asarray(rungs[:-1], jnp.int32)).sum()
    ops = (x, weights.astype(jnp.float32), w_gate, w_up, w_down)
    if len(rungs) == 1:
        y = _experts_at(rungs[0], activation, plan, *ops)
    else:
        y = _on_ladder(rungs, activation, rung, plan, ops)
    rows = jnp.asarray(rungs, jnp.int32)[rung]
    counters = jnp.stack([jnp.int32(N * k), local, sizes.max(),
                          jnp.maximum(local - rows, 0), rows])
    return y, counters.astype(jnp.int32)


class DroplessMoE(nn.Layer):
    """Expert layer of the DeepSeek-V3 / GLM-4.x form, for one member of
    an expert-parallel group.

        y = shared(x) + sum over the token's chosen experts HELD HERE of
            w_e * E_e(x)

    `num_experts` is the router's width (all routed experts of the
    model); `local_experts`, a range, says which of them this layer
    holds (all by default) and is data of the layer. Routing
    (`route_tokens`) is over all experts: sigmoid scores, a selection
    bias (the buffer `e_score_correction_bias`, zero unless set),
    renormalised top-k, `routed_scaling_factor` — or, `scoring=
    "softmax_topk"`, a softmax over the chosen logits. Assignments to
    experts held elsewhere add nothing here; every assignment to a held
    expert is computed — no capacity, no drop (`dropless_experts`).
    Experts and the `n_shared_experts` shared ones are feed-forwards of
    width `d_expert` gated by `activation` ("silu", "relu").
    forward(x, router_input=None) routes on `router_input` where one is
    given (SmallThinker's router reads the decoder layer's input, before
    attention) and transforms x. On one chip the layer runs without its
    exchange; under a mesh with an 'ep' axis the expert stacks shard over it
    (`sharding_spec()`, MoELayer's convention).

    Each forward records STEP_COUNTERS as one int32 vector
    (`step_counter_names`, `_step_counters`): all assignments, those to
    held experts, the largest group, those not computed (0), and, fifth,
    `moe.buffer_rows`: the rows of the rung of `buffer_rungs` the call
    took, so rows over held assignments says how many empty rows the
    layer walked. jit.TrainStep carries the vector
    out of the compiled step and folds it into profiler.monitor. A
    container that calls this layer under jax.checkpoint or lax.scan
    must hand the vector out of that inner trace itself
    (jit.api.take_step_counters; models/decoder.py does)."""

    step_counter_names = STEP_COUNTERS

    def __init__(self, d_model, d_expert, num_experts, top_k,
                 local_experts=None, n_shared_experts=0,
                 routed_scaling_factor=1.0, norm_topk_prob=True,
                 weight_attr=None, name=None, scoring="sigmoid",
                 activation="silu"):
        super().__init__()
        if scoring not in SCORINGS or activation not in GATE_ACTIVATIONS:
            raise ValueError(f"scoring={scoring!r} (of {SCORINGS}), "
                             f"activation={activation!r} (of "
                             f"{tuple(GATE_ACTIVATIONS)})")
        self.scoring, self.activation = scoring, activation
        local = range(num_experts) if local_experts is None \
            else local_experts
        if local.step != 1 or local.start < 0 or local.stop > num_experts \
                or len(local) < 1:
            raise ValueError(f"local_experts={local!r} is no contiguous "
                             f"range of the {num_experts} experts")
        if not 1 <= top_k <= num_experts:
            raise ValueError(f"top_k={top_k} out of range for "
                             f"{num_experts} experts")
        self.num_experts, self.top_k, self.local_experts = \
            num_experts, top_k, local
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.norm_topk_prob = bool(norm_topk_prob)
        init = weight_attr or nn.initializer.Normal(0.0, 0.02)
        self.router = nn.Linear(d_model, num_experts, weight_attr=init,
                                bias_attr=False)
        self.register_buffer("e_score_correction_bias", Tensor(
            jnp.zeros((num_experts,), jnp.float32)))
        E = len(local)
        self.experts_gate = self.create_parameter(
            [E, d_model, d_expert], default_initializer=init)
        self.experts_up = self.create_parameter(
            [E, d_model, d_expert], default_initializer=init)
        self.experts_down = self.create_parameter(
            [E, d_expert, d_model], default_initializer=init)
        self.shared = nn.GatedMLP(d_model, d_expert * n_shared_experts,
                                  activation=activation,
                                  weight_attr=init) \
            if n_shared_experts else None
        self._step_counters = None

    def sharding_spec(self):
        from jax.sharding import PartitionSpec as P
        return {"experts_gate": P("ep", None, None),
                "experts_up": P("ep", None, None),
                "experts_down": P("ep", None, None),
                "router.weight": P()}

    def forward(self, x, router_input=None):
        D = x.shape[-1]

        def fn(xa, ra, rw, bias, wg, wu, wd):
            x2 = xa.reshape(-1, D)
            with jax.named_scope("moe.route"):
                chosen, weights = route_tokens(
                    ra.reshape(-1, D), rw, bias, self.top_k,
                    self.routed_scaling_factor, self.norm_topk_prob,
                    self.scoring)
            y, counters = dropless_experts(
                x2, chosen, weights, wg, wu, wd, self.local_experts.start,
                GATE_ACTIVATIONS[self.activation], self.num_experts)
            return y.reshape(xa.shape), counters

        y, counters = apply_op(
            fn, x, x if router_input is None else router_input,
            self.router.weight, self.e_score_correction_bias,
            self.experts_gate, self.experts_up, self.experts_down,
            n_outputs=2)
        self._step_counters = counters.value
        return y if self.shared is None else y + self.shared(x)
