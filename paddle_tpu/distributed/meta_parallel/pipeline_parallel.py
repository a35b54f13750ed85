"""Pipeline-parallel execution engine.

Parity: python/paddle/distributed/fleet/meta_parallel/pipeline_parallel.py
(PipelineParallel: 1F1B/GPipe schedules over NCCL p2p).

TPU-native design: the schedule is ONE SPMD program. Per-stage parameter
pytrees are stacked on a leading [pp] axis and sharded over the 'pp' mesh
axis; inside shard_map every device runs the same stage function on its
local shard while lax.ppermute rotates microbatch activations to the next
stage over ICI. The fill/steady/drain phases of GPipe fall out of a single
fori_loop of length (n_micro + n_stages - 1); reverse-mode AD through
ppermute yields the backward pipeline automatically, so 1F1B-style
interleaving is XLA's scheduling problem, not hand-written control flow
(see PAPERS.md: MPMD pipeline parallelism — we deliberately choose the
SPMD formulation natural to XLA).

Constraint (documented): stages must be structurally uniform (same layer
stack per stage) — embedding/head run replicated outside the pipelined
segment. This matches how transformer trunks are pipelined in practice.
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from ...framework.core import Tensor
from ...jit.api import functional_call, state_arrays, _bind, _restore

__all__ = ["PipelineParallel", "pipeline_apply",
           "pipeline_apply_interleaved", "pipeline_1f1b"]


def pipeline_apply(stage_fn, stacked_params, x_micro, mesh, n_stages,
                   n_micro):
    """Run the GPipe schedule. stacked_params leaves: [pp, ...];
    x_micro: [n_micro, mb, ...] (replicated over pp). Returns stacked
    last-stage outputs [n_micro, mb, ...]."""

    def spmd(params_local, xs):
        # params_local leaves: [1, ...] → this stage's params
        params_here = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index("pp")
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        T = n_micro + n_stages - 1
        mb_shape = xs.shape[1:]
        outputs = jnp.zeros((n_micro,) + mb_shape, xs.dtype)
        carry = jnp.zeros(mb_shape, xs.dtype)

        def tick(t, state):
            recv, outputs = state
            feed_idx = jnp.clip(t, 0, n_micro - 1)
            first_in = jnp.where(t < n_micro, xs[feed_idx],
                                 jnp.zeros(mb_shape, xs.dtype))
            inp = jnp.where(stage == 0, first_in, recv)
            out = stage_fn(params_here, inp)
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            is_valid = (t >= n_stages - 1) & (stage == n_stages - 1)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs,
                jnp.where(is_valid, out, outputs[out_idx]), out_idx, 0)
            recv = jax.lax.ppermute(out, "pp", perm)
            return recv, outputs

        recv, outputs = jax.lax.fori_loop(0, T, tick, (carry, outputs))
        # broadcast last-stage outputs to every pp rank so downstream
        # (replicated head/loss) sees them
        outputs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outputs, 0.0), "pp")
        return outputs

    pp_specs = jax.tree.map(lambda _: P("pp"), stacked_params)
    return shard_map(
        spmd, mesh=mesh,
        in_specs=(pp_specs, P()), out_specs=P(),
        check_vma=False)(stacked_params, x_micro)


def pipeline_apply_interleaved(stage_fn, stacked_params, x_micro, mesh,
                               n_stages, n_micro, n_virtual):
    """Interleaved virtual-stage schedule (Megatron-style; ref
    pipeline_parallel.py "interleaved"/virtual pp + pp_layers.py virtual
    stages): each device owns V non-contiguous model chunks, so the
    pipeline fill is V× shallower relative to per-tick work — bubble
    fraction drops from (S-1)/(M+S-1) toward (S-1)/(M·V+S-1).

    stacked_params leaves: [S*V, ...] in DEVICE-MAJOR order (row d*V+c =
    chunk c living on device d); under P("pp") sharding device d holds
    exactly its V chunks. Schedule position for device d at tick t:
    k = t-d; group g = k//(S·V), j = k%(S·V), chunk c = j//S, and
    micro m = g·S + j%S. Activations hop d→d+1 each tick; the wrap
    S-1→0 carries the micro into its next chunk. Requires n_micro %
    n_stages == 0. Backward is reverse-mode AD through the loop (GPipe-
    class memory; combine with recompute for depth-bounded footprint)."""
    S, V, M = n_stages, n_virtual, n_micro
    if M % S != 0:
        raise ValueError(f"interleaved schedule needs n_micro ({M}) "
                         f"divisible by n_stages ({S})")
    G = M // S
    T = S - 1 + G * S * V

    def spmd(params_local, xs):
        # params_local leaves: [V, ...] — this device's chunks
        d = jax.lax.axis_index("pp")
        perm = [(i, (i + 1) % S) for i in range(S)]
        mb_shape = xs.shape[1:]
        outputs = jnp.zeros((M,) + mb_shape, xs.dtype)
        recv0 = jnp.zeros(mb_shape, xs.dtype)

        def tick(t, state):
            recv, outputs = state
            k = t - d
            valid = (k >= 0) & (k < G * S * V)
            kc = jnp.clip(k, 0, G * S * V - 1)
            g = kc // (S * V)
            j = kc % (S * V)
            c = j // S
            m = g * S + (j % S)
            params_here = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, c, 0,
                                                       keepdims=False),
                params_local)
            inp = jnp.where((d == 0) & (c == 0), xs[m], recv)
            out = stage_fn(params_here, inp)
            done = valid & (d == S - 1) & (c == V - 1)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(done, out, outputs[m]), m, 0)
            recv = jax.lax.ppermute(out, "pp", perm)
            return recv, outputs

        _, outputs = jax.lax.fori_loop(0, T, tick, (recv0, outputs))
        return jax.lax.psum(
            jnp.where(d == S - 1, outputs, 0.0), "pp")

    pp_specs = jax.tree.map(lambda _: P("pp"), stacked_params)
    return shard_map(
        spmd, mesh=mesh,
        in_specs=(pp_specs, P()), out_specs=P(),
        check_vma=False)(stacked_params, x_micro)


def pipeline_1f1b(stage_fn, stacked_params, edge_params, pre_fn, post_fn,
                  loss_arr, x_micro, y_micro, mesh, n_stages, n_micro):
    """1F1B schedule with a hand-written, recompute-based backward.

    Parity: the 1f1b schedule in the reference's
    fleet/meta_parallel/pipeline_parallel.py:81,170 — but formulated SPMD:
    one fori_loop of combined fwd+bwd "cycles"; each stage keeps only a
    ring buffer of min(n_micro, 2*n_stages-1) saved stage INPUTS and
    recomputes the stage forward inside jax.vjp at backward time. Peak
    activation memory is therefore bounded by the pipeline depth, not by
    n_micro (GPipe-via-AD saves every tick's residuals).

    Schedule algebra (stage s of S, cycle c):
      forward  micro  fm = c - s            (valid while 0 <= fm < n_micro)
      backward micro  bm = c - 2(S-1) + s   (last stage: bm == fm, so it
                                             backwards a micro in the same
                                             cycle it forwarded it)
    Cotangents ride the reverse ppermute ring; a micro's backward at stage
    s+1 lands exactly one cycle before stage s needs it.

    pre_fn/post_fn(edge_params, x) run at the pipeline edges (stage 0 /
    last stage) inside the loop — SharedLayerDesc tied weights live in
    `edge_params` once, so d(pre)+d(post) accumulate into one leaf.
    Returns (loss, trunk_grads [pp-sharded], edge_grads [replicated]).
    """
    S, M = n_stages, n_micro
    R = min(M, 2 * S - 1)

    def spmd(params_local, edge_p, xs, ys):
        params_here = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index("pp")
        fwd_perm = [(i, (i + 1) % S) for i in range(S)]
        bwd_perm = [(i, (i - 1) % S) for i in range(S)]
        C = M + 2 * (S - 1)

        # probe shapes (abstract eval only — no FLOPs at runtime)
        x0 = pre_fn(edge_p, xs[0])
        mb_shape, mb_dtype = x0.shape, x0.dtype

        ring = jnp.zeros((R,) + mb_shape, mb_dtype)
        fwd_recv = jnp.zeros(mb_shape, mb_dtype)
        bwd_recv = jnp.zeros(mb_shape, mb_dtype)
        grads0 = jax.tree.map(jnp.zeros_like, params_here)
        egrads0 = jax.tree.map(jnp.zeros_like, edge_p)
        loss0 = jnp.zeros((), jnp.float32)

        def cycle(c, state):
            ring, fwd_recv, bwd_recv, grads, egrads, loss_acc = state

            # ---------- forward slot ----------
            fm = c - stage
            fwd_valid = (fm >= 0) & (fm < M)
            fm_c = jnp.clip(fm, 0, M - 1)
            inp = jnp.where(stage == 0, pre_fn(edge_p, xs[fm_c]), fwd_recv)
            out = stage_fn(params_here, inp)
            slot = fm_c % R
            old = jax.lax.dynamic_index_in_dim(ring, slot, 0,
                                               keepdims=False)
            ring = jax.lax.dynamic_update_index_in_dim(
                ring, jnp.where(fwd_valid, inp, old), slot, 0)

            # last stage: per-micro loss + seed cotangent, same cycle
            def head_loss(ep, o):
                return loss_arr(post_fn(ep, o), ys[fm_c])

            l_m, head_vjp = jax.vjp(head_loss, edge_p, out)
            dep_head, seed = head_vjp(jnp.float32(1.0 / M))
            last = stage == S - 1
            loss_acc = loss_acc + jnp.where(
                fwd_valid & last, l_m.astype(jnp.float32) / M, 0.0)

            # ---------- backward slot ----------
            bm = c - 2 * (S - 1) + stage
            bwd_valid = (bm >= 0) & (bm < M)
            bm_c = jnp.clip(bm, 0, M - 1)
            x_saved = jax.lax.dynamic_index_in_dim(ring, bm_c % R, 0,
                                                   keepdims=False)
            cot_in = jnp.where(last, seed, bwd_recv)
            _, stage_vjp = jax.vjp(stage_fn, params_here, x_saved)
            dp, dx = stage_vjp(cot_in)

            bmask = bwd_valid.astype(jnp.float32)
            grads = jax.tree.map(
                lambda g, d: g + d.astype(g.dtype) * bmask.astype(g.dtype),
                grads, dp)
            # edge grads: head side lands on the last stage at fwd time;
            # pre side chains dx through pre_fn on stage 0 at bwd time
            def pre_chain(ep):
                return pre_fn(ep, xs[bm_c])

            _, pre_vjp = jax.vjp(pre_chain, edge_p)
            (dep_pre,) = pre_vjp(dx)
            hmask = (fwd_valid & last).astype(jnp.float32)
            pmask = (bwd_valid & (stage == 0)).astype(jnp.float32)
            egrads = jax.tree.map(
                lambda g, dh, dpr: g + dh.astype(g.dtype) *
                hmask.astype(g.dtype) + dpr.astype(g.dtype) *
                pmask.astype(g.dtype),
                egrads, dep_head, dep_pre)

            fwd_recv = jax.lax.ppermute(out, "pp", fwd_perm)
            bwd_recv = jax.lax.ppermute(dx, "pp", bwd_perm)
            return ring, fwd_recv, bwd_recv, grads, egrads, loss_acc

        state = (ring, fwd_recv, bwd_recv, grads0, egrads0, loss0)
        *_, grads, egrads, loss_acc = jax.lax.fori_loop(0, C, cycle, state)
        loss = jax.lax.psum(loss_acc, "pp")  # only last stage contributed
        egrads = jax.tree.map(lambda g: jax.lax.psum(g, "pp"), egrads)
        grads = jax.tree.map(lambda g: g[None], grads)
        return loss, grads, egrads

    pp_specs = jax.tree.map(lambda _: P("pp"), stacked_params)
    rep_specs = jax.tree.map(lambda _: P(), edge_params)
    return shard_map(
        spmd, mesh=mesh,
        in_specs=(pp_specs, rep_specs, P(), P()),
        out_specs=(P(), pp_specs, rep_specs),
        check_vma=False)(stacked_params, edge_params, x_micro, y_micro)


class PipelineParallel:
    """Engine over a PipelineLayer: builds the stacked-stage params and a
    jitted train step. Used by fleet and by tests/dryrun.

    schedule: "gpipe" (AD through the fill/steady/drain loop) or "1f1b"
    (hand-written interleaved backward, depth-bounded activation memory —
    ref fleet/meta_parallel/pipeline_parallel.py:81,170).

    SharedLayerDesc entries at the head/tail of the stack (tied
    embedding/LM-head) are lifted out of the pipelined trunk into
    replicated `edge` params applied at stage 0 / last stage; because the
    tied weight is ONE leaf used by both, its gradient is the sum of both
    uses (ref parallel_layers/pp_layers.py:49)."""

    def __init__(self, pipeline_layer, optimizer, mesh, n_micro=2,
                 loss_fn=None, schedule="gpipe", n_virtual=1):
        self.layer = pipeline_layer
        self.optimizer = optimizer
        self.mesh = mesh
        self.n_micro = n_micro
        self.n_stages = pipeline_layer.num_stages
        self.loss_fn = loss_fn or pipeline_layer._loss_fn
        self.schedule = schedule.lower().replace("-", "")
        if self.schedule not in ("gpipe", "1f1b", "interleaved"):
            raise ValueError(f"unknown pipeline schedule {schedule!r}")
        if self.schedule == "interleaved":
            self.n_virtual = max(2, int(n_virtual))
        else:
            self.n_virtual = 1
        self._step_i = 0

        # ---- split the stack: [pre edge][uniform trunk][post edge] -----
        shared_ids = {id(l) for l in pipeline_layer._shared.values()}
        items = list(pipeline_layer.run_function)
        pre_items, post_items = [], []
        while items and id(items[0][0]) in shared_ids:
            pre_items.append(items.pop(0))
        while items and id(items[-1][0]) in shared_ids:
            post_items.append(items.pop())
        post_items.reverse()
        n_seg = self.n_stages * self.n_virtual
        if len(items) % n_seg != 0:
            raise ValueError(
                f"trunk of {len(items)} layers does not divide into "
                f"{n_seg} uniform stages "
                f"({self.n_stages} stages x {self.n_virtual} chunks)")
        per = len(items) // n_seg
        segments = [items[i * per:(i + 1) * per] for i in range(n_seg)]
        self._segments = segments

        # ---- edge (replicated, possibly tied) params -------------------
        key_of = {id(l): name for name, l in pipeline_layer._shared.items()}
        edge = {}

        def _with_prefix(edge_items, base):
            out = []
            for j, (l, tag) in enumerate(edge_items):
                pref = key_of.get(id(l), f"{base}{j}") \
                    if hasattr(l, "named_parameters") else None
                out.append((l, tag, pref))
                if pref is not None:
                    for name, p in l.named_parameters():
                        edge[f"{pref}.{name}"] = p.value  # tied: one key
            return out

        pre_triples = _with_prefix(pre_items, "pre")
        post_triples = _with_prefix(post_items, "post")
        self.edge = edge

        def _edge_fn(triples):
            def fn(edge_p, x):
                xt = Tensor(x) if not isinstance(x, Tensor) else x
                for l, tag, pref in triples:
                    if pref is not None:
                        sub = {k[len(pref) + 1:]: v
                               for k, v in edge_p.items()
                               if k.startswith(pref + ".")}
                        saved = _bind(l, sub)
                        try:
                            xt = tag(l, xt) if callable(tag) and \
                                tag != "fn" else l(xt)
                        finally:
                            _restore(saved)
                    else:
                        xt = l(xt)
                return xt.value if isinstance(xt, Tensor) else xt
            return fn

        self._pre_fn = _edge_fn(pre_triples)
        self._post_fn = _edge_fn(post_triples)

        # ---- stacked per-stage trunk params; stages must be uniform ----
        seg_params = []
        for seg in segments:
            stage_arrays = {}
            for idx, (layer, tag) in enumerate(seg):
                if tag == "fn" or not hasattr(layer, "named_parameters"):
                    continue
                for name, p in layer.named_parameters():
                    stage_arrays[f"{idx}.{name}"] = p.value
            seg_params.append(stage_arrays)
        keys = sorted(seg_params[0].keys())
        for sp in seg_params[1:]:
            if sorted(sp.keys()) != keys:
                raise ValueError(
                    "pipeline stages are not structurally uniform: "
                    f"{sorted(sp.keys())} vs {keys}")
        # row order: device-major (row d*V+c = logical segment c*S+d) so
        # the P('pp') shard of device d is exactly its V chunks; for
        # V=1 this is plain segment order
        S, V = self.n_stages, self.n_virtual
        row_order = [c * S + d for d in range(S) for c in range(V)]
        self.stacked = {
            k: jnp.stack([seg_params[l][k] for l in row_order])
            for k in keys}
        pp_shard = {k: NamedSharding(mesh, P("pp"))
                    for k in self.stacked}
        self.stacked = {k: jax.device_put(v, pp_shard[k])
                        for k, v in self.stacked.items()}
        rep = NamedSharding(mesh, P())
        self.edge = {k: jax.device_put(v, rep)
                     for k, v in self.edge.items()}
        self.opt_state = {
            k: jax.tree.map(lambda s, _sh=pp_shard[k]:
                            jax.device_put(s, _sh),
                            optimizer.init_leaf_state(v))
            for k, v in self.stacked.items()}
        self.edge_opt_state = {
            k: jax.tree.map(lambda s: jax.device_put(s, rep),
                            optimizer.init_leaf_state(v))
            for k, v in self.edge.items()}

        seg0 = segments[0]

        def stage_fn(params_here, x):
            out = x
            for idx, (layer, tag) in enumerate(seg0):
                if tag == "fn":
                    out = layer(Tensor(out)).value if isinstance(
                        out, jnp.ndarray) else layer(out)
                    continue
                prefix = f"{idx}."
                sub = {name[len(prefix):]: arr
                       for name, arr in params_here.items()
                       if name.startswith(prefix)}
                out = functional_call(layer, sub, {}, (out,),
                                      training=True)
            return out

        self._stage_fn = stage_fn
        mesh_ = mesh
        n_stages = self.n_stages
        n_micro_ = n_micro
        opt = optimizer
        lfn = self.loss_fn
        pre_fn, post_fn = self._pre_fn, self._post_fn

        def loss_arr(out, y):
            l = lfn(Tensor(out), Tensor(y))
            return l.value if isinstance(l, Tensor) else l

        n_virtual_ = self.n_virtual

        def apply_trunk(ps, xa):
            if n_virtual_ > 1:
                return pipeline_apply_interleaved(
                    stage_fn, ps, xa, mesh_, n_stages, n_micro_,
                    n_virtual_)
            return pipeline_apply(stage_fn, ps, xa, mesh_, n_stages,
                                  n_micro_)

        if self.schedule == "1f1b":
            def train_step(stacked, edge, opt_state, edge_state, lr,
                           step_i, x, y):
                xm = jnp.stack(jnp.split(x, n_micro_, axis=0))
                ym = jnp.stack(jnp.split(y, n_micro_, axis=0))
                loss, grads, egrads = pipeline_1f1b(
                    stage_fn, stacked, edge, pre_fn, post_fn, loss_arr,
                    xm, ym, mesh_, n_stages, n_micro_)
                new_p, new_s = opt.apply_gradients_tree(
                    stacked, grads, opt_state, lr, step_i)
                if edge:
                    new_e, new_es = opt.apply_gradients_tree(
                        edge, egrads, edge_state, lr, step_i)
                else:
                    new_e, new_es = edge, edge_state
                return loss, new_p, new_e, new_s, new_es
        else:
            def train_step(stacked, edge, opt_state, edge_state, lr,
                           step_i, x, y):
                def loss_of(ps, ep):
                    xa = jax.vmap(lambda xi: pre_fn(ep, xi))(
                        jnp.stack(jnp.split(x, n_micro_, axis=0)))
                    outs = apply_trunk(ps, xa)
                    flat = outs.reshape((-1,) + outs.shape[2:])
                    return loss_arr(post_fn(ep, flat), y)

                loss, (grads, egrads) = jax.value_and_grad(
                    loss_of, argnums=(0, 1))(stacked, edge)
                new_p, new_s = opt.apply_gradients_tree(
                    stacked, grads, opt_state, lr, step_i)
                if edge:
                    new_e, new_es = opt.apply_gradients_tree(
                        edge, egrads, edge_state, lr, step_i)
                else:
                    new_e, new_es = edge, edge_state
                return loss, new_p, new_e, new_s, new_es

        self._train_step_fn = train_step
        self._jitted = jax.jit(train_step, donate_argnums=(0, 1, 2, 3))

    def train_batch(self, x, y):
        self._step_i += 1
        xa = x.value if isinstance(x, Tensor) else jnp.asarray(x)
        ya = y.value if isinstance(y, Tensor) else jnp.asarray(y)
        (loss, self.stacked, self.edge, self.opt_state,
         self.edge_opt_state) = self._jitted(
            self.stacked, self.edge, self.opt_state, self.edge_opt_state,
            jnp.asarray(self.optimizer.get_lr(), jnp.float32),
            self._step_i, xa, ya)
        return Tensor(loss)

    def forward(self, x):
        xa = x.value if isinstance(x, Tensor) else jnp.asarray(x)
        xm = jnp.stack(jnp.split(xa, self.n_micro, axis=0))
        xm = jax.vmap(lambda xi: self._pre_fn(self.edge, xi))(xm)
        if self.n_virtual > 1:
            outs = pipeline_apply_interleaved(
                self._stage_fn, self.stacked, xm, self.mesh,
                self.n_stages, self.n_micro, self.n_virtual)
        else:
            outs = pipeline_apply(self._stage_fn, self.stacked, xm,
                                  self.mesh, self.n_stages, self.n_micro)
        flat = outs.reshape((-1,) + outs.shape[2:])
        return Tensor(self._post_fn(self.edge, flat))
