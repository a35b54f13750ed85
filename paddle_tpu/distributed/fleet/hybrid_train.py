"""Hybrid-parallel jitted train step — the fleet execution engine.

The TPU-native replacement for the reference's HybridParallelOptimizer +
PipelineParallel + ShardingStage2 runtime classes (distributed/fleet/
meta_parallel/*): one jax.jit'ed SPMD program over the fleet mesh where

- batch is sharded over ('dp',)                       [data parallel]
- params follow per-layer PartitionSpecs over 'mp'    [tensor parallel]
- optimizer states are additionally sharded over the
  'sharding' axis (ZeRO-1/2)                          [sharding]
- blocks can be rematerialized (jax.checkpoint)       [recompute]
- gradient accumulation folds microbatches in a scan  [gradient_merge /
                                                       pipeline microbatch]

XLA inserts psum for dp grad sync (reference: reducer.cc fused allreduce),
allreduce/allgather for mp (reference: mp_allreduce), and reduce-scatter
for ZeRO — all over ICI.
"""
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ...framework.core import Tensor, no_grad, _Slot
from ...framework.random import split_key
from jax import shard_map
from ...framework import fault_injection as _fault
from ...jit.api import (functional_call, state_arrays, aot_compile,
                        count_train_use, export_step_metrics,
                        HealthMonitorMixin, CheckpointSnapshotMixin,
                        fire_step_faults, _step_arg_names,
                        epilogue_leaf_meta)
from ...jit import warm as _warm
from ...jit.deferred import DeferredLoss
from ...profiler import statistic as _stat
from ...profiler import monitor as _monitor
from ...profiler import cost as _cost
from ...profiler import flight_recorder as _flight
from ...profiler import mem_observatory as _mobs

__all__ = ["HybridTrainStep", "default_param_rules"]


def default_param_rules(name, arr):
    """Name-based PartitionSpec rules for transformer-family models when a
    layer doesn't announce its own sharding_spec."""
    if arr.ndim == 2:
        if any(k in name for k in ("qkv_proj.weight", "fc_in.weight",
                                   "q_proj.weight", "k_proj.weight",
                                   "v_proj.weight", "linear1.weight")):
            return P(None, "mp")
        if any(k in name for k in ("out_proj.weight", "fc_out.weight",
                                   "linear2.weight")):
            return P("mp", None)
        if any(k in name for k in ("wte.weight", "embed_tokens.weight",
                                   "word_embeddings.weight")):
            return P("mp", None)
    if arr.ndim == 1 and any(k in name for k in ("qkv_proj.bias",
                                                 "fc_in.bias",
                                                 "linear1.bias")):
        return P("mp")
    return P()


def _collect_specs(model, params):
    """Layer-announced sharding_spec()s override the name rules."""
    specs = {}
    for lname, layer in model.named_sublayers(include_self=True):
        spec_fn = getattr(layer, "sharding_spec", None)
        if spec_fn is None:
            continue
        for pname, spec in spec_fn().items():
            full = f"{lname}.{pname}" if lname else pname
            specs[full] = spec
    out = {}
    for k, v in params.items():
        out[k] = specs.get(k, default_param_rules(k, v))
    return out


def _zero_spec(pspec, mesh, arr):
    """Extend a param spec with the 'sharding' axis on the first
    axis that is unsharded and divisible (ZeRO state placement)."""
    deg = mesh.shape.get("sharding", 1)
    if deg == 1:
        return pspec
    dims = list(pspec) + [None] * (arr.ndim - len(pspec))
    for i, d in enumerate(dims):
        if d is None and arr.shape[i] % deg == 0 and arr.shape[i] >= deg:
            dims[i] = "sharding"
            return P(*dims)
    return pspec


class HybridTrainStep(HealthMonitorMixin, CheckpointSnapshotMixin):
    """Build once, call per batch. See module docstring."""

    def __init__(self, model, loss_fn, optimizer, mesh, recompute=False,
                 accumulate_steps=1, donate=True, param_dtype=None,
                 sharding_stage=1, scaler=None, monitor_health=False,
                 fused_update=None):
        """sharding_stage selects the ZeRO behavior over the 'sharding'
        mesh axis (ref sharding/sharding_stage2.py:43, sharding_stage3.py:51):
          1 — optimizer state sharded (grads allreduced, params replicated)
          2 — + gradients pinned to the zero specs: the update runs on
              grad shards and the grad sync lowers to all-reduce+slice,
              which the TPU ReduceScatterCreator pass fuses into a true
              reduce-scatter (half the sync bytes); updated params
              all-gather back to their param specs
          3 — + parameters THEMSELVES stored sharded; XLA all-gathers
              weights at use sites and frees them after use

        monitor_health=True appends the training-health vector (global
        grad norm, param norm, update ratio — jit/api.py
        HealthMonitorMixin) to the compiled SPMD program, replicated
        over the mesh, resolved on the async is_ready-gated path."""
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.mesh = mesh
        self.accumulate_steps = accumulate_steps
        self.sharding_stage = int(sharding_stage)
        if self.sharding_stage not in (1, 2, 3):
            raise ValueError(f"sharding_stage must be 1|2|3, got "
                             f"{sharding_stage}")
        self._step_i = 0
        # GradScaler state rides inside the compiled step (donated, like
        # params/opt state); replicated over the mesh
        self.scaler = scaler
        self.scaler_state = scaler.init_jit_state() if scaler is not None \
            else {}
        self.retraces = 0
        self.compile_s = 0.0
        self.last_compile_s = None
        self._init_health(monitor_health)

        params, buffers = state_arrays(model)
        if param_dtype is not None:
            from ...framework.dtype import convert_dtype
            dt = convert_dtype(param_dtype)
            params = {k: v.astype(dt) if jnp.issubdtype(
                v.dtype, jnp.floating) else v for k, v in params.items()}
        self.param_specs = _collect_specs(model, params)
        self.zero_specs = {
            k: _zero_spec(self.param_specs[k], mesh, v)
            for k, v in params.items()}
        # stage 3: parameters live sharded over 'sharding'; XLA
        # all-gathers them at use sites (ZeRO-3 param partitioning)
        store_specs = self.zero_specs if self.sharding_stage >= 3 \
            else self.param_specs
        self.param_shardings = {
            k: NamedSharding(mesh, store_specs[k])
            for k in self.param_specs}
        # params are donated every step; place a private copy
        # (jnp.array), as TrainStep does, so the model's own Parameters
        # stay valid: device_put of a replicated leaf shares the buffer
        # on the device it already lives on — even with may_alias=False
        # under jax 0.9.0 — and the first step's donation would delete
        # the model's array with it
        self.params = {
            k: jax.device_put(jnp.array(v), self.param_shardings[k])
            for k, v in params.items()}
        self.buffers = buffers

        # optimizer state: param spec + ZeRO sharding axis
        def init_state(k, v):
            # init_leaf_state may wrap the tuple with an f32 master copy
            # (multi_precision); master/state leaves all share the param's
            # ZeRO sharding (same shapes)
            st = optimizer.init_leaf_state(v)
            sh = NamedSharding(mesh, _zero_spec(self.param_specs[k], mesh,
                                                v))
            return jax.tree.map(lambda s: jax.device_put(s, sh), st)
        self.opt_state = {k: init_state(k, v)
                          for k, v in self.params.items()}
        # memory-observatory attribution: donated stores are REPLACED
        # each step — getters read the current trees at report time
        _mobs.register("params",
                       self, lambda s: jax.tree.leaves(s.params))
        _mobs.register("opt_state",
                       self, lambda s: jax.tree.leaves(s.opt_state))

        # batch dim over dp; with a sequence-parallel mesh (sp>1), the
        # sequence dim is sharded over 'sp' too — ring attention inside
        # the model consumes it without gathering (long-context path)
        sp_deg = mesh.shape.get("sp", 1)
        self.batch_sharding = NamedSharding(
            mesh, P(("dp",), "sp") if sp_deg > 1 else P(("dp",)))
        self._dp_only = NamedSharding(mesh, P(("dp",)))
        loss_sharding = NamedSharding(mesh, P())

        model_ref = model
        opt = optimizer
        stage = self.sharding_stage
        zero_shardings = {k: NamedSharding(mesh, s)
                          for k, s in self.zero_specs.items()}
        # per-leaf epilogue metadata, shared by the fused kernels and
        # the tree path (defaults are trivial: historical numerics)
        (self._leaf_meta, self._need_clip_tree, self._decay_mask_tree,
         self._lr_scale_tree) = epilogue_leaf_meta(model, optimizer,
                                                   self.params)
        # fused multi-tensor epilogue over PER-SHARD dtype buckets:
        # every leaf's ZeRO shard flattens into its device-local bucket,
        # the kernels run on local contiguous buffers, and ONE psum (of
        # norm-weighted partial sums) yields the global grad norm
        self._fused = self._build_fused(fused_update)
        if self._fused is not None:
            from ...nn.clip import ClipGradByGlobalNorm
            lay = self._fused.layout
            master_keys = {
                key for key, leaf in lay.leaf_order
                if isinstance(self.opt_state[leaf.name], dict)}
            # PER-DEVICE bytes (local shards), matching the per-device
            # cost_analysis the step record's bytes come from
            self._epilogue_bytes = self._fused.bytes_per_step(
                scaling=scaler is not None and scaler.is_enable(),
                need_norm=bool(monitor_health) or isinstance(
                    optimizer._grad_clip, ClipGradByGlobalNorm),
                master_keys=master_keys)
            # hybrid packs grads/params/opt into local buckets each
            # step inside the shard_map (states stay tree-sharded at
            # rest): account that traffic too
            pack_elems = sum(b.total * b.dtype.itemsize
                             for b in lay.buckets.values())
            n_state = self._fused.spec["n_moments"] + 1 + (
                1 if master_keys else 0)
            self._epilogue_bytes += 2 * (n_state + 1) * pack_elems

        def loss_of(ps, bufs, key, micro):
            def run(inputs):
                from ...jit.api import (reset_aux_losses,
                                        collect_aux_losses)
                from ...ops import kernels_partitioned_over
                reset_aux_losses(model_ref)
                # this step is ONE auto-partitioned program over the
                # mesh, and a Mosaic kernel cannot be partitioned
                # automatically: while the forward traces, the flash
                # kernel runs per shard — batch over 'dp', heads over
                # 'mp', the axes this step shards them on
                with kernels_partitioned_over(mesh, "dp", "mp"):
                    out = functional_call(model_ref, ps, bufs,
                                          inputs[:-1], rng_key=key,
                                          training=True)
                tgt = Tensor(inputs[-1])
                l = loss_fn(out if isinstance(out, Tensor) else Tensor(out),
                            tgt)
                l = l.value if isinstance(l, Tensor) else l
                aux = collect_aux_losses(model_ref)
                return l if aux is None else l + aux.astype(l.dtype)
            if recompute:
                run = jax.checkpoint(run)
            return run(micro)

        scaler_ref = scaler
        mon_health = self.monitor_health

        def step_fn(params_, opt_state_, scaler_state_, bufs, key, lr,
                    step_i, *batch):
            scaling = scaler_ref is not None and scaler_ref.is_enable()
            scale = scaler_state_["scale"] if scaling else None

            def objective(ps, micro):
                l = loss_of(ps, bufs, key, micro)
                return l.astype(jnp.float32) * scale if scaling else l

            if accumulate_steps > 1:
                micros = [jnp.stack(jnp.split(b, accumulate_steps, axis=0))
                          for b in batch]

                def acc_body(carry, micro):
                    loss_sum, grads_sum = carry
                    l, g = jax.value_and_grad(
                        lambda ps: objective(ps, micro))(params_)
                    return (loss_sum + l,
                            jax.tree.map(jnp.add, grads_sum, g)), None

                zeros = jax.tree.map(jnp.zeros_like, params_)
                (loss_sum, grads), _ = jax.lax.scan(
                    acc_body, (jnp.zeros((), jnp.float32), zeros),
                    tuple(micros))
                loss = loss_sum / accumulate_steps
                grads = jax.tree.map(lambda g: g / accumulate_steps, grads)
            else:
                loss, grads = jax.value_and_grad(
                    lambda ps: objective(ps, batch))(params_)

            if scaling:
                loss = loss / scale

            if self._fused is not None:
                # fused multi-tensor epilogue: unscale + ONE psum'd
                # global norm + clip + update, as per-shard bucket
                # kernels under shard_map (see _fused_finish)
                new_params, new_state, new_scaler_state, aux = \
                    self._fused_finish(grads, params_, opt_state_,
                                       scaler_state_, lr, step_i)
            else:
                if scaling:
                    grads, found_inf, new_scaler_state = \
                        scaler_ref.jit_unscale_and_update(scaler_state_,
                                                          grads)
                else:
                    found_inf, new_scaler_state = None, scaler_state_

                if stage >= 2:
                    # ZeRO-2: pin gradients to the zero specs — the SPMD
                    # partitioner then lowers dp grad sync as
                    # reduce-scatter (each rank keeps only its grad
                    # shard) instead of all-reduce, and the optimizer
                    # update below runs on shards (ref
                    # sharding_stage2.py:43)
                    grads = jax.lax.with_sharding_constraint(
                        grads, zero_shardings)

                from ...nn.clip import (clip_grads_tree, global_grad_norm,
                                        ClipGradByGlobalNorm)
                gn = None
                if mon_health or isinstance(opt._grad_clip,
                                            ClipGradByGlobalNorm):
                    # computed ONCE, shared by the clip factor and the
                    # health vector's grad_norm (no second traversal)
                    gn = global_grad_norm(grads, self._need_clip_tree)
                grads = clip_grads_tree(grads, opt._grad_clip,
                                        need_clip=self._need_clip_tree,
                                        global_norm=gn)
                new_params, new_state = opt.apply_gradients_tree(
                    params_, grads, opt_state_, lr, step_i,
                    found_inf=found_inf,
                    decay_mask=self._decay_mask_tree,
                    lr_scale=self._lr_scale_tree)
                aux = {"grad_norm": gn, "found_inf": found_inf}
                if mon_health:
                    self._tree_health_aux(aux, params_, new_params)
                    if gn is not None and \
                            self._need_clip_tree is not None:
                        # leaves the need_clip mask keeps out of the
                        # norm must still trip health found_inf
                        nonfin = ~jnp.isfinite(gn)
                        for k, g in grads.items():
                            if not self._need_clip_tree.get(k, True):
                                nonfin = nonfin | jnp.any(~jnp.isfinite(
                                    g.astype(jnp.float32)))
                        aux["nonfinite"] = nonfin
            if mon_health:
                health = self._health_vec(loss, aux)
                return loss, health, new_params, new_state, \
                    new_scaler_state
            return loss, new_params, new_state, new_scaler_state

        # mirror each state leaf's structure (tuple, or the
        # {master, state} dict init_leaf_state builds for multi_precision)
        state_shardings = {
            k: jax.tree.map(
                lambda _s, _sh=NamedSharding(
                    mesh, _zero_spec(self.param_specs[k], mesh,
                                     self.params[k])): _sh,
                self.opt_state[k])
            for k in self.opt_state}
        scaler_shardings = jax.tree.map(
            lambda _: NamedSharding(mesh, P()), self.scaler_state)
        out_shardings = (loss_sharding, self.param_shardings,
                         state_shardings, scaler_shardings)
        if mon_health:  # health vector rides replicated, like the loss
            out_shardings = (loss_sharding, NamedSharding(mesh, P()),
                             *out_shardings[1:])
        self._jitted = jax.jit(
            step_fn,
            donate_argnums=(0, 1, 2) if donate else (),
            out_shardings=out_shardings)
        # AOT executables keyed by batch signature (jit.api.aot_compile):
        # trace/compile phases timed, persistent-cache hit observed,
        # cost_analysis free
        self._exec = {}

    # -- fused per-shard epilogue ---------------------------------------
    def _build_fused(self, fused_update):
        """A FusedEpilogue over the LOCAL (ZeRO-shard) leaf shapes, or
        None -> per-leaf tree path. The bucket layout is built from each
        leaf's `zero_spec` shard shape — the update always runs on
        optimizer-state shards (ZeRO semantics for every stage); leaves
        replicated over some mesh axes carry a norm_weight of
        1/replication so the ONE global-norm psum does not count a
        replica per device."""
        import os
        if fused_update is None:
            fused_update = os.environ.get(
                "PADDLE_TPU_FUSED_UPDATE", "1") != "0"
        if not fused_update or not self.params:
            return None
        spec = self.optimizer.fused_spec()
        if spec is None:
            return None
        from ...nn.clip import ClipGradByGlobalNorm, ClipGradByValue
        clip = self.optimizer._grad_clip
        if clip is not None and not isinstance(
                clip, (ClipGradByGlobalNorm, ClipGradByValue)):
            return None
        if not all(jnp.issubdtype(v.dtype, jnp.floating)
                   for v in self.params.values()):
            return None
        from ...ops.pallas.fused_update import (BucketLayout,
                                                FusedEpilogue)
        mesh = self.mesh
        leaves, meta = [], {}
        for k, v in self.params.items():
            zspec = self.zero_specs[k]
            lshape = NamedSharding(mesh, zspec).shard_shape(v.shape)
            axes = set()
            for d in zspec:
                if d is None:
                    continue
                axes.update(d if isinstance(d, (tuple, list)) else (d,))
            sharded = int(np.prod([mesh.shape[a] for a in axes])) \
                if axes else 1
            rep = mesh.size // sharded
            leaves.append((k, lshape, v.dtype))
            meta[k] = dict(self._leaf_meta[k], norm_weight=1.0 / rep)
        layout = BucketLayout(leaves, meta=meta)
        epi = FusedEpilogue(layout, spec)
        epi.set_psum_axes(tuple(mesh.axis_names))
        return epi

    def _fused_finish(self, grads, params, opt_state, scaler_state, lr,
                      step_i):
        """The fused epilogue as ONE shard_map region: every device
        packs its local ZeRO shards into dtype buckets, runs the two
        Pallas passes, and the global grad norm / found_inf / health
        sums reduce with one psum (+pmax) — then the per-leaf tree comes
        back out and the jit-level out_shardings re-gather parameters to
        their storage layout (an all-gather for stage < 3, a no-op for
        stage 3 where storage IS the zero layout)."""
        epi = self._fused
        lay = epi.layout
        scaler = self.scaler
        clip = self.optimizer._grad_clip
        mon = self.monitor_health
        zero = jnp.float32(0.0)

        def body(grads, params, opt_state, scaler_state, lr, step_i):
            g_store = lay.pack(grads)
            p_store = lay.pack(params)
            o_store = epi.pack_opt_tree(opt_state)
            new_p, new_o, new_sc, aux = epi.finish(
                g_store, p_store, o_store, lr, step_i, scaler=scaler,
                scaler_state=scaler_state, clip=clip, with_stats=mon)
            found = aux["found_inf"]
            aux_vec = jnp.stack([
                aux["grad_norm"],
                found.astype(jnp.float32) if found is not None
                else jnp.float32(-1.0),
                aux.get("param_sumsq", zero),
                aux.get("update_sumsq", zero)])
            return (lay.unpack(new_p), epi.state_view(new_o), new_sc,
                    aux_vec)

        zspecs = {k: self.zero_specs[k] for k in params}
        state_specs = {
            k: jax.tree.map(lambda _, s=self.zero_specs[k]: s,
                            opt_state[k])
            for k in opt_state}
        scaler_specs = jax.tree.map(lambda _: P(), scaler_state)
        new_params, new_state, new_sc, aux_vec = shard_map(
            body, mesh=self.mesh,
            in_specs=(zspecs, zspecs, state_specs, scaler_specs, P(),
                      P()),
            out_specs=(zspecs, state_specs, scaler_specs, P()),
            check_vma=False)(grads, params, opt_state, scaler_state, lr,
                             step_i)
        found = None
        if scaler is not None and scaler.is_enable():
            found = aux_vec[1] > 0
        aux = {"grad_norm": aux_vec[0], "found_inf": found,
               "param_sumsq": aux_vec[2], "update_sumsq": aux_vec[3]}
        return new_params, new_state, new_sc, aux

    def input_sharding(self, arr):
        """Sharding the compiled step expects for a batch leaf (batch dim
        over 'dp', sequence over 'sp' when sequence-parallel). The device
        prefetch ring (io/device_prefetch.py) places H2D copies with this
        while the previous step computes, so `_prep` below finds the
        arrays already resident and sharded."""
        return self.batch_sharding if arr.ndim >= 2 else self._dp_only

    def _prep(self, batch, step_i):
        """(sig, full arg tuple) for one dispatch — the ONE place the
        batch is sharded and the signature built: __call__ and the
        inspection paths must agree exactly, because the cached
        executable bakes the input shardings. An array that already
        carries its target sharding (prefetch ring) passes through
        without a fresh device_put."""
        arrays = []
        for b in batch:
            a = b.value if isinstance(b, Tensor) else jnp.asarray(b)
            sh = self.input_sharding(a)
            if getattr(a, "sharding", None) != sh:
                a = jax.device_put(a, sh)
            arrays.append(a)
        sig = tuple((tuple(a.shape), str(a.dtype)) for a in arrays)
        args = (self.params, self.opt_state, self.scaler_state,
                self.buffers, split_key(),
                jnp.asarray(self.optimizer.get_lr(), jnp.float32),
                step_i, *arrays)
        return sig, args

    def _warm_submit(self, sig, args, n_batch, inline=False):
        """Single-flight compile of this signature's SPMD executable
        (jit/warm.py submit_cached) — shared by `warm()` (background)
        and the dispatch/inspection paths (`inline=True`: compile on
        the calling thread rather than queue behind background warms),
        so a warm in flight is always joined, never duplicated."""
        return _warm.submit_cached(
            self._exec, sig, "fleet.hybrid_step",
            lambda: aot_compile(self._jitted, args,
                                tag="fleet.hybrid_step",
                                arg_names=_step_arg_names(n_batch)),
            inline=inline)

    def warm(self, *batch):
        """Start a BACKGROUND AOT compile of the hybrid SPMD executable
        for exactly this batch signature (same `_prep`, same shardings
        and donation as dispatch — warming adds zero executables beyond
        steady state) and return a `jit.warm.WarmHandle`. The first
        `__call__` with this signature joins the in-flight compile."""
        sig, args = self._prep(batch, self._step_i + 1)
        return self._warm_submit(sig, args, len(batch))

    def set_tree_state(self, params=None, opt_state=None):
        """Load per-leaf state back into the step (checkpoint restore:
        distributed/checkpoint.py) — the sharded counterpart of
        TrainStep.set_tree_state: every array is device_put DIRECTLY
        onto its storage sharding (params to `param_shardings`,
        optimizer state to its live leaf's ZeRO placement), so a
        resume lands dp/mp-sharded without materializing the full
        tree on one host."""
        if params is not None:
            self.params = {
                k: jax.device_put(v, self.param_shardings[k])
                for k, v in params.items()}
        if opt_state is not None:
            self.opt_state = {
                k: jax.tree.map(
                    lambda new, cur: jax.device_put(new, cur.sharding),
                    opt_state[k], self.opt_state[k])
                for k in self.opt_state}

    def __call__(self, *batch):
        """One hybrid-parallel optimizer step. On the host the call is
        one `fleet.hybrid_step` span with TrainStep.__call__'s children
        under TrainStep's names: `train.step.prep`,
        `train.step.dispatch`, `train.step.telemetry`."""
        self._step_i += 1
        with _stat.span("fleet.hybrid_step", step_num=self._step_i):
            with _stat.span("train.step.prep"):
                if _fault.active():  # fault drills only; two dict reads when off
                    batch = fire_step_faults(self, batch)
                sig, args = self._prep(batch, self._step_i)
            out, info, compiled_now, dispatch_s = self._dispatch(
                sig, args, len(batch))
            health = None
            if self.monitor_health:
                loss, health, self.params, self.opt_state, \
                    self.scaler_state = out
            else:
                loss, self.params, self.opt_state, self.scaler_state = out
            with _stat.span("train.step.telemetry"):
                if health is not None:
                    self._queue_health(self._step_i, health)
                export_step_metrics(self, dispatch_s, info, compiled_now)
                # non-blocking handle (see jit/deferred.py): the fit
                # loop keeps dispatching while the loss streams back
                return DeferredLoss(loss)

    def _dispatch(self, sig, args, n_batch):
        """Executable-cache lookup, a joined or inline compile on a
        miss, and the timed dispatch, under `train.step.dispatch`.
        Returns (outputs, info, compiled_now, dispatch_s)."""
        _flight.heartbeat(self._step_i)  # watchdog liveness pulse
        _stat.begin_span("train.step.dispatch")
        try:
            entry = self._exec.get(sig)
            compiled_now = entry is None
            if compiled_now:
                entry = self._warm_submit(sig, args, n_batch,
                                          inline=True).result()
            compiled, info = entry
            count_train_use(self, info)
            try:
                if getattr(self, "_oom_fault", False):
                    # oom@train.step soft fault: raise the synthetic
                    # exhaustion inside the real dispatch try (same
                    # contract as TrainStep._dispatch)
                    self._oom_fault = False
                    raise RuntimeError(
                        "RESOURCE_EXHAUSTED: injected OOM "
                        "(oom@train.step fault): failed to allocate "
                        "request for 8.00GiB on device")
                out = compiled(*args)
            except (FloatingPointError, RuntimeError) as e:
                if _mobs.is_oom(e):
                    raise _mobs.oom_error(
                        e, site="fleet.hybrid_step") from e
                # jax_debug_nans found a non-finite value: flight-record
                # and write a debug bundle before re-raising (same
                # contract as TrainStep._dispatch, incl. the donated-
                # buffer re-run surfacing as a deleted-array error)
                donated_rerun = (
                    isinstance(e, RuntimeError)
                    and jax.config.jax_debug_nans
                    and "deleted" in str(e))
                if isinstance(e, RuntimeError) and not donated_rerun:
                    raise
                _flight.record_event("nan_detected",
                                     where="fleet.hybrid_step",
                                     step=int(self._step_i),
                                     error=str(e)[:300])
                _flight.dump("nan", exc=e)
                if donated_rerun:
                    raise FloatingPointError(
                        "jax_debug_nans detected a non-finite value in "
                        "the compiled fleet.hybrid_step program (the "
                        "op-level re-run could not localize it because "
                        "the step donates its buffers; build with "
                        "donate=False to localize)") from e
                raise
        finally:
            dispatch_s = _stat.end_span()
        return out, info, compiled_now, dispatch_s

    def cost_analysis(self, *batch):
        """XLA cost report for this batch signature's SPMD executable
        (per-device flops/bytes; free once the step has run, and never
        touching the retrace counters)."""
        return _cost.cost_analysis(self._executable(*batch))

    def flops(self, *batch):
        """Per-step per-device FLOPs of the compiled SPMD program."""
        return _cost.executable_flops(self._executable(*batch))

    def _executable(self, *batch):
        sig, args = self._prep(batch, self._step_i + 1)
        entry = self._exec.get(sig)
        if entry is None:
            entry = self._warm_submit(sig, args, len(batch),
                                      inline=True).result()
        return entry[0]

    def sync_to_model(self):
        named = dict(self.model.named_parameters())
        with no_grad():
            for k, v in self.params.items():
                named[k]._slot = _Slot(v)
        if self.scaler is not None and self.scaler_state:
            self.scaler.sync_from_jit_state(self.scaler_state)

    def compiled_text(self, *batch):
        """Optimized HLO for inspection/tests; reuses the AOT executable
        cache — no extra compile once the step has run."""
        return self._executable(*batch).as_text()
