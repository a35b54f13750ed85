"""LocalSGD — K local optimizer steps per worker, then parameter averaging.

Parity: python/paddle/distributed/fleet/meta_optimizers/localsgd_optimizer.py
(LocalSGDOptimizer: workers train independently for k_steps, then
broadcast-average parameters). TPU-native design: instead of per-worker
processes + allreduce ops inserted into a Program, the per-worker replicas
live as a leading 'dp' axis on every parameter array, sharded over the dp
mesh axis. One jitted shard_map program runs the local step WITHOUT any
gradient psum (each device updates its own replica on its own batch
shard); every k-th call a pmean over 'dp' averages parameters AND
optimizer state (post-local-SGD-style momentum averaging) back into sync.

The payoff on TPU is the same as the reference's on GPU clusters: k-1 of
every k steps run with ZERO cross-device traffic — useful when the
interconnect (DCN between pods) is the bottleneck, not ICI.
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from ...framework.core import Tensor, no_grad, _Slot
from ...framework.random import split_key
from ...jit.api import functional_call, state_arrays

__all__ = ["LocalSGDTrainStep"]


class LocalSGDTrainStep:
    """Build once, call per batch; parameters sync every `k_steps` calls.

        step = LocalSGDTrainStep(model, loss_fn, opt, mesh, k_steps=4)
        for x, y in loader:
            loss = step(x, y)     # psum-free except on sync steps
    """

    def __init__(self, model, loss_fn, optimizer, mesh, k_steps=4,
                 begin_step=1, donate=True):
        if "dp" not in mesh.shape:
            raise ValueError("LocalSGD needs a 'dp' axis on the mesh")
        self.model = model
        self.optimizer = optimizer
        self.mesh = mesh
        self.k_steps = int(k_steps)
        # reference localsgd_configs['begin_step']: train synchronously
        # (sync every call) for the first begin_step calls, THEN switch
        # to K-local-steps mode
        self.begin_step = int(begin_step)
        self._call_i = 0
        dp = mesh.shape["dp"]
        self._dp = dp

        params, self.buffers = state_arrays(model)
        # one replica per dp rank, leading axis sharded over 'dp'
        rep = NamedSharding(mesh, P("dp"))
        self.params = {
            k: jax.device_put(jnp.broadcast_to(v[None], (dp,) + v.shape),
                              rep)
            for k, v in params.items()}
        self.opt_state = {
            k: jax.tree.map(
                lambda s: jax.device_put(
                    jnp.broadcast_to(s[None], (dp,) + s.shape), rep),
                optimizer.init_leaf_state(v))
            for k, v in params.items()}

        model_ref = model
        opt = optimizer

        def loss_of(ps, bufs, key, batch):
            from ...jit.api import reset_aux_losses, collect_aux_losses
            reset_aux_losses(model_ref)
            out = functional_call(model_ref, ps, bufs, batch[:-1],
                                  rng_key=key, training=True)
            l = loss_fn(out if isinstance(out, Tensor) else Tensor(out),
                        Tensor(batch[-1]))
            l = l.value if isinstance(l, Tensor) else l
            aux = collect_aux_losses(model_ref)
            return l if aux is None else l + aux.astype(l.dtype)

        from ...nn.clip import clip_grads_tree

        def _clip(grads):
            return clip_grads_tree(grads, opt._grad_clip)

        def make_local_step(sync):
            # `sync` is STATIC: the k-1 local-step program contains no
            # collective at all (the point of LocalSGD); the sync-step
            # program appends ONE pmean over params+state
            def local_step(params_, opt_state_, bufs, key, lr, step_i,
                           *batch):
                # inside shard_map: arrays are the PER-DEVICE block —
                # params carry their replica axis of size 1; drop it
                ps = jax.tree.map(lambda a: a[0], params_)
                st = jax.tree.map(lambda a: a[0], opt_state_)
                loss, grads = jax.value_and_grad(
                    lambda p: loss_of(p, bufs, key, batch))(ps)
                grads = _clip(grads)
                new_ps, new_st = opt.apply_gradients_tree(
                    ps, grads, st, lr, step_i)
                if sync:
                    new_ps = jax.tree.map(
                        lambda a: jax.lax.pmean(a, "dp"), new_ps)
                    new_st = jax.tree.map(
                        lambda a: jax.lax.pmean(a, "dp"), new_st)
                # loss stays per-replica (shape [1] per shard): averaging
                # happens on host, so local steps carry NO collective
                return (loss[None],
                        jax.tree.map(lambda a: a[None], new_ps),
                        jax.tree.map(lambda a: a[None], new_st))
            return local_step

        self._make_local_step = make_local_step
        self._donate = donate
        self._jit_cache = {}  # (n_batch_arrays, sync) -> jitted program

    def _build(self, n_batch, sync):
        rep_spec = jax.tree.map(lambda _: P("dp"), self.params)
        st_spec = jax.tree.map(lambda _: P("dp"), self.opt_state)
        smapped = shard_map(
            self._make_local_step(sync), mesh=self.mesh,
            in_specs=(rep_spec, st_spec, P(), P(), P(), P())
            + tuple(P("dp") for _ in range(n_batch)),
            out_specs=(P("dp"), rep_spec, st_spec),
            check_vma=False)
        return jax.jit(smapped,
                       donate_argnums=(0, 1) if self._donate else ())

    def __call__(self, *batch):
        arrays = [b.value if isinstance(b, Tensor) else jnp.asarray(b)
                  for b in batch]
        self._call_i += 1
        sync = bool(self._call_i <= self.begin_step
                    or self._call_i % self.k_steps == 0)
        key = (len(arrays), sync)
        jitted = self._jit_cache.get(key)
        if jitted is None:
            jitted = self._jit_cache[key] = self._build(*key)
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        batch_sh = NamedSharding(self.mesh, P("dp"))
        arrays = [jax.device_put(a, batch_sh) for a in arrays]
        losses, self.params, self.opt_state = jitted(
            self.params, self.opt_state, self.buffers, split_key(), lr,
            jnp.asarray(self._call_i, jnp.float32), *arrays)
        return Tensor(jnp.mean(losses))  # host-side mean over replicas

    def replica_spread(self):
        """Max abs deviation across replicas (0 right after a sync step) —
        observability for tests and drift monitoring."""
        m = 0.0
        for v in self.params.values():
            arr = np.asarray(v)
            m = max(m, float(np.max(np.abs(arr - arr[:1]))))
        return m

    def sync_to_model(self):
        """Average replicas into the eager model's parameters."""
        named = dict(self.model.named_parameters())
        with no_grad():
            for k, v in self.params.items():
                named[k]._slot = _Slot(jnp.mean(v, axis=0))
