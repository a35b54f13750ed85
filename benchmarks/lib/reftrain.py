"""Three optimizer steps of the plain reference: loss and gradients of
the configuration's reference forward, and AdamW with decoupled weight
decay as Loshchilov & Hutter (2019) state it and as the cell's file
parameterises it — all float32, nothing of the program imported.

Also the reference put in the program's place, for the control and the
planted faults (run by benchmarks/tools/calibrate.py and the tests,
never by a benchmark run): `prec` below float32, or `fault`:
  "half_batch"   the second half of every batch left out, the mean
                 taken over the rest
"""
import jax
import jax.numpy as jnp
import numpy as np

from ..references.common import (mean_xent, seed_arg, seeded_leaf,
                                  weights_from_seed)
from .memory import compiled

STACKED = ".h.*."


def l2(v, stacked=False):
    """A leaf's L2 norm in float32, or each layer's of a stacked leaf."""
    return jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)),
                            axis=tuple(range(1, v.ndim)) if stacked
                            else None))


def unstack_norms(norms):
    """{leaf: float} from one device call's {leaf: norm, or a row of
    them for a leaf stacked by layer}: "h.*." -> "h.<i>."."""
    out = {}
    for k, v in jax.device_get(norms).items():
        if STACKED in k:
            for i, x in enumerate(np.asarray(v)):
                out[k.replace(STACKED, f".h.{i}.")] = float(x)
        else:
            out[k] = float(v)
    return out


def leaf_norms(tree):
    """{leaf (layers unstacked): float} of L2 norms, one device call."""
    return unstack_norms(jax.jit(
        lambda t: {k: l2(v, STACKED in k) for k, v in t.items()})(tree))


def leafwise_norms(spec, read, seeded=None):
    """Inside a trace: {spec's name: the L2 norm of read(name, layer)
    (layer None for a leaf that is not stacked; a row of norms, one a
    layer, for one that is), less the seeded leaf where `seeded` is
    given}. seeded(i, name, done) gives leaf i of spec's sorted names,
    asked for once the norms before it (`done`) are in the trace; a leaf
    in one row (see regenerated) is cut into its layers' rows."""
    out, done = {}, jnp.zeros((), jnp.float32)
    for i, (k, (shape, _)) in enumerate(sorted(spec.items())):
        w0 = None if seeded is None else seeded(i, k, done)
        rows = []
        for j in range(shape[0]) if STACKED in k else [None]:
            x = read(k, j)
            if w0 is not None:
                w = w0 if j is None else w0[j] if w0.ndim > 1 \
                    else w0[j * x.size:(j + 1) * x.size]
                x = x - w.reshape(x.shape)
            rows.append(l2(x))
        out[k] = done = jnp.stack(rows) if STACKED in k else rows[0]
    return out


def regenerated(spec, s, dtype, flat=False):
    """`seeded` for leafwise_norms: the seeded weights made again inside
    the trace from the folded seed `s`, one leaf at a time (each behind a
    barrier on the norms before it, so that the call holds at most one);
    `flat`: each drawn in one row, as the fused path reads its leaves
    (laid out in its own shape, the leaf would be copied into a row)."""
    key = jax.random.PRNGKey(s)

    def seeded(i, k, done):
        ki, _ = jax.lax.optimization_barrier((key, done))
        shape, kind = spec[k]
        return seeded_leaf(ki, i, shape, kind, jnp.dtype(dtype), flat)
    return seeded


def change_norms(spec, seed, dtype, w, phases=None):
    """{leaf: |w - the seeded weights|} in one call that makes the seeded
    weights again from the seed and returns the norms alone."""
    def change(w, s):
        return leafwise_norms(
            spec, lambda k, j: w[k] if j is None else w[k][j],
            regenerated(spec, s, dtype))
    s = jnp.int32(seed_arg(seed))
    exe = compiled(change, w, s)
    if phases is not None:
        phases.readings("reference.change", exe)
    return unstack_norms(exe(w, s))


def adamw(w, g, m, v, t, hp):
    b1, b2 = hp["beta1"], hp["beta2"]
    lr_t = hp["lr"] * (1 - b2 ** t) ** 0.5 / (1 - b1 ** t)

    def leaf(w, g, m, v):
        w = w * (1.0 - hp["lr"] * hp["weight_decay"])
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        return w - lr_t * m / (jnp.sqrt(v) + hp["epsilon"]), m, v

    out = {k: leaf(w[k], g[k], m[k], v[k]) for k in w}
    return ({k: o[0] for k, o in out.items()},
            {k: o[1] for k, o in out.items()},
            {k: o[2] for k, o in out.items()})


def reference_train(ref, config, seed, batches, hp, prec="f32", micro=4,
                    fault=None, phases=None):
    """batches: the first steps' token arrays [B, S+1] (host). Returns
    {"losses", "grad_norms" (first step), "change_norms" (after all)}.
    Holds w, m, v and one gradient: the seeded weights are made again
    for the change, and a batch split into micro-batches adds each part's
    gradient into the one sum in place. `phases` (lib/memory.py), where
    given, is told each executable and marked at each step's fullest."""
    spec = ref.param_spec(config)

    def loss_and_grad(w, toks):
        def loss(w):
            return mean_xent(ref.forward(w, config, toks[:, :-1], prec),
                             toks[:, 1:])
        return jax.value_and_grad(loss)(w)

    def add_grad(w, toks, acc, share):
        l, g = loss_and_grad(w, toks)
        return l, jax.tree.map(lambda a, b: a + b * share, acc, g)

    w = weights_from_seed(spec, seed, config["dtype"])
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    exe = {}
    out = {"losses": []}
    for t, toks in enumerate(batches, 1):
        toks = np.asarray(toks)
        if fault == "half_batch":
            toks = toks[:toks.shape[0] // 2]
        parts = [jnp.asarray(toks[i:i + micro], jnp.int32)
                 for i in range(0, toks.shape[0], micro)]
        if len(parts) == 1:
            key = ("grad", parts[0].shape)
            if key not in exe:
                exe[key] = compiled(loss_and_grad, w, parts[0],
                                    phases=phases, name="reference.grad")
            l, grads = exe[key](w, parts[0])
            loss = float(l)
        else:
            loss, grads = 0.0, jax.tree.map(jnp.zeros_like, w)
            for p in parts:
                share = p.shape[0] / toks.shape[0]
                key = ("add_grad", p.shape)
                if key not in exe:
                    exe[key] = compiled(add_grad, w, p, grads,
                                        jnp.float32(share), phases=phases,
                                        name="reference.grad",
                                        donate_argnums=2)
                l, grads = exe[key](w, p, grads, jnp.float32(share))
                loss += float(l) * share
        out["losses"].append(loss)
        if phases is not None:
            phases.mark(f"step{t}")
        if t == 1:
            out["grad_norms"] = leaf_norms(grads)
        upd = compiled(lambda w, g, m, v: adamw(w, g, m, v, t, hp),
                       w, grads, m, v, phases=phases,
                       name="reference.update", donate_argnums=(0, 2, 3))
        w, m, v = upd(w, grads, m, v)
        # the gradient goes once the update has read it: the next step's,
        # dispatched while the update still runs, would lie beside it
        del grads
        jax.block_until_ready(w)
    del m, v
    out["change_norms"] = change_norms(spec, seed, config["dtype"], w,
                                       phases)
    return out
