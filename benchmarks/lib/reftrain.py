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

from ..references.common import mean_xent, weights_from_seed


def leaf_norms(tree):
    """{leaf (layers unstacked: "h.*." -> "h.<i>."): float} of L2 norms,
    float32 accumulation, one device call."""
    @jax.jit
    def norms(t):
        return {k: jnp.sqrt(jnp.sum(
            jnp.square(v.astype(jnp.float32)),
            axis=tuple(range(1, v.ndim)) if ".h.*." in k else None))
            for k, v in t.items()}
    out = {}
    for k, v in jax.device_get(norms(tree)).items():
        if ".h.*." in k:
            for i, x in enumerate(np.asarray(v)):
                out[k.replace(".h.*.", f".h.{i}.")] = float(x)
        else:
            out[k] = float(v)
    return out


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
                    fault=None):
    """batches: the first steps' token arrays [B, S+1] (host). Returns
    {"losses", "grad_norms" (first step), "change_norms" (after all)}."""
    spec = ref.param_spec(config)
    w0 = weights_from_seed(spec, seed, config["dtype"])

    @jax.jit
    def loss_and_grad(w, toks):
        def loss(w):
            return mean_xent(ref.forward(w, config, toks[:, :-1], prec),
                             toks[:, 1:])
        return jax.value_and_grad(loss)(w)

    upd = jax.jit(lambda w, g, m, v, t: adamw(w, g, m, v, t, hp),
                  static_argnums=(4,), donate_argnums=(0, 2, 3))
    w = jax.tree.map(jnp.copy, w0)
    m = jax.tree.map(jnp.zeros_like, w0)
    v = jax.tree.map(jnp.zeros_like, w0)
    out = {"losses": []}
    for t, toks in enumerate(batches, 1):
        toks = np.asarray(toks)
        if fault == "half_batch":
            toks = toks[:toks.shape[0] // 2]
        parts = [toks[i:i + micro] for i in range(0, toks.shape[0], micro)]
        loss, grads = 0.0, None
        for p in parts:
            l, g = loss_and_grad(w, jnp.asarray(p, jnp.int32))
            share = p.shape[0] / toks.shape[0]
            loss += float(l) * share
            g = jax.tree.map(lambda a: a * share, g)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        out["losses"].append(loss)
        if t == 1:
            out["grad_norms"] = leaf_norms(grads)
        w, m, v = upd(w, grads, m, v, t)
    out["change_norms"] = leaf_norms(
        jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))(w, w0))
    return out
