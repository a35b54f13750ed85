"""Fused multi-tensor optimizer-update epilogue (Pallas, TPU-native).

The train-step epilogue — unscale, global-norm clip, decoupled decay,
moment update, master-weight downcast — is classically emitted as a
per-leaf op chain: for an L-layer model that is hundreds of tiny HLO ops
XLA cannot always fuse across leaf boundaries (*Operator Fusion in XLA*,
arxiv 2301.13062), inflating both bytes-accessed per step and compile
seconds. This module is the multi-tensor fix, scheduled as locality-aware
passes over contiguous buffers (the *Neptune* pattern, arxiv 2510.08726):

- Parameters, gradients, moments, and f32 master weights live in
  **dtype-bucketed flat buffers** (`BucketLayout`): one exact-sized
  buffer per (dtype, scan-group run) — the members of a run (same role
  across the layer stack, e.g. every layer's qkv weight) pack densely
  in layer order. The model's forward consumes cheap slice views
  (`unpack`); a scan-over-layers model's per-step `jnp.stack` of block
  weights folds onto the run buffer (a free reshape, not a gather),
  and the stacked gradient its backward emits folds straight back into
  the run's gradient buffer through `unpack`'s custom VJP (one stack
  per run — not a pad+add chain per leaf, and no concat traffic for
  scan groups).
- **Pass 1** (`_pass1_math`) fuses gradient unscaling with per-chunk L2
  partial sums and a non-finite sweep: ONE read of the grads yields the
  unscaled buffer, the global grad norm, and found_inf. The norm is
  shared three ways by the caller — GradScaler found_inf handling, the
  clip factor, and the health vector's grad_norm.
- **Pass 2** (`_pass2_math`) applies clip factor + decoupled weight
  decay + the moment update (AdamW/Adam/Momentum/SGD) + the
  master-weight downcast in one sweep, with the found_inf skip folded
  in as a select and optional health statistics (param norm, update
  norm) accumulated on the side.

Per-leaf metadata — lr scale, decay-applies, need-clip, and the norm
weight hybrid sharding uses to de-duplicate replicated leaves — is
uniform per bucket BY CONSTRUCTION (a bucket is one (dtype, scan-group
run, metadata class)), so both execution paths resolve it to
python-static decisions per bucket (`BucketLayout.bucket_meta`): no
per-chunk table rides to the kernel. Stores are exact-sized (padding to
whole 128-lane rows exists only transiently at the Pallas call
boundary, and only for a bucket whose size is not a multiple of 128).

Execution modes (`FusedEpilogue`): on TPU the passes run as real Pallas
kernels over the bucket viewed as [rows, 128] — `_BLOCK_ROWS` rows per
grid step (a bucket smaller than that is one full block; a tail block
is masked out of the reductions), buffers aliased in place via
input_output_aliases to compose with the step's donation. Off-TPU the
SAME per-bucket math (`_pass1_block`/`_pass2_block`) runs directly on
the whole flat buffers — XLA:CPU then fuses it like any elementwise
graph, so tier-1 proves the identical update math, and
`PADDLE_TPU_FUSED_INTERPRET=1` (or interpret=True) additionally routes
CPU through Pallas interpret mode with the SAME blocking the chip gets,
so the kernel plumbing itself — grid, BlockSpecs, tail mask, SMEM
accumulators — is exercised by tests too.
"""
import collections.abc
import functools
import os

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import I0

__all__ = ["BucketLayout", "FusedEpilogue"]

# per-leaf metadata bit flags (BucketLayout.leaf_flags)
FLAG_NEED_CLIP = 1
FLAG_DECAY = 2

_F32 = jnp.float32
# the kernels view a bucket as [rows, 128]: one lane row per 128
# elements, _BLOCK_ROWS rows per grid step. 1024 rows keeps pass 2's
# nine double-buffered windows (g, p bf16; two moments and the master
# f32, in and out) near 8 MB of VMEM and GPT-medium's largest bucket
# (75.5M elements) at 576 grid steps.
_LANES = 128
_BLOCK_ROWS = 1024


def _scan_group_order(named_leaves):
    """Reorder leaves so same-role leaves across a layer stack sit
    ADJACENTLY in layer order: "h.0.qkv", "h.1.qkv", ... become one
    contiguous region. This is what lets a scan-over-layers model's
    per-step `jnp.stack([h.0.qkv, h.1.qkv, ...])` fold into a FREE
    reshape of one contiguous slice (XLA folds a concat of adjacent
    ascending slices) instead of a gather/copy of every block weight —
    the flat layout turns the scan path's stacking cost into zero.
    Grouping key: the leaf name with its last integer path component
    wildcarded, plus shape+dtype (stacking requires homogeneity)."""
    groups = {}
    entries = []
    for pos, (name, shape, dtype) in enumerate(named_leaves):
        parts = str(name).split(".")
        idx = 0
        gparts = parts
        for j in range(len(parts) - 1, -1, -1):
            if parts[j].isdigit():
                idx = int(parts[j])
                gparts = parts[:j] + ["*"] + parts[j + 1:]
                break
        gkey = (".".join(gparts), tuple(shape), str(jnp.dtype(dtype)))
        if gkey not in groups:
            groups[gkey] = len(groups)
        entries.append((groups[gkey], idx, pos, (name, shape, dtype)))
    entries.sort(key=lambda t: (t[0], t[1], t[2]))
    return [(e[0], e[3]) for e in entries]


class LeafStateView(collections.abc.Mapping):
    """{leaf name: its optimizer state}, each leaf sliced out of the flat
    store when it is read and not kept: `{k: s["master"] for k, s in
    view.items()}` holds one copy of the masters, not of the moments too.
    Read-only, and NOT a dict: `dict(view)` is the plain dict
    FusedEpilogue.state_view gives (and holds every leaf at once). As a
    pytree it is a node of its own around that dict: jax.tree.leaves and
    a one-tree jax.tree.map see the dict's leaves (the map returns the
    plain dict), but its treedef is not a dict's, so to map it against a
    dict tree take `dict(view)` first."""

    def __init__(self, epilogue, opt_store):
        self._epilogue, self._store = epilogue, opt_store
        self._names = [leaf.name for _, leaf in epilogue.layout.leaf_order]

    def __getitem__(self, name):
        if name not in self._epilogue.layout._by_name:
            raise KeyError(name)
        return self._epilogue.leaf_state(self._store, name)

    def __iter__(self):
        return iter(self._names)

    def __len__(self):
        return len(self._names)


jax.tree_util.register_pytree_node(
    LeafStateView, lambda view: ((dict(view),), None),
    lambda _, children: children[0])


class _Leaf:
    """One flat slice of a bucket: name + shape + [start, start+size)."""
    __slots__ = ("name", "shape", "size", "start", "index")

    def __init__(self, name, shape, size, start, index):
        self.name = name
        self.shape = tuple(shape)
        self.size = size
        self.start = start          # element offset into the flat bucket
        self.index = index          # row in the per-leaf metadata arrays


class _Bucket:
    """One (dtype, scan-group run) flat buffer: the run's members (same
    role across the layer stack, same metadata) pack back-to-back in
    layer order so a stacked view is one contiguous — free — reshape.
    Exact-sized; the Pallas drivers pad to whole lane rows
    transiently."""
    __slots__ = ("dtype", "leaves", "total", "cursor")

    def __init__(self, dtype):
        self.dtype = dtype
        self.leaves = []
        self.total = 0
        self.cursor = 0


class BucketLayout:
    """Static description of the dtype-bucketed flat layout for one
    parameter tree, plus the per-leaf metadata tables. Built once at
    TrainStep construction; everything here is host-side numpy, nothing
    traced."""

    def __init__(self, named_leaves, meta=None):
        """named_leaves: ordered [(name, shape, dtype)]. meta: optional
        {name: {"need_clip": bool, "decay": bool, "lr_scale": float,
        "norm_weight": float}} — missing names/keys default to
        (True, True, 1.0, 1.0), which reproduces the tree path."""
        meta = meta or {}
        # ONE bucket per (dtype, scan-group run, metadata class): the
        # run's members (same role across the layer stack) pack densely
        # in layer order, so the scan path's per-step jnp.stack of
        # block weights folds onto the buffer (free view) and — the
        # mirror image — the stacked gradient the scan's backward emits
        # IS the run's gradient buffer, no concat/pack traffic at all.
        # Still dtype-bucketed (a run is dtype-homogeneous); a run is
        # the contiguity unit the multi-tensor kernels sweep.
        self.buckets = {}           # "dtype#run" -> _Bucket
        self.leaf_order = []        # (bucket_key, _Leaf) in layout order
        self._by_name = {}
        flags, lr_scale, norm_w = [], [], []
        prev = None
        for gid, (name, shape, dtype) in _scan_group_order(named_leaves):
            dt = jnp.dtype(dtype)
            size = int(np.prod(shape)) if shape else 1
            m = meta.get(name, {})
            mtup = (
                (FLAG_NEED_CLIP if m.get("need_clip", True) else 0)
                | (FLAG_DECAY if m.get("decay", True) else 0),
                float(m.get("lr_scale", 1.0)),
                float(m.get("norm_weight", 1.0)))
            if prev != (gid, mtup, str(dt)):
                key = f"{dt}#{len(self.buckets)}"
                b = self.buckets[key] = _Bucket(dt)
            prev = (gid, mtup, str(dt))
            leaf = _Leaf(name, shape, size, b.cursor, len(flags))
            b.cursor += size
            b.leaves.append(leaf)
            self.leaf_order.append((key, leaf))
            self._by_name[name] = (key, leaf)
            flags.append(mtup[0])
            lr_scale.append(mtup[1])
            norm_w.append(mtup[2])
        self.leaf_flags = np.asarray(flags, np.int32)
        self.leaf_lr_scale = np.asarray(lr_scale, np.float32)
        self.leaf_norm_weight = np.asarray(norm_w, np.float32)
        for b in self.buckets.values():
            # stores are EXACT-sized (padding would ride every store
            # traversal); the Pallas drivers pad to whole lane rows
            # transiently at the kernel boundary
            b.total = b.cursor
        self.n_leaves = len(flags)
        # unpack with a custom VJP: the cotangent of the flat buffer is
        # ONE concatenate of leaf cotangents per bucket, not the pad+add
        # chain jax's slice transpose would emit per leaf
        self._unpack = jax.custom_vjp(self._unpack_impl)
        self._unpack.defvjp(
            lambda store: (self._unpack_impl(store), None),
            lambda _, cts: (self.pack(cts),))

    def bucket_meta(self, key):
        """(flags, lr_scale, norm_weight) of one bucket — uniform over
        its leaves by construction, so both the kernels and the direct
        path fold it in as python-static decisions (exactly how the
        tree path decides per leaf): no per-row metadata array in the
        fused expression, which XLA:CPU's in-place analysis under
        donation and the chip's SMEM budget both want."""
        li = self.buckets[key].leaves[0].index
        return (int(self.leaf_flags[li]), float(self.leaf_lr_scale[li]),
                float(self.leaf_norm_weight[li]))

    # -- pack / unpack ---------------------------------------------------
    # Buckets are stored 1-D [total]. This is load-bearing for honest
    # cost accounting, not style: a [n_chunks, chunk] store would make
    # every unpack slice start with a flattening bitcast, and XLA's
    # HloCostAnalysis cannot see slice utilization through that bitcast
    # — every consumer fusion of a 512-byte bias would be charged the
    # whole megabuffer. The kernels reshape to [rows, 128] at their
    # call boundary, where the whole buffer is genuinely read.
    def bucket_shape(self, key):
        b = self.buckets[key]
        return (b.total,)

    def pack(self, tree, dtype_map=None, keys=None):
        """Tree {name: array} -> {bucket_key: [total]}.
        dtype_map optionally overrides the storage dtype per bucket key
        (moment/master buffers share the param layout at another
        dtype); keys restricts packing to a subset of buckets (master
        buffers exist only for low-precision buckets)."""
        out = {}
        for key, b in self.buckets.items():
            if keys is not None and key not in keys:
                continue
            dt = (dtype_map or {}).get(key, b.dtype)
            vals = [jnp.asarray(tree[leaf.name]).astype(dt)
                    for leaf in b.leaves]
            if len(vals) == 1:
                flat = vals[0].reshape(-1)
            elif all(v.shape == vals[0].shape for v in vals):
                # a scan-group run: stack of its members — when the
                # members are the per-layer slices of a scan's stacked
                # gradient, XLA folds this straight back onto that
                # buffer and the "pack" costs nothing
                flat = jnp.stack(vals).reshape(-1)
            else:
                flat = jnp.concatenate([v.reshape(-1) for v in vals])
            out[key] = flat
        return out

    def _unpack_impl(self, store):
        out = {}
        for key, b in self.buckets.items():
            flat = store[key]
            for leaf in b.leaves:
                out[leaf.name] = jax.lax.slice(
                    flat, (leaf.start,),
                    (leaf.start + leaf.size,)).reshape(leaf.shape)
        return out

    def unpack(self, store):
        """{bucket_key: buffer} -> {name: array} views (differentiable;
        the VJP packs cotangents with one concat per bucket)."""
        return self._unpack(store)

    def leaf_view(self, store, name, dtype=None):
        """One leaf's values out of a store (host/eager inspection)."""
        key, leaf = self._by_name[name]
        flat = store[key]
        v = jax.lax.slice(flat, (leaf.start,),
                          (leaf.start + leaf.size,)).reshape(leaf.shape)
        return v.astype(dtype) if dtype is not None else v


# ---------------------------------------------------------------------------
# the shared per-bucket math — ONE definition executed by both the Pallas
# kernels (TPU / interpret, on [rows, 128] blocks) and the direct off-TPU
# path (on the whole 1-D bucket); metadata is python-static per bucket
# ---------------------------------------------------------------------------

def _pass1_block(g, inv, write_u):
    """Unscale + L2 sum + non-finite sweep of one block (any shape).
    Returns (u or None, sumsq, nonfinite_flag) — the caller applies the
    bucket's static norm weight."""
    g32 = g.astype(_F32)
    # found_inf sweeps the RAW grads (pre-unscale), exactly like the
    # tree path's GradScaler.jit_unscale_and_update
    nonfin = jnp.max(jnp.where(jnp.isfinite(g32), jnp.float32(0.0),
                               jnp.float32(1.0)))
    if write_u:
        u = (g32 * inv).astype(g.dtype)
        u32 = u.astype(_F32)
    else:
        u, u32 = None, g32
    return u, jnp.sum(u32 * u32), nonfin


def _norm_weight(flags, nw):
    """A bucket's static weight in the global grad norm: its
    replication weight, or 0 when its leaves are outside the clip."""
    return nw if (flags & FLAG_NEED_CLIP) else 0.0


def _update_core(kind, hp, w, g32, ms32, lr, lr_t):
    """The optimizer recurrence itself. Returns (np32, new_moments32)."""
    if kind in ("adam", "adamw"):
        # (1 - beta) precomputed in f64 then rounded, exactly like the
        # tree path's weak-typed python-float literals — bit parity
        b1 = jnp.float32(hp["beta1"])
        b2 = jnp.float32(hp["beta2"])
        omb1 = jnp.float32(1.0 - hp["beta1"])
        omb2 = jnp.float32(1.0 - hp["beta2"])
        eps = jnp.float32(hp["eps"])
        m = b1 * ms32[0] + omb1 * g32
        v = b2 * ms32[1] + omb2 * g32 * g32
        return w - lr_t * m / (jnp.sqrt(v) + eps), [m, v]
    if kind == "momentum":
        mom = jnp.float32(hp["momentum"])
        vel = mom * ms32[0] + g32
        if hp.get("nesterov"):
            return w - lr * (g32 + mom * vel), [vel]
        return w - lr * vel, [vel]
    return w - lr * g32, []  # sgd


def _pass2_block(g, p, ms, mw, meta, sc, *, kind, hp, global_clip,
                 clip_value, with_stats):
    """Clip + decoupled decay + moment update + master downcast +
    found_inf skip of one block (any shape) of one bucket, `meta` its
    static (flags, lr_scale, norm_weight). `sc` = [lr, lr_t, found_inf,
    clip_factor] (lr_t is the bias-corrected Adam rate, == lr for
    SGD/Momentum). Returns (new_p, new_moments, new_master,
    param_sumsq, update_sumsq)."""
    flags, lrsc, nw = meta
    found = sc[2] > jnp.float32(0.0)
    clip_f = sc[3]
    lr = sc[0] if lrsc == 1.0 else sc[0] * jnp.float32(lrsc)
    lr_t = sc[1] if lrsc == 1.0 else sc[1] * jnp.float32(lrsc)

    # per-leaf need_clip gates BOTH the factor application here and
    # the norm contribution in pass 1 (same mask, same semantics as
    # nn.clip.clip_grads_tree with a need_clip mask)
    if global_clip and (flags & FLAG_NEED_CLIP):
        g = (g.astype(_F32) * clip_f).astype(g.dtype)
    if clip_value is not None:
        g = jnp.clip(g, jnp.asarray(clip_value[0], g.dtype),
                     jnp.asarray(clip_value[1], g.dtype))
    g32 = g.astype(_F32)
    p32 = p.astype(_F32)
    w = mw if mw is not None else p32
    wd = hp.get("wd", 0.0)
    if wd and (flags & FLAG_DECAY):
        w = w * (jnp.float32(1.0) - lr * jnp.float32(wd))
    np32, new_m32 = _update_core(kind, hp, w, g32,
                                 [m.astype(_F32) for m in ms], lr, lr_t)
    # downcast tails (master keeps f32; the working param is its
    # rounded shadow), then the branchless found_inf skip
    npw = np32.astype(p.dtype)
    new_p = jnp.where(found, p, npw)
    new_ms = [jnp.where(found, old, nm.astype(old.dtype))
              for old, nm in zip(ms, new_m32)]
    new_mw = jnp.where(found, mw, np32) if mw is not None else None
    sp = su = None
    if with_stats:
        # norm_weight de-duplicates mesh-replicated leaves in the psum'd
        # health sums, exactly like pass 1's grad-norm partials
        sel32 = new_p.astype(_F32)
        sp = jnp.float32(nw) * jnp.sum(sel32 * sel32)
        su = jnp.float32(nw) * jnp.sum((sel32 - p32) * (sel32 - p32))
    return new_p, new_ms, new_mw, sp, su


# ---------------------------------------------------------------------------
# Pallas kernel wrappers over the shared math
# ---------------------------------------------------------------------------

def _as_rows(x):
    """A 1-D bucket as [rows, 128] (tail-padded with zeros to a whole
    lane row when its size is not a multiple of 128 — stores are
    exact-sized; only the kernel boundary sees the padded view)."""
    n_rows = -(-x.shape[0] // _LANES)
    pad = n_rows * _LANES - x.shape[0]
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
    return x.reshape(n_rows, _LANES)


def _block_rows(n_rows):
    """Rows per grid step: the whole bucket when it is at most one
    block (a full dimension always tiles), else _BLOCK_ROWS (a multiple
    of every dtype's sublane tile). The SAME choice in interpret mode
    and on the chip, so tier-1 runs the blocking that ships."""
    return n_rows if n_rows <= _BLOCK_ROWS else _BLOCK_ROWS


def _tail_masked(refs, rows, n_rows):
    """Load blocks; where the grid's last block hangs over the bucket
    (n_rows % rows), zero its out-of-range rows — their loads are
    unspecified and must not reach the reductions (their stores are
    dropped by the pipeline)."""
    vals = [r[...] for r in refs]
    if n_rows % rows == 0:
        return vals
    row = pl.program_id(0) * rows + jax.lax.broadcasted_iota(
        jnp.int32, (rows, _LANES), 0)
    keep = row < n_rows
    return [jnp.where(keep, v, jnp.zeros((), v.dtype)) for v in vals]


def _smem(shape):
    """A whole small operand in scalar memory. The index map is spelt
    out in int32: under x64 the default one returns i64 zeros, which
    Mosaic cannot legalize."""
    return pl.BlockSpec(shape, lambda i: (I0,) * len(shape),
                        memory_space=pltpu.SMEM)


_ACC_SHAPE = jax.ShapeDtypeStruct((1, 1), _F32)
_ACC = _smem((1, 1))


def _pass1_kernel(inv_ref, g_ref, *rest, write_u, weight, rows, n_rows):
    if write_u:
        u_ref, ss_ref, fi_ref = rest
    else:
        ss_ref, fi_ref = rest

    @pl.when(pl.program_id(0) == 0)
    def _init():
        ss_ref[0, 0] = jnp.float32(0.0)
        fi_ref[0, 0] = jnp.float32(0.0)

    g, = _tail_masked([g_ref], rows, n_rows)
    u, ss, nonfin = _pass1_block(g, inv_ref[0], write_u)
    if write_u:
        u_ref[...] = u
    fi_ref[0, 0] = jnp.maximum(fi_ref[0, 0], nonfin)
    if weight:
        ss_ref[0, 0] += jnp.float32(weight) * ss


def _pass2_kernel(sc_ref, *refs, n_moments, has_master, with_stats,
                  rows, n_rows, **math_kw):
    n_in = 2 + n_moments + (1 if has_master else 0)
    ins, outs = refs[:n_in], refs[n_in:]
    if with_stats:
        sp_ref, su_ref = outs[-2:]

        @pl.when(pl.program_id(0) == 0)
        def _init_stats():
            sp_ref[0, 0] = jnp.float32(0.0)
            su_ref[0, 0] = jnp.float32(0.0)

    vals = _tail_masked(ins, rows, n_rows)
    new_p, new_ms, new_mw, sp, su = _pass2_block(
        vals[0], vals[1], vals[2:2 + n_moments],
        vals[2 + n_moments] if has_master else None,
        sc=sc_ref, with_stats=with_stats, **math_kw)
    outs[0][...] = new_p
    for mo, nm in zip(outs[1:1 + n_moments], new_ms):
        mo[...] = nm
    if has_master:
        outs[1 + n_moments][...] = new_mw
    if with_stats:
        sp_ref[0, 0] += sp
        su_ref[0, 0] += su


# ---------------------------------------------------------------------------
# per-bucket pass drivers
# ---------------------------------------------------------------------------

def _run_pass1(layout, grads, inv_scale, write_u, mode):
    """Per-bucket pass 1. Returns (unscaled store or None, sumsq f32
    scalar, found_inf f32 scalar). sumsq accumulates bucket-major then
    block-major — the multi-tensor analogue of the tree path's
    leaf-major sum (equal within reduction-order ulps)."""
    sumsq = jnp.zeros((), _F32)
    found = jnp.zeros((), _F32)
    out_u = {} if write_u else None
    inv = jnp.asarray(inv_scale, _F32)
    for key, b in layout.buckets.items():
        flags, _lrsc, nw = layout.bucket_meta(key)
        weight = _norm_weight(flags, nw)
        if mode == "direct":
            u, ss, fi = _pass1_block(grads[key], inv, write_u)
            ss = jnp.float32(weight) * ss if weight else jnp.float32(0.0)
        else:
            # Pallas path: buckets live 1-D and exact-sized; the
            # [rows, 128] view exists only at the kernel boundary (a
            # full read through a reshape is charged exactly)
            g = _as_rows(grads[key])
            n_rows = g.shape[0]
            rows = _block_rows(n_rows)
            blk = pl.BlockSpec((rows, _LANES), lambda i: (i, I0))
            res = pl.pallas_call(
                functools.partial(_pass1_kernel, write_u=write_u,
                                  weight=weight, rows=rows,
                                  n_rows=n_rows),
                grid=(pl.cdiv(n_rows, rows),),
                in_specs=[_smem((1,)), blk],
                out_specs=([blk] if write_u else []) + [_ACC, _ACC],
                out_shape=([jax.ShapeDtypeStruct(g.shape, g.dtype)]
                           if write_u else []) + [_ACC_SHAPE, _ACC_SHAPE],
                name="fused_update_pass1",
                interpret=mode == "interpret",
            )(inv.reshape(1), g)
            u = res[0].reshape(-1)[:b.total] if write_u else None
            ss, fi = res[-2][0, 0], res[-1][0, 0]
        if write_u:
            out_u[key] = u
        sumsq = sumsq + ss
        found = jnp.maximum(found, fi)
    return out_u, sumsq, found


def _run_pass2(layout, spec, grads, params, moments, masters, scalars,
               with_stats, global_clip, clip_value, mode):
    """Per-bucket pass 2. Returns (new_params, new_moments, new_masters,
    stats) — stats is (param_sumsq, update_sumsq) f32 or None."""
    n_moments = spec["n_moments"]
    new_p, new_m, new_mw = {}, [dict() for _ in range(n_moments)], {}
    p_sq = jnp.zeros((), _F32)
    u_sq = jnp.zeros((), _F32)
    for key, b in layout.buckets.items():
        has_master = key in (masters or {})
        ops = [grads[key], params[key]] + [m[key] for m in moments] \
            + ([masters[key]] if has_master else [])
        math_kw = dict(meta=layout.bucket_meta(key), kind=spec["kind"],
                       hp=spec, global_clip=global_clip,
                       clip_value=clip_value)
        if mode == "direct":
            po, mos, mwo, sp, su = _pass2_block(
                ops[0], ops[1], ops[2:2 + n_moments],
                ops[2 + n_moments] if has_master else None,
                sc=scalars, with_stats=with_stats, **math_kw)
        else:
            ops = [_as_rows(a) for a in ops]
            n_rows = ops[0].shape[0]
            rows = _block_rows(n_rows)
            blk = pl.BlockSpec((rows, _LANES), lambda i: (i, I0))
            # outputs mirror inputs 1.. (param, moments, master) and
            # alias them in place; operand 0 is the scalars and grads
            # (operand 1) are NOT aliased (pass 1 may still own that
            # buffer)
            n_alias = len(ops) - 1
            res = pl.pallas_call(
                functools.partial(
                    _pass2_kernel, n_moments=n_moments,
                    has_master=has_master, with_stats=with_stats,
                    rows=rows, n_rows=n_rows, **math_kw),
                grid=(pl.cdiv(n_rows, rows),),
                in_specs=[_smem((4,))] + [blk] * len(ops),
                out_specs=[blk] * n_alias
                + ([_ACC, _ACC] if with_stats else []),
                out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype)
                           for a in ops[1:]]
                + ([_ACC_SHAPE, _ACC_SHAPE] if with_stats else []),
                input_output_aliases={2 + j: j for j in range(n_alias)},
                name="fused_update_pass2",
                interpret=mode == "interpret",
            )(scalars, *ops)
            flat = [r.reshape(-1)[:b.total] for r in res[:n_alias]]
            po, mos = flat[0], flat[1:1 + n_moments]
            mwo = flat[1 + n_moments] if has_master else None
            sp = res[-2][0, 0] if with_stats else None
            su = res[-1][0, 0] if with_stats else None
        new_p[key] = po
        for j in range(n_moments):
            new_m[j][key] = mos[j]
        if has_master:
            new_mw[key] = mwo
        if with_stats:
            p_sq = p_sq + sp
            u_sq = u_sq + su
    stats = (p_sq, u_sq) if with_stats else None
    return new_p, new_m, new_mw, stats


# ---------------------------------------------------------------------------
# the epilogue driver TrainStep/HybridTrainStep call under the trace
# ---------------------------------------------------------------------------

class FusedEpilogue:
    """Owns one BucketLayout + one optimizer fused-spec and drives the
    two passes. Pure w.r.t. its traced inputs — call under jit."""

    def __init__(self, layout, spec, interpret=None):
        self.layout = layout
        self.spec = dict(spec)
        if jax.default_backend() == "tpu":
            self.mode = "pallas"
        elif (interpret if interpret is not None
              else os.environ.get("PADDLE_TPU_FUSED_INTERPRET") == "1"):
            self.mode = "interpret"
        else:
            # off-TPU default: the same _math bodies run directly on
            # the flat buffers — XLA:CPU fuses them like any
            # elementwise graph (Pallas interpret mode would execute
            # the same math through grid emulation machinery that the
            # CPU backend cannot fuse, inflating bytes-accessed ~2x)
            self.mode = "direct"

    # -- state construction (host side, once) ----------------------------
    def init_stores(self, params_tree, multi_precision):
        """(param_store, opt_store). opt_store = {"moments": tuple of
        per-bucket dicts (state dtype), "masters": {bucket: f32}} —
        masters only for non-f32 buckets under multi_precision."""
        lay = self.layout
        p_store = lay.pack(params_tree)
        sdt = self.spec.get("state_dtype") or jnp.float32
        moments = tuple(
            {key: jnp.zeros(lay.bucket_shape(key), sdt)
             for key in lay.buckets}
            for _ in range(self.spec["n_moments"]))
        masters = {}
        if multi_precision:
            for key, b in lay.buckets.items():
                if b.dtype != jnp.float32:
                    masters[key] = p_store[key].astype(jnp.float32)
        return p_store, {"moments": moments, "masters": masters}

    def pack_opt_tree(self, state_tree):
        """Per-leaf optimizer-state tree (init_leaf_state layout) ->
        flat opt store — the inverse of state_view. HybridTrainStep
        packs its TREE-persistent (per-leaf-sharded) state into local
        buckets each step inside its shard_map epilogue."""
        lay = self.layout
        sdt = self.spec.get("state_dtype") or jnp.float32

        def inner(name):
            s = state_tree[name]
            return s["state"] if isinstance(s, dict) and "master" in s \
                else s

        moments = tuple(
            lay.pack({leaf.name: inner(leaf.name)[j]
                      for _, leaf in lay.leaf_order},
                     dtype_map={k: sdt for k in lay.buckets})
            for j in range(self.spec["n_moments"]))
        master_keys = {key for key, leaf in lay.leaf_order
                       if isinstance(state_tree[leaf.name], dict)}
        masters = lay.pack(
            {leaf.name: state_tree[leaf.name]["master"]
             for key, leaf in lay.leaf_order if key in master_keys},
            dtype_map={k: jnp.float32 for k in lay.buckets},
            keys=master_keys) if master_keys else {}
        return {"moments": moments, "masters": masters}

    def leaf_state(self, opt_store, name):
        """One leaf's optimizer state out of the flat store: tuple(moments)
        or {"master": f32, "state": tuple}, as Optimizer.init_leaf_state
        lays it out."""
        lay = self.layout
        moments = tuple(lay.leaf_view(m, name) for m in opt_store["moments"])
        if lay._by_name[name][0] in opt_store["masters"]:
            return {"master": lay.leaf_view(opt_store["masters"], name),
                    "state": moments}
        return moments

    def state_view(self, opt_store):
        """Per-leaf optimizer-state VIEW of the flat store — {name:
        tuple(moments) | {"master": f32, "state": tuple}} — mirroring
        Optimizer.init_leaf_state's tree layout exactly, so state_dict
        round-trips and tests see the same structure on both paths."""
        return {leaf.name: self.leaf_state(opt_store, leaf.name)
                for _, leaf in self.layout.leaf_order}

    def lazy_state_view(self, opt_store):
        """state_view as a Mapping that slices a leaf out only when it is
        asked for (every view of a flat buffer is a copy: all of them at
        once are a second optimizer state on the device)."""
        return LeafStateView(self, opt_store)

    def bytes_per_step(self, scaling, need_norm, master_keys=()):
        """Analytic HBM traffic of the epilogue passes (the
        `epilogue_bytes` step-record field): pass 1 reads grads (and
        writes the unscaled buffer when a scaler rides along), pass 2
        reads grads+params+moments+masters and writes
        params+moments+masters."""
        total = 0
        sdt = self.spec.get("state_dtype") or jnp.float32
        s_size = jnp.dtype(sdt).itemsize
        for key, b in self.layout.buckets.items():
            n = b.total
            it = b.dtype.itemsize
            if scaling:
                total += n * it * 2          # pass 1: read g, write u
            elif need_norm:
                total += n * it              # pass 1: read g
            total += n * it * 3              # pass 2: read g+p, write p
            total += n * s_size * 2 * self.spec["n_moments"]
            if key in master_keys:
                total += n * 4 * 2           # master read+write
        return int(total)

    # -- the traced epilogue --------------------------------------------
    def finish(self, grads, p_store, opt_store, lr, step, scaler=None,
               scaler_state=None, clip=None, with_stats=False):
        """From bucketed grads to the updated bucketed carry.

        Returns (new_p_store, new_opt_store, new_scaler_state, aux) with
        aux = {"grad_norm", "found_inf"} (+ "param_sumsq",
        "update_sumsq" when with_stats) — grad_norm is the ONE global
        norm shared by clip, found_inf handling, and the health vector.
        Hybrid sets psum axes (set_psum_axes) so the partial sums and
        found flag reduce across shards."""
        scaling = scaler is not None and scaler.is_enable()
        global_clip, clip_value, clip_norm = _resolve_clip(clip)
        need_norm = bool(global_clip) or with_stats

        found = jnp.zeros((), _F32)
        gn = jnp.zeros((), _F32)
        u = grads
        if scaling or need_norm:
            inv = (jnp.float32(1.0) / scaler_state["scale"]) if scaling \
                else jnp.float32(1.0)
            u_out, sumsq, found = _run_pass1(
                self.layout, grads, inv, write_u=scaling,
                mode=self.mode)
            if scaling:
                u = u_out
            sumsq = self._psum(sumsq)
            found = self._pmax(found)
            gn = jnp.sqrt(sumsq)
        new_scaler_state = scaler_state
        found_b = None
        if scaling:
            found_b = found > 0
            new_scaler_state = scaler.jit_update_scale_state(
                scaler_state, found_b)
        clip_f = jnp.float32(1.0)
        if global_clip:
            clip_f = jnp.minimum(
                jnp.float32(clip_norm) / jnp.maximum(gn,
                                                     jnp.float32(1e-12)),
                jnp.float32(1.0))
        # the rate math runs on lr/step exactly as the tree path's
        # _update would see them (weak-type promotion included); the
        # single round to f32 happens here, where the tree path rounds
        # at the multiply into the f32 update
        lr_t = self._rate(lr, step)
        # the found_inf SKIP only exists under a live GradScaler (tree
        # parity: found_inf=None otherwise, and a NaN grad updates)
        skip = found if scaling else jnp.zeros((), _F32)
        scalars = jnp.stack([jnp.asarray(lr).astype(_F32),
                             jnp.asarray(lr_t).astype(_F32),
                             skip, clip_f])
        new_p, new_m, new_mw, stats = _run_pass2(
            self.layout, self.spec, u, p_store,
            list(opt_store["moments"]), opt_store["masters"], scalars,
            with_stats, global_clip, clip_value, self.mode)
        aux = {"grad_norm": gn, "found_inf": found_b}
        if scaling or need_norm:
            # pass 1's non-finite sweep covers EVERY leaf (the clip
            # mask only gates the norm) — the health vector's found_inf
            # signal, exact even for need_clip=False leaves whose norm
            # contribution is masked out
            aux["nonfinite"] = found > 0
        if with_stats:
            aux["param_sumsq"] = self._psum(stats[0])
            aux["update_sumsq"] = self._psum(stats[1])
        return new_p, {"moments": tuple(new_m), "masters": new_mw}, \
            new_scaler_state, aux

    def _rate(self, lr, step):
        """The per-element rate pass 2 applies: bias-corrected for
        Adam/AdamW (the same scalar expression the tree path's _update
        evaluates, on the same lr/step values), plain lr otherwise."""
        if self.spec["kind"] in ("adam", "adamw"):
            b1 = self.spec["beta1"]
            b2 = self.spec["beta2"]
            return lr * (1 - b2 ** step) ** 0.5 / (1 - b1 ** step)
        return lr

    # hybrid: reduce partial sums / found across mesh axes. The partial
    # sums psum (replicated leaves pre-weighted by 1/replication via
    # norm_weight metadata, so the psum does not double-count them); the
    # found flag pmaxes (any shard's hit is everyone's hit).
    _psum_axes = None

    def set_psum_axes(self, axes):
        self._psum_axes = tuple(axes) if axes else None

    def _psum(self, v):
        return jax.lax.psum(v, self._psum_axes) if self._psum_axes else v

    def _pmax(self, v):
        return jax.lax.pmax(v, self._psum_axes) if self._psum_axes else v


def _resolve_clip(clip):
    """(global_clip, clip_value, clip_norm) for a nn.clip config the
    fused path supports; raises on an unsupported one (the caller's
    eligibility check is the real gate — this is the backstop)."""
    if clip is None:
        return False, None, None
    from ...nn.clip import (ClipGradByGlobalNorm, ClipGradByValue,
                            ClipGradByNorm)
    if isinstance(clip, ClipGradByGlobalNorm):
        return True, None, float(clip.clip_norm)
    if isinstance(clip, ClipGradByValue):
        return False, (float(clip.min), float(clip.max)), None
    if isinstance(clip, ClipGradByNorm):
        raise NotImplementedError(
            "fused epilogue does not support per-leaf ClipGradByNorm; "
            "use the tree path (PADDLE_TPU_FUSED_UPDATE=0)")
    return False, None, None
