"""The comparison that decides `correct`, and the numbers it compares.

Training: the program's first three steps against the plain reference's
(float32, matmuls at the highest precision) — each step's loss, the
first gradient's norm by the worst leaf, the parameters' change after
the three steps by the worst leaf. A norm gap is |program's norm -
reference's norm| over the larger of the reference's norm of that leaf
and of the median leaf (some gradients are all but zero).

Serving: the widest gap by which a served token's reference logit lies
below the reference's best logit at that position, and the mean of that
gap over the sample's tokens (a widest gap swings by its nature; the mean
is the steadier reading of the same thing).

Each number has a limit of its own in the cell's file ("correct":
{"limits": {...}}); PERF.md gives the readings each was set from."""
import json
import statistics
import sys

# leaves whose reference gradient is under this share of the median
# leaf's move under Adam by round-off alone: left out of the change
QUIET_LEAF = 1e-3


def worst_leaf_gap(prog, ref, leaves=None):
    """max over leaves of |prog - ref| / max(ref, median ref), and the
    leaf that gives it."""
    med = statistics.median(ref.values())
    worst, at = 0.0, None
    for k in (leaves if leaves is not None else ref):
        gap = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        if gap > worst:
            worst, at = gap, k
    return worst, at


def train_numbers(prog, ref):
    """prog/ref: {"losses": [l1, l2, l3], "grad_norms": {leaf: norm},
    "change_norms": {leaf: norm}}. Returns ({number: value}, notes)."""
    out, notes = {}, {}
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"]), 1):
        out[f"loss{i}_gap"] = abs(a - b) / abs(b)
    out["grad_norm_gap"], notes["grad_norm_leaf"] = worst_leaf_gap(
        prog["grad_norms"], ref["grad_norms"])
    med = statistics.median(ref["grad_norms"].values())
    loud = [k for k, g in ref["grad_norms"].items() if g >= QUIET_LEAF * med]
    notes["quiet_leaves"] = len(ref["grad_norms"]) - len(loud)
    out["change_norm_gap"], notes["change_norm_leaf"] = worst_leaf_gap(
        prog["change_norms"], ref["change_norms"], loud)
    return out, notes


def serve_numbers(gaps):
    """gaps: per served token, reference's best logit minus the
    reference's logit of the served token (>= 0)."""
    return {"max_logit_gap": float(max(gaps)),
            "mean_logit_gap": float(sum(gaps) / len(gaps))}


def judge(numbers, limits):
    """(correct, [(name, value, limit)]): every compared number at or
    under its limit; the numbers compared are those the cell's file gives
    a limit."""
    rows = []
    for name in sorted(limits):
        rows.append((name, float(numbers[name]), float(limits[name])))
    ok = all(v <= lim and v == v for _, v, lim in rows)
    return ok, rows


def report(rows, correct):
    """Each number compared beside its limit, as the last lines on
    standard error; returns the same as the result line's last key."""
    for name, v, lim in rows:
        print(f"compared {name} {v:.6g} limit {lim:.6g} "
              f"{'ok' if v <= lim else 'OVER'}", file=sys.stderr)
    print(f"correct {json.dumps(bool(correct))}", file=sys.stderr,
          flush=True)
    return {name: {"value": v, "limit": lim} for name, v, lim in rows}
