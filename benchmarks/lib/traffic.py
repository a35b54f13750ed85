"""The one traffic generator. A cell's file gives parameters under
"traffic"; this turns (parameters, seed) into requests. Nothing here
knows a cell's name.

Copied in idea from tools/load_harness.py generate_trace (seeded
Poisson arrivals with a burst window, lognormal clipped lengths), with
two changes the benchmark needs: every seed gets the SAME multiset of
lengths and gaps (open loop: in another order, so a seed moves the
interleaving, not the amount of work; closed loop: in the same order),
and a closed-loop mode gives each client its own request list.

Parameters (all under the cell file's "traffic"):
  loop            "open" | "closed"
  prompt          {"lo", "hi", "median", "sigma"}   lognormal, clipped
  output          {"lo", "hi", "median", "sigma"}
  max_total       optional cap on prompt + output (outputs are cut)
  open loop:      rate_rps, burst {"every_s", "length_s", "factor"},
                  horizon_s (requests are generated to cover it)
  closed loop:    clients, requests_per_client (the sizes one client
                  cycles through), requests_per_client_per_s (an upper
                  estimate, so that a client never runs out in a window)
"""
import numpy as np

# quantile grids are drawn once from this fixed stream, then permuted
# by the run's seed: same sizes in every run, another order
_BASE_SEED = 20240924


def _lengths(spec, n, base_rng):
    """n lengths whose multiset depends on `spec` and n only: the
    lognormal's quantiles at n evenly spaced probabilities, jittered by
    the fixed base stream, clipped."""
    q = (np.arange(n) + base_rng.uniform(0.2, 0.8, size=n)) / n
    # inverse normal CDF by sorting a large fixed normal sample
    z = np.sort(base_rng.standard_normal(65536))
    zq = z[np.clip((q * z.size).astype(int), 0, z.size - 1)]
    vals = np.exp(np.log(spec["median"]) + spec["sigma"] * zq)
    return np.clip(np.rint(vals), spec["lo"], spec["hi"]).astype(np.int64)


def _sizes(traffic, n, seed, reorder=True):
    """(prompt lengths, output lengths, the seed's stream). The order
    comes from the seed, or with reorder=False from the fixed stream."""
    base = np.random.RandomState(_BASE_SEED)
    prompts = _lengths(traffic["prompt"], n, base)
    outs = _lengths(traffic["output"], n, base)
    rng = np.random.RandomState(seed % (2 ** 32))
    order = rng if reorder else base
    prompts = prompts[order.permutation(n)]
    outs = outs[order.permutation(n)]
    cap = traffic.get("max_total")
    if cap:
        outs = np.maximum(np.minimum(outs, cap - prompts), 1)
    return prompts, outs, rng


def open_loop(traffic, vocab, seed):
    """[{"t", "prompt", "max_new"}] sorted by due time t (seconds from
    the window's start), covering traffic["horizon_s"]. Gaps are the
    exponential's quantiles permuted by the seed; inside each burst
    window the clock runs `factor` times faster."""
    rate, horizon = float(traffic["rate_rps"]), float(traffic["horizon_s"])
    burst = traffic.get("burst") or {"every_s": horizon * 2,
                                     "length_s": 0.0, "factor": 1.0}
    duty = burst["length_s"] / burst["every_s"]
    mean_rate = rate * (1 - duty + duty * burst["factor"])
    n = int(np.ceil(mean_rate * horizon * 1.05)) + 8
    prompts, outs, rng = _sizes(traffic, n, seed)
    base = np.random.RandomState(_BASE_SEED + 1)
    q = (np.arange(n) + base.uniform(0.2, 0.8, size=n)) / n
    gaps = -np.log1p(-q)[rng.permutation(n)] / rate   # unit-rate / rate
    out, t = [], 0.0
    for i in range(n):
        in_burst = (t % burst["every_s"]) < burst["length_s"]
        t += gaps[i] / (burst["factor"] if in_burst else 1.0)
        if t >= horizon:
            break
        out.append({"t": float(t),
                    "prompt": rng.randint(0, vocab, size=int(prompts[i])),
                    "max_new": int(outs[i])})
    return out


def closed_loop(traffic, vocab, seed, min_requests):
    """One request list per client. A client's sizes are the same
    `requests_per_client` prompt and output lengths in the same order for
    every seed, repeated until the list holds `min_requests`; the seed
    draws the token ids, fresh in every request. The order is fixed
    because a window holds only some tens of these requests: which of
    them the close cuts off would otherwise be the seed's doing, and
    completed tokens per second would swing by a request's worth."""
    c, k = int(traffic["clients"]), int(traffic["requests_per_client"])
    prompts, outs, rng = _sizes(traffic, c * k, seed, reorder=False)
    cycles = -(-int(min_requests) // k)
    clients = []
    for ci in range(c):
        sizes = list(zip(prompts[ci * k:(ci + 1) * k],
                         outs[ci * k:(ci + 1) * k])) * cycles
        clients.append([
            {"prompt": rng.randint(0, vocab, size=int(p)), "max_new": int(o)}
            for p, o in sizes])
    return clients
