"""The expert layer's routing load, from the program's own counters
(profiler.monitor, fed once a step from the in-graph vector each expert
layer records): sums over layers and steps since the process started.

what = "load_max_over_mean": the largest group of a layer over the mean
group, averaged over layers and steps; "dropped": assignments to a held
expert that were not computed; "local_share": held assignments over all
assignments. None where the program has no such counters."""


def counter(name):
    try:
        from paddle_tpu.profiler import monitor
    except ImportError:
        return None
    m = monitor.get_metric(name)
    return None if m is None else float(m.snapshot())


def local_share():
    local, every = counter("moe.local_assignments"), \
        counter("moe.assignments")
    return local / every if local is not None and every else None


def read(ctx, what):
    if what == "dropped":
        return counter("moe.dropped")
    if what == "local_share":
        return local_share()
    local, biggest = counter("moe.local_assignments"), \
        counter("moe.expert_load_max")
    if not local or biggest is None:
        return None
    return biggest * ctx["config"]["n_routed_experts"] / local
