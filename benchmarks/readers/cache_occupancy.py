"""Pages or state slots in use over those allocated: the mean of the
load generator's readings of the cache object, one per turn of its loop,
at most every 10 ms."""


def read(ctx):
    occ = ctx["window"].get("occupancy")
    return 100.0 * sum(occ) / len(occ) if occ else None
