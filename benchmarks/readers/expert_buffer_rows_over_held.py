"""How many rows the dropless expert layer's sorted buffer had for each
assignment to a held expert: `moe.buffer_rows` (the static row count of
the rung each layer call took) over `moe.local_assignments`, both summed
over layers and steps since the process started (profiler.monitor, fed
from the in-graph vector, see moe_counters). 1.0 would be a buffer with
no empty row; the worst case reads router experts / held experts (8.0 in
both expert-layer cells). None where the program has no such counter."""
from benchmarks.readers.moe_counters import counter


def read(ctx):
    rows, held = counter("moe.buffer_rows"), \
        counter("moe.local_assignments")
    return rows / held if rows is not None and held else None
