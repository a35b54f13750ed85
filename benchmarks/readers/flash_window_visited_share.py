"""Of the tiles a causal walk of the flash kernels visits, the share
their walk of the window's band visits, in %: the mean over the traced
calls that had a window, as the program observed it from the shapes
(profiler.monitor `flash.window.visited_share`, from ops/pallas/
attention_core.py window_visited_share). 100 where a window is masked
over a full causal walk; None where the program observes no such call."""


def read(ctx):
    try:
        from paddle_tpu.profiler import monitor
    except ImportError:
        return None
    m = monitor.get_metric("flash.window.visited_share")
    return m.avg if m is not None and m.count else None
