"""What each phase of a run holds on the chip: the result line's
`extra.memory_by_phase`, so that a configuration can be sized against
set-up, window and reference apart and not against one peak.

For each phase: the allocator's `bytes_in_use` at its start, the running
`peak_bytes_in_use` at its end (the process's high-water mark: it never
falls, so a phase's own peak shows only where it passes the earlier
phases'), the two at the points the phase marks ("marks": [label, in
use, peak]), and the compiler's `memory_analysis()` of the phase's
largest executable. The allocator does not count an executable's
temporaries (PR 21), so a phase needs about what it holds at its fullest
mark plus those."""
import jax

from . import program as P


def analysis(compiled):
    """{argument, output, alias, temp, total}_bytes of an executable by
    its own memory analysis; total = arguments + outputs + temporaries
    less what outputs alias (donated arguments)."""
    ma = compiled.memory_analysis()
    out = {f"{k}_bytes": int(getattr(ma, f"{k}_size_in_bytes", 0) or 0)
           for k in ("argument", "output", "alias", "temp")}
    out["total_bytes"] = (out["argument_bytes"] + out["output_bytes"]
                          + out["temp_bytes"] - out["alias_bytes"])
    return out


def compiled(fn, *args, phases=None, name=None, **jit_kw):
    """fn jitted and compiled for `args` (the persistent cache serves a
    warm run), noted as an executable of the current phase."""
    exe = jax.jit(fn, **jit_kw).lower(*args).compile()
    if phases is not None:
        phases.executable(name or fn.__name__, exe)
    return exe


class Phases:
    """One run's phases, in order: `out` is memory_by_phase."""

    def __init__(self, devs):
        self.devs = devs
        self.out = {}
        self.now = None

    def bytes_in_use(self):
        return P.memory_stat(self.devs, "bytes_in_use")

    def start(self, name):
        self.now = self.out[name] = {
            "bytes_in_use_at_start": self.bytes_in_use()}

    def end(self):
        self.now["peak_bytes_in_use_at_end"] = P.memory_stat(
            self.devs, "peak_bytes_in_use")

    def mark(self, label):
        """The allocator's bytes in use and running peak at a point of
        the phase, in order: where within it the peak is set."""
        self.now.setdefault("marks", []).append(
            [label, self.bytes_in_use(),
             P.memory_stat(self.devs, "peak_bytes_in_use")])

    def executable(self, name, exe):
        a = analysis(exe)
        largest = self.now.get("largest_executable")
        if largest is None or a["total_bytes"] > largest["total_bytes"]:
            self.now["largest_executable"] = {"name": name, **a}
        return a

    def readings(self, name, exe):
        """A correctness reading's call: what it adds beside the state
        just before it, its outputs and its compiled temporaries."""
        a = self.executable(name, exe)
        self.now.setdefault("readings", {})[name] = {
            "bytes_in_use_before": self.bytes_in_use(),
            "output_bytes": a["output_bytes"],
            "temp_bytes": a["temp_bytes"]}
