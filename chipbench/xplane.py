"""Reduction of a profiler trace (`*.xplane.pb`) to what the per-layer
metrics read, with `jax.profiler.ProfileData` alone.

A device plane is one whose name starts with `/device:TPU:`. On it the
line `XLA Ops` holds one event per executed HLO operation (fusions,
custom calls = Pallas kernels, copies) and the line `XLA Modules` one
event per execution of a compiled program (`jit_<name>(<fingerprint>)`).
Times are nanoseconds on the device's clock. Nothing here is specific to
a cell: names and patterns are data in `layer_metrics/*.json`."""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


_KIND = re.compile(r"[\]})]\s([a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(name):
    """`%jvp__.54 custom-call tpu_custom_call` from an operation's whole
    HLO text: its name, its kind and a custom call's target."""
    head, _, rest = name.partition(" = ")
    kind, target = _KIND.search(rest), _TARGET.search(rest)
    parts = [head] + ([kind.group(1)] if kind else []) \
        + ([target.group(1)] if target else [])
    return " ".join(parts)[:120]


class Line:
    """Events of one line as parallel lists: names, start ns, duration ns."""

    def __init__(self, names, starts, durs):
        import numpy as np
        self.names = names
        self.starts = np.asarray(starts, np.float64)
        self.durs = np.asarray(durs, np.float64)

    def select(self, pattern):
        """Indices of the events whose name the regular expression finds."""
        import numpy as np
        rx = re.compile(pattern)
        hit = {n: bool(rx.search(n)) for n in set(self.names)}
        return np.fromiter((i for i, n in enumerate(self.names) if hit[n]),
                           dtype=np.int64)

    def total_s(self, pattern):
        idx = self.select(pattern)
        return float(self.durs[idx].sum()) * 1e-9, int(idx.size)

    def by_name_s(self):
        out = {}
        for n, d in zip(self.names, self.durs):
            out[n] = out.get(n, 0.0) + d * 1e-9
        return out


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return found[-1]


class Trace:
    """Device planes of one trace: {plane name: {line name: Line}}."""

    def __init__(self, path, skip_head_s=0.0):
        """`skip_head_s` drops, on each device, the events that start in
        the first so many seconds after its first event (the profiler's
        own start-up, see `tracing.py`)."""
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        self.devices = {}
        self.other_planes = []
        for plane in data.planes:
            if not plane.name.startswith(DEVICE_PLANE):
                self.other_planes.append(plane.name)
                continue
            lines = {}
            first = min((ev.start_ns for line in plane.lines
                         for ev in line.events), default=0)
            cut = first + skip_head_s * 1e9
            for line in plane.lines:
                names, starts, durs = [], [], []
                for ev in line.events:
                    if ev.start_ns >= cut:
                        names.append(ev.name)
                        starts.append(ev.start_ns)
                        durs.append(ev.duration_ns)
                lines[line.name] = Line(names, starts, durs)
            self.devices[plane.name] = lines
        if not self.devices:
            raise ValueError(
                f"{path}: no device plane ({DEVICE_PLANE}*) among "
                f"{self.other_planes}")

    def line(self, name):
        """The named line of each device that has it."""
        return [lines[name] for lines in self.devices.values()
                if name in lines and len(lines[name].names)]

    # -- busy and idle ----------------------------------------------------
    def busy_and_window_s(self):
        """(busy_s, window_s) averaged over the devices: the union of the
        intervals in which an operation ran, and the span from the first
        operation's start to the last one's end."""
        busy, window = [], []
        for ops in self.line(OPS_LINE):
            b, w, _ = _union(ops)
            busy.append(b)
            window.append(w)
        if not busy:
            raise ValueError("the trace holds no device operation")
        return sum(busy) / len(busy), sum(window) / len(window)

    def idle_gaps(self, top=10):
        """The longest gaps between device operations on the first device:
        [(what ran before the gap, seconds)]."""
        ops = self.line(OPS_LINE)[0]
        _, _, gaps = _union(ops, keep_gaps=True)
        gaps.sort(key=lambda g: -g[1])
        return [[f"after {short_name(name)}", sec] for name, sec in gaps[:top]]

    def top_ops(self, top=10):
        total = {}
        for ops in self.line(OPS_LINE):
            for n, s in ops.by_name_s().items():
                total[n] = total.get(n, 0.0) + s
        n_dev = max(1, len(self.line(OPS_LINE)))
        ranked = sorted(total.items(), key=lambda kv: -kv[1])[:top]
        return [[short_name(n), s / n_dev] for n, s in ranked]

    def _time_s(self, line_name, pattern):
        pairs = [ln.total_s(pattern) for ln in self.line(line_name)]
        n = max(1, len(pairs))
        return sum(p[0] for p in pairs) / n, sum(p[1] for p in pairs) // n

    def op_time_s(self, pattern):
        """(seconds, count) of the device operations whose name matches,
        averaged over the devices."""
        return self._time_s(OPS_LINE, pattern)

    def module_time_s(self, pattern):
        """The same of the compiled programs' executions."""
        return self._time_s(MODULES_LINE, pattern)

    def describe(self, top=25):
        """What a person reads first: planes, lines, the heaviest names."""
        out = {"other_planes": self.other_planes, "devices": {}}
        for pname, lines in self.devices.items():
            out["devices"][pname] = {
                lname: {"events": len(ln.names),
                        "top": sorted(ln.by_name_s().items(),
                                      key=lambda kv: -kv[1])[:top]}
                for lname, ln in lines.items()}
        return out


def _union(ops, keep_gaps=False):
    """busy seconds, window seconds and (optionally) the gaps of a Line."""
    import numpy as np
    order = np.argsort(ops.starts, kind="stable")
    starts = ops.starts[order]
    ends = starts + ops.durs[order]
    reach = np.maximum.accumulate(ends)
    # a gap opens where the next start lies past everything seen so far
    gap = starts[1:] - reach[:-1]
    opens = gap > 0
    window = float(reach[-1] - starts[0])
    busy = window - float(gap[opens].sum())
    gaps = []
    if keep_gaps:
        for i in np.nonzero(opens)[0]:
            gaps.append((ops.names[order[i]], float(gap[i]) * 1e-9))
    return busy * 1e-9, window * 1e-9, gaps
