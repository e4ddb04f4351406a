"""The traced part of a `--trace 1` run: a few seconds of the steady
window under `jax.profiler`, written under the checkout and removed once
read. Per-layer metrics read the traced interval: the path remembers its
counters at both ends (`toggle(mark)`), and the interval's length on the
host's clock leaves the profiler's own start and stop out. The profiler's
start stalls the host for some tenths of a second (PR 23 read one 0.40 s
gap in a 2.1 s trace of a loop that otherwise shows none), so the interval
opens `settle_s` after the start and the reducer drops the device events
before it."""
from __future__ import annotations

import os
import shutil
import time


class Tracer:
    """Starts the profiler `start_s` into the window and stops it
    `length_s` later; the path asks `due(now)` and calls `toggle(mark)`."""

    def __init__(self, on, trace_dir, start_s=2.0, length_s=3.0,
                 settle_s=0.5):
        self.on = bool(on)
        self.dir = trace_dir
        self.settle_s = settle_s
        # window seconds at which: the profiler starts, the interval opens,
        # the interval closes and the profiler stops
        self.at = (start_s, start_s + settle_s, start_s + settle_s + length_s)
        self.state = 0     # 0 idle, 1 settling, 2 interval open, 3 done
        self.marks = []
        self.t_started = self.t_stopping = None

    def due(self, now):
        return self.on and self.state < 3 and now >= self.at[self.state]

    def toggle(self, mark=None):
        import jax
        if self.state == 0:
            shutil.rmtree(self.dir, ignore_errors=True)
            os.makedirs(self.dir, exist_ok=True)
            jax.profiler.start_trace(self.dir)
        elif self.state == 1:
            self.marks.append(mark)
            self.t_started = time.perf_counter()
        else:
            self.marks.append(mark)
            self.t_stopping = time.perf_counter()
            jax.profiler.stop_trace()
        self.state += 1

    def finish(self, mark=None):
        """The window closed: end whatever is open."""
        import jax
        if self.state == 2:
            self.toggle(mark)
        elif self.state == 1:       # never settled: nothing to read
            jax.profiler.stop_trace()
            self.state = 0
            self.on = False

    def traced(self):
        return self.state == 3

    def interval_s(self):
        return self.t_stopping - self.t_started

    def cleanup(self):
        shutil.rmtree(self.dir, ignore_errors=True)
