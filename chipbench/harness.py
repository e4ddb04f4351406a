"""The harness: finds a cell's files by the names in `BENCHMARK.json`,
hands them to the cell's path, reduces the trace, asks each per-layer
metric's reader, and prints the result line. It holds nothing that belongs
to one configuration, one traffic mix or one metric."""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

from . import traffic as traffic_mod
from . import tracing, xplane

HERE = os.path.dirname(os.path.abspath(__file__))


class Bench:
    """`BENCHMARK.json` and the data directories it names."""

    def __init__(self, root, file="BENCHMARK.json"):
        self.root = root
        with open(os.path.join(root, file)) as f:
            self.spec = json.load(f)
        self.dirs = [os.path.join(root, p) for p in self.spec["paths"]]

    def find(self, kind, name, ext=".json"):
        for d in self.dirs:
            path = os.path.join(d, kind, name + ext)
            if os.path.exists(path):
                return path
        raise FileNotFoundError(f"no {kind}/{name}{ext} under "
                                f"{self.spec['paths']}")

    def cell(self, name):
        for c in self.spec["workloads"]:
            if c["name"] == name:
                return c
        raise KeyError(f"no cell {name!r}; --list shows them")

    def config(self, name):
        entry = next(c for c in self.spec["configs"] if c["name"] == name)
        with open(os.path.join(self.root, entry["file"])) as f:
            return json.load(f)

    def metrics(self, group, cell):
        return [mt for mt in self.spec[group]
                if cell in mt.get("workloads", [cell])]

    def listing(self):
        out = {"cells": [], "configs": {}, "traffic": [], "layer_metrics": [],
               "paths": _modules("paths"), "readers": _modules("readers")}
        for c in self.spec["workloads"]:
            out["cells"].append(
                {"name": c["name"], "config": c["config"],
                 "traffic": self.find("traffic", c["traffic"]),
                 "end_to_end": [mt["name"] for mt in
                                self.metrics("end_to_end", c["name"])],
                 "per_layer": [mt["name"] for mt in
                               self.metrics("per_layer", c["name"])]})
        for c in self.spec["configs"]:
            out["configs"][c["name"]] = c["file"]
        for d in self.dirs:
            for kind in ("traffic", "layer_metrics"):
                sub = os.path.join(d, kind)
                if os.path.isdir(sub):
                    out[kind] += sorted(os.path.splitext(f)[0]
                                        for f in os.listdir(sub))
        return out


def _modules(package):
    return sorted(os.path.splitext(f)[0]
                  for f in os.listdir(os.path.join(HERE, package))
                  if f.endswith(".py") and not f.startswith("_"))


def load_peaks(kind):
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table or not isinstance(table[kind], dict):
        raise KeyError(f"device kind {kind!r} is not in chipbench/peaks.json")
    return table[kind]


class NoDevice(RuntimeError):
    """No TPU, too few chips, or a device kind without published peaks."""


def find_device(chips):
    """The device as JAX reports it; raises NoDevice unless it is a TPU
    with at least `chips` chips and published peaks."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoDevice(f"jax found no TPU (platform {dev.platform!r})")
    if len(devices) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, jax found "
                       f"{len(devices)}")
    try:
        load_peaks(dev.device_kind)
    except KeyError as e:
        raise NoDevice(e.args[0]) from e
    return dev


def arm_compile_cache():
    """Where `JAX_COMPILATION_CACHE_DIR` says, else the one fixed path in
    the checkout (the program's own rule, `deploy.py`)."""
    from incubator_mxnet_tpu import deploy
    deploy.default_compile_cache_to_checkout()
    deploy.maybe_enable_compile_cache()


def run_cell(bench, workload, seed, seconds, trace, t_process_start,
             check_device=True, describe_to=None):
    """Runs one cell and returns the result line as a dict. `describe_to`
    (tests and first looks only) keeps a description of the trace's
    planes, lines and heaviest names in that file."""
    cell = bench.cell(workload)
    config = bench.config(cell["config"])
    spec = traffic_mod.load(bench.find("traffic", cell["traffic"]))

    import jax
    if check_device:
        dev = find_device(cell["chips"])
        peaks = load_peaks(dev.device_kind)
    else:                      # chipbench/tests only: the CPU rehearsal
        dev = jax.devices()[0]
        peaks = load_peaks("TPU v5 lite")
    arm_compile_cache()

    tracer = tracing.Tracer(
        trace, os.path.join(bench.root, ".chipbench_trace", workload),
        **config.get("trace", {}))
    path = importlib.import_module(f"chipbench.paths.{config['path']}")
    ctx = {"config": config, "traffic": spec, "seed": int(seed),
           "seconds": float(seconds), "tracer": tracer,
           "t_process_start": t_process_start}
    try:
        result = path.run(ctx)
        reduced = None
        if tracer.traced():
            try:
                reduced = xplane.Trace(xplane.find_xplane(tracer.dir),
                                       skip_head_s=tracer.settle_s)
            except ValueError:
                if check_device:    # a chip run whose trace shows no device
                    raise
            if describe_to and reduced is not None:
                with open(describe_to, "w") as f:
                    json.dump(reduced.describe(), f, indent=1)
    finally:
        tracer.finish()
        tracer.cleanup()

    if trace and reduced is None and check_device:
        raise RuntimeError("--trace 1, and the window closed before the "
                           "traced interval did: nothing to read")
    limits = config["limits"]
    compared = {name: {"value": value, "limit": limits[name]}
                for name, value in result["compared"].items()}
    # a NaN is under no limit
    correct = bool(compared) and all(c["value"] <= c["limit"]
                                     for c in compared.values())
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": result["memory_peak_bytes"]}
    line = {"correct": correct, "attempted": result["attempted"],
            "failed": result["failed"], "metrics": {}, "device": device}

    if not trace:
        for mt in bench.metrics("end_to_end", workload):
            line["metrics"][mt["name"]] = {
                "value": result["e2e"][mt["name"]], "unit": mt["unit"]}
    else:
        if reduced is not None:
            busy_s, window_s = reduced.busy_and_window_s()
            device["busy_s"], device["window_s"] = busy_s, window_s
            line["breakdown"] = {"device_ops": reduced.top_ops(10),
                                 "idle_gaps": reduced.idle_gaps(10)}
        rctx = {"trace": reduced, "counters": result["counters"],
                "window_s": tracer.interval_s(), "peaks": peaks}
        for mt in bench.metrics("per_layer", workload):
            with open(bench.find("layer_metrics", mt["name"])) as f:
                how = json.load(f)
            reader = importlib.import_module(
                f"chipbench.readers.{how['reader']}")
            value = reader.read(how.get("params", {}), rctx)
            if value is not None:
                line["metrics"][mt["name"]] = {"value": value,
                                               "unit": mt["unit"]}
    line["notes"] = {k: v for k, v in result["counters"].items()
                     if isinstance(v, (int, float, str))}
    line["compared"] = compared
    return line


def main(argv, root, t_process_start, check_device=True):
    ap = argparse.ArgumentParser(prog="chipbench/run.py")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--list", action="store_true",
                    help="print the cells, files and modules found")
    ap.add_argument("--bench", default="BENCHMARK.json",
                    help="the benchmark file, relative to the checkout")
    args = ap.parse_args(argv)
    bench = Bench(root, args.bench)
    if args.list:
        print(json.dumps(bench.listing(), indent=1))
        return 0
    if args.workload is None:
        ap.error("--workload or --list")
    seconds = args.seconds if args.seconds is not None \
        else bench.spec["run_seconds"]
    try:
        import incubator_mxnet_tpu  # noqa: F401  the system under test
        line = run_cell(bench, args.workload, args.seed, seconds,
                        bool(args.trace), t_process_start, check_device)
    except (ImportError, NoDevice) as e:
        print(f"chipbench: nothing was run: {e}", file=sys.stderr)
        return 3
    for name, c in line["compared"].items():
        print(f"chipbench: compared {name} = {c['value']!r} "
              f"(limit {c['limit']!r})", file=sys.stderr)
    print(f"chipbench: correct = {line['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
