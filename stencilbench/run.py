#!/usr/bin/env python3
"""The stencil solver's benchmark: one cell, one run, one process.

    python stencilbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``configs/<name>.json``:
operator, grid, dtype, boundary value) and a traffic mix
(``traffic/<name>.json``: its parameters and the driver that runs it,
``drivers/<driver>.py``).  The run loads the program from ``src/``, draws
its data on the device from ``--seed``, warms up every shape the mix uses
(set-up, timed from process start), measures for ``--seconds``, checks what
the window produced against ``reference.py`` with the limits in
``limits/<cell>.json``, and prints one JSON line last on stdout::

    {"correct", "attempted", "failed", "metrics", "device", ["breakdown"],
     "checks"}

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the window is traced by the profiler (for the mix's
``trace_seconds`` at most) and the metrics are the cell's per-layer ones,
each read by ``metrics/<name up to its first dot>.py``.  Every number
compared is also printed, beside its limit, as the last lines on stderr.

The run needs a TPU with the cell's number of chips; anywhere else it exits
non-zero and prints no result.  ``--rehearse`` (tests only, refused on a
TPU) runs the sizes the configuration and mix give under ``rehearse`` on
any device.  ``--control`` runs the control in the program's place, its
own bfloat16 path, which the comparison has to find not correct.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up is timed from here  # noqa: E402

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import gc  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class Refused(Exception):
    """The run cannot give a result here; the message says why."""


class Context:
    """What a driver gets: the cell's files, the seed and two helpers."""

    def __init__(self, cell, config, traffic, seed, dtype):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.dtype = seed, dtype

    @staticmethod
    def span(name: str):
        """A host span in the profiler's trace (cheap when not tracing)."""
        import jax
        return jax.profiler.TraceAnnotation(name)

    @staticmethod
    def note(**fields):
        """One line on stderr about what the run set up."""
        print(json.dumps({"setup": fields}, default=str), file=sys.stderr,
              flush=True)


def _json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as e:
        raise Refused(f"cannot read {os.path.relpath(path, ROOT)}: {e}")


def load_cell(name: str, rehearse: bool):
    """The cell ``name`` of BENCHMARK.json with its configuration, mix,
    limits and metrics; a rehearsal takes the files' ``rehearse`` sizes."""
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json "
                      f"(have {sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(os.path.join(ROOT, configs[cell["config"]]["file"]))
    traffic = _json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    limits = _json(os.path.join(BENCH, "limits", name + ".json"))
    if rehearse:
        config = {**config, **config.get("rehearse", {})}
        traffic = {**traffic, **traffic.get("rehearse", {})}

    def applies(metric):
        return name in metric.get("workloads", [name])
    return (cell, config, traffic, limits,
            [m for m in bench["end_to_end"] if applies(m)],
            [m for m in bench["per_layer"] if applies(m)])


def trace_dir(cell: str) -> str:
    """Where a traced run of ``cell`` leaves its trace (the last one only:
    the next traced run of the cell clears it first)."""
    return os.path.join(ROOT, ".stencilbench", "trace", cell)


def per_layer(metrics, reduction, counters, cell) -> dict:
    """Each per-layer metric its reader finds something for."""
    out = {}
    for m in metrics:
        reader = importlib.import_module(
            "stencilbench.metrics." + m["name"].split(".")[0])
        value = reader.read(reduction=reduction, counters=counters,
                            cell=cell)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(args) -> dict:
    cell, config, traffic, limits, e2e, layers = load_cell(args.workload,
                                                           args.rehearse)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import repro.core  # noqa: F401
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        raise Refused(f"cannot import the program from {ROOT}/src: {e}")
    import jax
    if not args.rehearse:
        enable_compile_cache()
        # every program, however quick to build, comes from the cache
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    platform = devices[0].platform
    if args.rehearse and platform == "tpu":
        raise Refused("--rehearse runs tiny sizes off the chip only")
    if platform != "tpu" and not args.rehearse:
        raise Refused(f"no TPU: JAX runs on {platform!r} "
                      f"({devices[0].device_kind})")
    chips = int(cell["chips"])
    if len(devices) < chips and not args.rehearse:
        raise Refused(f"the cell needs {chips} chips, JAX sees "
                      f"{len(devices)}")
    devices = devices[:chips]
    Context.note(chip_found_s=time.perf_counter() - T0)

    events = []   # JAX's timed events: tracing, lowering, compiling
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: events.append((event, secs)))

    driver = importlib.import_module("stencilbench.drivers."
                                     + traffic["driver"])
    if args.control:
        config = {**config, "dtype": "bfloat16"}
    return measure(args, driver, cell, config, traffic, limits, e2e, layers,
                   devices, events)


class Pauses:
    """The garbage collector's pauses while the block runs."""

    def __enter__(self):
        self.seconds, self.longest, self._t = 0.0, 0.0, None
        gc.callbacks.append(self._callback)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._callback)

    def _callback(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            pause = time.perf_counter() - self._t
            self.seconds += pause
            self.longest = max(self.longest, pause)


def in_window(events) -> dict:
    """``{event: [count, seconds]}`` of JAX's timed events in a list."""
    out = {}
    for event, secs in events:
        acc = out.setdefault(event.rsplit("/", 1)[-1], [0, 0.0])
        acc[0] += 1
        acc[1] += secs
    return out


def measure(args, driver, cell, config, traffic, limits, e2e, layers,
            devices, events) -> dict:
    import jax
    platform = devices[0].platform
    ctx = Context(cell, config, traffic, args.seed, config["dtype"])
    state = driver.Run(ctx)
    setup_s = time.perf_counter() - T0
    ctx.note(setup_s=setup_s)

    seconds = args.seconds
    tdir = trace_dir(args.workload)
    if args.trace:
        seconds = min(seconds, float(traffic.get("trace_seconds", seconds)))
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(tdir)
    n_events = len(events)
    try:
        with ctx.span("bench.window"), Pauses() as pauses:
            window = state.window(seconds)
    finally:
        if args.trace:
            jax.profiler.stop_trace()
    window_events = in_window(events[n_events:])
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    checks = state.check()
    del state

    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": max(peaks) if None not in peaks else None}
    result = {"attempted": window["attempted"], "failed": window["failed"]}
    if args.trace:
        from stencilbench import trace
        path = trace.find_xplane(tdir)
        reduction = trace.reduce(trace.load(path)) if path else None
        result["metrics"] = per_layer(layers, reduction, window["counters"],
                                      cell)
        if reduction is not None:
            device.update(busy_s=reduction.busy_s,
                          window_s=reduction.window_s)
            result["breakdown"] = reduction.breakdown()
    else:
        values = dict(window["metrics"], setup_s=setup_s)
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]} for m in e2e}
    result["device"] = device
    # what may hold the host inside the window: JAX tracing, lowering or
    # compiling (each should be absent), the collector's pauses
    print(json.dumps({"window": {
        "seconds": seconds, "compiles": window_events.get(
            "backend_compile_duration", [0])[0],
        "jax_events": window_events, "gc_s": pauses.seconds,
        "gc_longest_s": pauses.longest, **window["counters"]}}),
        file=sys.stderr)

    if set(checks) != set(limits):
        raise Refused(f"limits/{args.workload}.json limits "
                      f"{sorted(limits)}, the run compares {sorted(checks)}")
    result["checks"] = {k: {"value": v, "limit": limits[k]["limit"]}
                        for k, v in checks.items()}
    # a NaN reading never passes
    result["correct"] = window["failed"] == 0 and all(
        not math.isnan(c["value"]) and c["value"] <= c["limit"]
        for c in result["checks"].values())
    for k, c in result["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tests only: tiny sizes, off the chip only")
    ap.add_argument("--control", action="store_true",
                    help="run the program's bfloat16 path in place of the "
                         "configuration's dtype (the comparison's control)")
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    try:
        result = run(args)
    except Refused as e:
        print(f"stencilbench: {e}", file=sys.stderr)
        return 1
    order = ("correct", "attempted", "failed", "metrics", "device",
             "breakdown", "checks")
    print(json.dumps({k: result[k] for k in order if k in result}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
