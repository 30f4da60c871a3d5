#!/usr/bin/env python3
"""One run of one benchmark cell on the accelerator.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name in
``BENCHMARK.json`` and under ``bench/``; this file has no branch for
any of them.  The traffic mix names a driver (``bench/drivers/``) that
sets the system up from ``--seed``, warms every shape it will use,
drives the measured window and then compares what the window produced
with the plain reference.

With ``--trace 0`` the last line of standard output carries the cell's
end-to-end metrics; with ``--trace 1`` (a separate run) it carries the
per-layer metrics read from the profiler trace and the program's spans
and counters, with ``device.busy_s``/``window_s`` and a ``breakdown``.
The comparison's numbers end both, beside their limits, and end
standard error too.  Without a TPU, or with fewer chips than the cell
asks for, the run exits 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is counted from process start

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def emit(obj):
    print(json.dumps(obj), flush=True)


def run_cell(args, check_chips=True, out=emit, resize=None, patch=None,
             spec=None):
    """Drive one run and return the result object (printed last by
    ``main``).  The CPU rehearsal in ``bench/tests`` passes
    ``check_chips=False``, ``resize(config, traffic)`` to shrink the
    configuration, ``patch(driver)`` to plant a fault or a control, and
    ``spec`` with the parked cells added; everything else is the same
    code."""
    spec = spec or harness.load_spec()
    cell, entry, config, traffic = harness.cell_files(spec, args.workload)
    if resize is not None:
        config, traffic = resize(config, traffic)
    e2e = harness.metrics_of(spec, "end_to_end", cell["name"])
    per_layer = harness.metrics_of(spec, "per_layer", cell["name"],
                                   [m["name"] for m in e2e])
    harness.configure_jax_cache()
    if args.trace:
        os.environ["REPRO_OBS"] = "1"

    import jax

    if check_chips:
        devices = harness.require_chips(cell["chips"])
        peaks = harness.peaks_for(devices[0].device_kind)
    else:
        devices, peaks = jax.devices()[:1], None
    importlib.import_module("repro")      # the system under test is here
    run = harness.Run(args, cell, entry, config, traffic, T_START)
    run.compiles.install()
    driver = harness.load_driver(traffic).Driver(run)
    if patch is not None:
        patch(driver)

    with harness.quiet_stdout():
        driver.setup()
        driver.window()
        setup_s = run.t0 - T_START
        mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices]
        values = driver.end_to_end()
        driver.release()
        checks = driver.check()

    comp = run.compiles
    in_window = comp.between(run.t0, run.t1)
    loads_in_window = comp.between(run.t0, run.t1, loads=True)
    # set-up builds every program the window runs: a compile, or a load
    # from the persistent cache, inside the window fails the run
    checks["compiles_in_window"] = (len(in_window) + len(loads_in_window), 0)
    out({"compiles": {
            "count": len(comp.events),
            "seconds": sum(s for _, s, _ in comp.events),
            "loads": len(comp.loads),
            "load_seconds": sum(s for _, s, _ in comp.loads),
            "in_window": [n for _, n in in_window][:20],
            "loads_in_window": len(loads_in_window),
            "load_seconds_in_window": sum(s for s, _ in loads_in_window)},
         "window_s": run.window_s, "setup_s": setup_s,
         "driver": getattr(driver, "notes", {})})

    result = {"correct": all(v <= lim for v, lim in checks.values()),
              "attempted": driver.attempted, "failed": driver.failed}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": max(mem)}
    if args.trace:
        readings = driver.readings(peaks)
        metrics = {}
        for m in per_layer:
            read, kw = harness.load_reader(m["name"])
            v = read(readings, **kw)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out({"kernel_figures": readings.figures})
        device["busy_s"] = readings.trace.busy_ns() / 1e9
        device["window_s"] = readings.trace.window_ns / 1e9
        result["breakdown"] = readings.trace.breakdown()
    else:
        values["setup_s"] = setup_s
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in e2e}
    result.update(metrics=metrics, device=device)
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"check {k}: {v!r} (limit {lim!r}) "
              f"{'ok' if v <= lim else 'FAILED'}", file=sys.stderr)
    return result


def main(argv=None):
    args = parse(argv)
    try:
        result = run_cell(args)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
