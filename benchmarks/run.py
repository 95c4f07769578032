#!/usr/bin/env python3
"""One run of one benchmark cell.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no children. Device check, set-up (imports, compile cache,
operands from the seed, one warm-up call), the measured window, the
device's peak memory, the correctness check, then ONE JSON object as
the last line of stdout. Everything a cell, a traffic kind or a metric
needs is found by its name in ``BENCHMARK.json`` (see README.md); this
file knows none of them.

``--rehearse-on-cpu`` (with ``--n/--nb``) walks the same code on
whatever backend there is and always ends ``"correct": false`` with
exit code 1: a rehearsal is never a result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()            # set-up is timed from here

import argparse                     # noqa: E402
import glob                         # noqa: E402
import importlib                    # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import shutil                       # noqa: E402
import sys                          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TRACED_SOLVES = 5
TRACE_DIR = os.path.join(ROOT, "benchmarks", ".trace")


DEVICE_TAG: dict = {}       # platform, kind, count: on every line printed


def say(**line) -> None:
    print(json.dumps({**line, **DEVICE_TAG}), flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help='any backend; always ends "correct": false')
    ap.add_argument("--n", type=int, help="rehearsal only")
    ap.add_argument("--nb", type=int, help="rehearsal only")
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="copy the raw .xplane.pb there (by hand only)")
    args = ap.parse_args(argv)
    if (args.n or args.nb) and not args.rehearse_on_cpu:
        ap.error("--n/--nb are for --rehearse-on-cpu only")
    return args


def find_devices(spec: dict, rehearsal: bool):
    """The cell's chips as JAX reports them, or exit non-zero."""
    import jax
    from benchmarks.harness.peaks import PEAKS
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    right = (device["platform"] == "tpu" and device["kind"] in PEAKS
             and device["count"] == spec["chips"])
    if not right and not rehearsal:
        print(f"benchmarks/run.py: cell {spec['name']} needs "
              f"{spec['chips']} TPU chip(s) of a kind in "
              f"harness/peaks.py, jax found {device}", file=sys.stderr)
        raise SystemExit(1)
    if len(devices) < spec["chips"]:
        raise SystemExit(f"rehearsal needs {spec['chips']} devices, "
                         f"found {len(devices)}")
    return devices, device, right


def peak_bytes(devices) -> list:
    stats = [d.memory_stats() for d in devices]
    return [int(s["peak_bytes_in_use"]) if s else 0 for s in stats]


def start_trace() -> None:
    """``jax.profiler`` into a fixed directory of the checkout, emptied
    first."""
    import jax
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # no per-frame host events
    jax.profiler.start_trace(TRACE_DIR, profiler_options=options)


def stop_trace() -> str:
    """Stop the profiler; the path of the one ``.xplane.pb`` it wrote."""
    import jax
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(
        TRACE_DIR, "plugins", "profile", "*", "*.xplane.pb"))
    if len(found) != 1:
        raise SystemExit(f"expected one .xplane.pb, found {found}")
    return found[0]


def read_metrics(package: str, entries: list, run: dict) -> dict:
    """Each metric's reader is ``benchmarks/<package>/<name>.py`` with a
    ``compute(run)``; one that finds nothing to read returns None and
    the metric is left out of the line."""
    out = {}
    for entry in entries:
        module = importlib.import_module(
            f"benchmarks.{package}.{entry['name'].replace('.', '_')}")
        value = module.compute(run)
        if value is not None:
            out[entry["name"]] = {"value": float(value),
                                  "unit": entry["unit"]}
    return out


def run_cell(spec: dict, devices, args, rehearsal: bool = False) -> dict:
    """Everything after the device check; returns the result line."""
    import jax
    from slate_tpu.cache import place_jax_compile_cache
    from benchmarks.harness import trace_reduce
    from benchmarks.harness.compiles import CompileLog

    cache_dir = place_jax_compile_cache()
    # every program goes to the persistent cache, however quick its
    # compile, so a warm set-up compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    log = CompileLog()
    log.mark("setup")
    devices = devices[:spec["chips"]]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    DEVICE_TAG.update(device)
    say(step="device", cell=spec["name"], seed=args.seed,
        jax=jax.__version__, jax_cache_dir=cache_dir,
        rehearsal=rehearsal)

    traffic = importlib.import_module(
        f"benchmarks.traffic.{spec['traffic']['kind']}")
    session = traffic.open_session(spec, devices, args.seed)
    peaks_operands = peak_bytes(devices)
    first_call_s = session.warm_up()
    peaks_warm_up = peak_bytes(devices)

    # ------------------------------------------------------- the window
    log.mark("window")
    t_window = time.perf_counter()
    setup_s = t_window - T0
    trace_path = None
    if args.trace:
        start_trace()
        session.drive(calls=TRACED_SOLVES, annotate=lambda:
                      jax.profiler.TraceAnnotation(trace_reduce.ANNOTATION))
        trace_path = stop_trace()
    session.drive(until=t_window + args.seconds)
    window_s = time.perf_counter() - t_window
    log.mark("after")

    # -------------------------------- memory first, then the comparison
    peaks = peak_bytes(devices)
    checks = session.check()
    for row in checks:
        say(**row)
    correct = (all(row["ok"] for row in checks) and session.failed == 0
               and len(session.walls) > 0)

    reduced = None
    if trace_path:
        if args.keep_trace:
            os.makedirs(args.keep_trace, exist_ok=True)
            shutil.copy(trace_path, args.keep_trace)
        raw = trace_reduce.load_xplane(trace_path, rehearsal=rehearsal)
        reduced = trace_reduce.reduce(raw)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    run = {"spec": spec, "device": device, "walls": session.walls, "attempted": session.attempted,
           "failed": session.failed, "window_s": window_s,
           "seconds": args.seconds, "setup_s": setup_s,
           "first_call_s": first_call_s, "compiles": log.phases,
           "peak_bytes": peaks, "trace": reduced}
    say(step="window", window_s=window_s, samples=len(session.walls),
        walls_s=session.walls, setup_s=setup_s,
        first_call_s=first_call_s, compiles=log.phases,
        peak_bytes=peaks, peak_bytes_after_operands=peaks_operands,
        peak_bytes_after_warm_up=peaks_warm_up)

    if args.trace:
        metrics = read_metrics("layer_metrics", spec["per_layer"], run)
    else:
        metrics = read_metrics("end_to_end", spec["end_to_end"], run)
    device["memory_peak_bytes"] = max(peaks)
    result = {"correct": bool(correct), "attempted": session.attempted,
              "failed": session.failed, "metrics": metrics,
              "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s()
        device["window_s"] = reduced.window_s
        result["breakdown"] = trace_reduce.breakdown(reduced)
    return result


def main(argv=None) -> int:
    args = parse(argv)
    from benchmarks.harness.cells import load_cell
    spec = load_cell(args.workload, n=args.n, nb=args.nb)
    devices, _, right = find_devices(spec, args.rehearse_on_cpu)
    result = run_cell(spec, devices, args, rehearsal=not right)
    if not right:
        result["correct"] = False       # a rehearsal is never a result
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0 if right else 1


if __name__ == "__main__":
    sys.exit(main())
