"""One run of one cell.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process: finds the cell's files by name (``harness/spec.py``),
refuses to start without the chips the cell asks for, builds the system
under test with weights from ``--seed``, warms the cell's own shapes
(set-up), measures for ``--seconds``, checks what the timed path produced
against the plain reference, and prints one JSON object as the last line
of standard output. ``--rehearse`` is the CPU tests' way in: the
configuration's ``rehearse`` sizes, no device metric in the line.
"""
from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()      # as near to process start as we see

import argparse      # noqa: E402
import faulthandler  # noqa: E402
import importlib     # noqa: E402
import json          # noqa: E402
import sys           # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", action="store_true",
                    help="also put the control (the lower-precision "
                         "reference) and the planted faults in the program's "
                         "place and judge them by the cell's own limits; for "
                         "setting limits, never in a measured run")
    ap.add_argument("--set", action="append", default=[], metavar="K=V",
                    help="override a number of the traffic mix (the rate "
                         "sweep that finds a knee); never in a measured run")
    return ap.parse_args(argv)


def metrics_of(cell, run, trace: bool, on_chip: bool) -> dict:
    from .harness import spec
    kind, entries = (("layer_metrics", cell.per_layer) if trace
                     else ("end_metrics", cell.end_to_end))
    out = {}
    for m in entries:
        if not on_chip and m["source"] != "program_counter":
            continue          # a CPU run never names a device metric
        value = spec.reader(cell.root, kind, m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    args = parse(argv)
    # the contract's limit is 1200 s for a cold run: dump every thread's
    # stack and exit non-zero rather than hang past it
    faulthandler.dump_traceback_later(1150, exit=True)
    from .harness import check, device, spec
    cell = spec.load(args.workload)
    cfg, mix = spec.sizes(cell, args.rehearse)
    for kv in args.set:
        key, value = kv.split("=", 1)
        mix[key] = json.loads(value)
    record, peaks = device.require(cell.chips, args.rehearse,
                                   mix.get("client_options"))
    if not args.rehearse:
        device.enable_compile_cache(cell.root)
    ctx = {"t_process": _T_PROCESS, "peaks": peaks,
           "cfg": cfg, "mix": mix, "compiles": device.CompileCounter(),
           "memory_peak": lambda: device.memory_peak_bytes(cell.chips),
           "rehearse": args.rehearse, "control": args.control}
    driver = importlib.import_module(f".drivers.{mix['driver']}",
                                     __package__)
    got = driver.run(cell, args, ctx)
    run = got["run"]
    limits = cfg["rehearse_limits" if args.rehearse else "limits"]
    unjudged = () if args.rehearse else cfg.get("not_compared", ())
    correct, table = check.judge(got["numbers"], limits, unjudged)
    dev = dict(record, memory_peak_bytes=run.memory_peak_bytes)
    line = {"correct": correct, "attempted": int(got["attempted"]),
            "failed": int(got["failed"]),
            "metrics": metrics_of(cell, run, bool(args.trace),
                                  peaks is not None),
            "device": dev}
    if run.trace is not None:
        dev["busy_s"] = run.trace.busy_s
        dev["window_s"] = run.trace.window_s
        line["breakdown"] = {"device_ops": run.trace.top_ops(10),
                             "idle_gaps": run.trace.idle_gaps}
    line["compiles_in_window"] = run.compiles_in_window
    if "_info" in got["numbers"]:
        line["info"] = got["numbers"]["_info"]
    extra = {"compiles_in_window": run.compiles_in_window,
             "compared_over": got["numbers"].get("_compared")}
    # the control and the planted faults, judged as the program is: each
    # has to come out as not correct
    for key in ("_control", "_fault_half_batch"):
        if key in got["numbers"]:
            ok, tab = check.judge(got["numbers"][key], limits, unjudged)
            line[key.lstrip("_")] = {"correct": ok, "compared": tab}
            extra[f"{key.lstrip('_')}_correct"] = str(ok).lower()
    line["compared"] = table
    check.report(table, correct, extra)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
