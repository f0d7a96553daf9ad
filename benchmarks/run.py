#!/usr/bin/env python3
"""One cell of the benchmark, once, in a new process.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in ``BENCHMARK.json``; its configuration is
``benchmarks/configs/<config>.json``, its traffic mix is
``benchmarks/traffic/<traffic>.json``, the job the mix names is
``benchmarks/jobs/<job>.py`` and each per-layer metric is read by
``benchmarks/layer_metrics/<name>.json`` or ``.py``.  A later PR adds files
and entries and edits none of these.

Earlier lines are free (one JSON object per phase); the last line of
standard output is the contract's: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, traced ``breakdown``, and last ``checks``: every
number ``correct`` compared beside its limit (also the last lines of
standard error).  With ``--trace 0`` the
metrics are the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics.  This file never imports JAX: a stepping job imports it inside the
job, the resume job's orchestrator stays off it.

``--rehearse`` walks the same control flow on the CPU at ``tiny`` sizes.  It
prints every line under the word REHEARSAL and never a result line: a CPU
number cannot come out under a device metric's name.
"""

T_PROCESS_START = __import__("time").time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

EXIT_NO_PROGRAM = 4
EXIT_NO_CHIP = 3
EXIT_JOB_FAILED = 5


from benchmarks import common  # noqa: E402
from benchmarks.common import load_module, read_json  # noqa: E402


def metric_applies(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def read_layer_metric(name, observed):
    """The metric's own reader: a ``.json`` that names a key of what the
    job observed, or a ``.py`` with ``read(observed)``.  ``None`` (nothing
    to read) leaves the metric out of the line."""
    as_json = os.path.join(HERE, "layer_metrics", name + ".json")
    if os.path.exists(as_json):
        value = observed["values"].get(read_json(as_json)["key"])
    else:
        value = load_module("layer_metrics", name).read(observed)
    return None if value is None else float(value)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)

    bench = read_json(ROOT, "BENCHMARK.json")
    # cells that wait to be admitted: same schema, looked up second
    waiting = read_json(HERE, "candidates.json")
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        have = {entry["name"] for entry in bench[key]}
        bench[key] = bench[key] + [
            entry for entry in waiting[key]
            if entry["name"] not in have or key in ("end_to_end", "per_layer")
        ]
    if not os.path.isdir(os.path.join(ROOT, "dlrover_tpu")):
        print("the program (dlrover_tpu/) is not in this checkout",
              file=sys.stderr)
        return EXIT_NO_PROGRAM
    cells = {c["name"]: c for c in bench["workloads"]}
    if args.workload not in cells:
        print(f"no cell {args.workload!r}; have {sorted(cells)}",
              file=sys.stderr)
        return 2
    cell = cells[args.workload]
    config_entry = next(
        c for c in bench["configs"] if c["name"] == cell["config"]
    )
    config = read_json(ROOT, config_entry["file"])
    traffic = read_json(HERE, "traffic", cell["traffic"] + ".json")
    seconds = args.seconds if args.seconds else bench["run_seconds"]
    job = load_module("jobs", traffic["job"])
    run = common.Run(
        cell=cell, config=config, traffic=traffic, seed=args.seed,
        seconds=float(seconds), trace=bool(args.trace),
        rehearse=args.rehearse, t_process_start=T_PROCESS_START,
    )
    try:
        observed = job.run(run)
    except common.NoChip as e:
        run.emit({"phase": "device", "ok": False, "error": str(e)})
        return EXIT_NO_CHIP
    except Exception as e:  # noqa: BLE001 - the phase line carries the cause
        run.emit({"phase": run.phase, "ok": False,
                  "error": f"{type(e).__name__}: {e}"[:2000],
                  "traceback": traceback.format_exc()[-3000:],
                  **getattr(e, "detail", {})})
        return EXIT_JOB_FAILED
    finally:
        run.cleanup()

    wanted = bench["per_layer"] if args.trace else bench["end_to_end"]
    metrics = {}
    for metric in wanted:
        if not metric_applies(metric, cell["name"]):
            continue
        if args.trace:
            value = read_layer_metric(metric["name"], observed)
        else:
            value = observed["values"].get(metric["name"])
        if value is not None:
            metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}
    line = {
        "correct": bool(observed["correct"]),
        "attempted": int(observed["attempted"]),
        "failed": int(observed["failed"]),
        "metrics": metrics,
        "device": observed["device"],
    }
    if args.trace and not args.rehearse and not (
            line["device"].get("busy_s") or 0) > 0:
        run.emit({"phase": "trace", "ok": False,
                  "error": "the trace holds no operation on the device"})
        return EXIT_JOB_FAILED
    if args.trace and observed.get("breakdown"):
        line["breakdown"] = observed["breakdown"]
    # what ``correct`` compared, each number beside its limit: the last
    # lines of standard error and the last key of the result's line
    checks = observed.get("checks") or {}
    for name, (value, limit) in checks.items():
        print(f"check {name}: {value} limit {limit}", file=sys.stderr)
    sys.stderr.flush()
    line["checks"] = {   # a NaN (the one value unequal to itself) as a word
        name: {"value": value if value == value else "nan", "limit": limit}
        for name, (value, limit) in checks.items()}
    if args.rehearse:
        run.emit({"phase": "result", "would_print": sorted(metrics),
                  "correct": line["correct"], "attempted": line["attempted"],
                  "failed": line["failed"]})
        return 0
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
