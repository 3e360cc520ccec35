"""One run of one cell.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process per run: loads, warms only the cell's own shapes (set-up),
measures for ``--seconds``, checks the outputs, and prints ONE JSON
object as the last line of its standard output. ``--trace 0`` reports
the cell's end-to-end metrics, ``--trace 1`` its per-layer metrics from
a traced run. Fails without the TPU chips the cell asks for; the CPU is
used only under ``--rehearse-cpu``, which stamps ``platform: cpu``.
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # as near to the process's start as code gets

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

if __package__ in (None, ""):  # run as a file: make the package findable
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmark import manifest  # noqa: E402

WORK = os.path.join(manifest.ROOT, ".bench_work")  # listed in .gitignore


def metric_values(cell: dict, facts: dict, trace: bool,
                  rehearse: bool = False) -> dict:
    """The cell's end-to-end metrics (by their own small arithmetic) or,
    traced, its per-layer metrics (each by its reader)."""
    from benchmark import end_to_end

    if not trace:
        return {m["name"]: {"value": end_to_end.VALUE[m["name"]](facts),
                            "unit": m["unit"]} for m in cell["end_to_end"]}
    out = {}
    for m in cell["per_layer"]:
        try:
            value = manifest.layer_metric_reader(m["name"])(facts)
        except KeyError:
            if not rehearse:  # (the CPU has no entry in the table of peaks)
                raise
            continue
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny widths on the CPU; never a measurement")
    args = ap.parse_args(argv)
    cell = manifest.cell(manifest.load_manifest(), args.workload)

    # This process orchestrates and must be UNABLE to take a chip; the
    # node agent sets each worker's platform from its TPU grant.
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.chdir(manifest.ROOT)  # workers import `benchmark` from their cwd
    try:
        from ray_tpu._private import accelerator
    except ImportError as e:
        print(f"benchmark: the program is not here: {e}", file=sys.stderr)
        return 1
    if args.rehearse_cpu:
        os.environ["RAY_TPU_CHIPS"] = "0"
    else:
        found = accelerator.detect_tpu_chips()
        if found < cell["chips"]:
            print(f"benchmark: {args.workload} needs {cell['chips']} TPU "
                  f"chip(s), found {found}", file=sys.stderr)
            return 1
    from benchmark import serve_driver, train_driver

    driver = {"serve": serve_driver, "train": train_driver}[
        cell["traffic"]["kind"]]
    work_dir = os.path.join(WORK, args.workload)
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir)
    # Standard output carries the result line and nothing else: workers'
    # forwarded output goes to stderr from here on.
    out, sys.stdout = os.fdopen(os.dup(sys.stdout.fileno()), "w"), sys.stderr
    try:
        facts = driver.run(
            cell, seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace), rehearse=args.rehearse_cpu,
            t_start=T_START, work_dir=work_dir)
        dev = facts["device"]
        want = "cpu" if args.rehearse_cpu else "tpu"
        if dev["platform"] != want or (
                want == "tpu" and dev["count"] != cell["chips"]):
            print(f"benchmark: ran on {dev}, not on {cell['chips']} {want} "
                  "device(s): no result", file=sys.stderr)
            return 1
        line = {"correct": facts["correct"], "attempted": facts["attempted"],
                "failed": facts["failed"]}
        if args.trace:
            from benchmark import trace_reduce

            facts["trace"] = trace_reduce.load_xplane(facts["log_dir"])
            busy = trace_reduce.busy(facts["trace"])
            if busy is None and want == "tpu":
                print("benchmark: no operation ran on the device in the "
                      "traced window", file=sys.stderr)
                return 1
            dev.update(busy or {"busy_s": 0.0, "window_s": 0.0})
            line["breakdown"] = trace_reduce.breakdown(facts["trace"])
            print("benchmark: device time by kind of operation: "
                  f"{trace_reduce.op_kinds(facts['trace'])}",
                  file=sys.stderr, flush=True)
        line["metrics"] = metric_values(cell, facts, bool(args.trace),
                                        args.rehearse_cpu)
        line["device"] = dev
        print(json.dumps(line), file=out, flush=True)
        return 0
    except BaseException:  # noqa: BLE001 — reported, then exit code 1
        traceback.print_exc()
        return 1
    finally:
        sys.stdout = sys.__stdout__
        shutil.rmtree(work_dir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
