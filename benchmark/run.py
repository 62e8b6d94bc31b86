"""One run of one cell of BENCHMARK.json:

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, on the machine it is started on. It fails at once, printing no
result, unless JAX finds a TPU with the chips the cell asks for whose
`device_kind` is in benchmark/peaks.json. Set-up (loading, warming up,
compiling) counts as `setup_s`; the window lasts `--seconds`; then the
program's state is freed, the device's memory peak is read, and the plain
reference decides `correct`. The last line of standard output is the
result; the numbers compared, each beside its limit, are the last lines of
standard error and the last key of the result.
"""

from __future__ import annotations

import time

T_START = time.time()  # process start, as near as Python can see it

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

from benchmark import harness  # noqa: E402
from benchmark.harness import Context  # noqa: E402

WATCHDOG_S = 1150  # a first run may take 1200 s; a hang ends with stacks


def run_cell(cell: dict, ctx: Context) -> dict:
    """Everything after the look for a chip: the cell's traffic kind drives
    the window, the configuration's family decides `correct`, the readers
    named in BENCHMARK.json give the metrics. Returns the result line."""
    kind = harness.load_by_name("traffic.kinds", cell["traffic_data"]["kind"])
    result = kind.run(cell, ctx)
    device = harness.device_record(ctx.devices)  # before the reference runs
    family = harness.load_by_name("correct", cell["config_data"]["family"])
    t_check = time.time()
    checks = family.check(cell, ctx, result)
    check_s = time.time() - t_check
    within = harness.within_limits(checks)
    metrics, breakdown = {}, None
    if ctx.trace:
        for m in cell["per_layer"]:
            reader = harness.load_by_name("layer_metrics", m["name"])
            value = reader.read(result, cell, ctx.peaks)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        from benchmark.layer_metrics import _shared

        reduced = _shared.reduced(result)
        if reduced and reduced.get("n_devices"):
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            breakdown = {"device_ops": reduced["device_ops"],
                         "idle_gaps": reduced["idle_gaps"]}
    else:
        values = {"setup_s": result["setup_s"], **result["end_to_end"]}
        for m in cell["end_to_end"]:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    line = {"correct": within and result["attempted"] > 0,
            "attempted": result["attempted"], "failed": result["failed"],
            "metrics": metrics, "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    line["info"] = {"setup_s": result["setup_s"], "check_s": check_s,
                    **harness.memory_parts(ctx.devices),
                    "compiles_in_window": result["compile"]["programs"],
                    **result.get("info", {}), **result.get("check_info", {})}
    line["checks"] = checks
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True,
                                      file=sys.__stderr__)
    try:
        cell = harness.load_cell(args.workload)
        devices, peaks = harness.find_chip(cell["chips"])
        out_dir = os.path.join(harness.HERE, "out")
        os.makedirs(out_dir, exist_ok=True)
        meter = harness.CompileMeter()
        ctx = Context(devices=devices, peaks=peaks, meter=meter,
                      seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), out_dir=out_dir,
                      t_start=T_START)
        line = run_cell(cell, ctx)
        meter.close()
    except harness.BenchmarkError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    finally:
        faulthandler.cancel_dump_traceback_later()
    sys.stdout.flush()
    for name, c in line["checks"].items():
        print(f"check {name}: value {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
