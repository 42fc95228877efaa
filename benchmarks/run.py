"""One cell of the benchmark, once: load, warm up, measure for
``--seconds``, print one line of JSON, exit.

    python3 -m benchmarks.run --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Everything a cell is made of is found by name from ``BENCHMARK.json``:
its configuration (``configs/<config>.json``, which names its
``builder``), its traffic (``traffic/<traffic>.json``, whose ``kind``
names the module under ``kinds/`` that drives it) and, in a traced
run, its per-layer metrics (``layer_metrics/<metric>.json``, each
naming its ``reader``).  This file holds no list of its own; see
README.md for how a later PR adds to each.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` is a
run of its own with the profiler on for a short steady slice, and
prints the cell's per-layer metrics, the ``breakdown`` and, on
``[bench]`` lines, the device time by the program's own scopes.  Without a
TPU, or with a device that ``peaks.json`` has no row for, the command
exits non-zero and prints no result line: it never falls back to the
CPU.
"""
from __future__ import annotations

import time

_PROCESS_T0 = time.perf_counter()     # before jax is imported

import argparse                        # noqa: E402
import importlib                       # noqa: E402
import json                            # noqa: E402
import os                              # noqa: E402
import shutil                          # noqa: E402
import sys                             # noqa: E402
import tempfile                        # noqa: E402
from typing import Optional            # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def resolve(name: str):
    """``module:function`` -> the function."""
    module, function = name.split(":")
    return getattr(importlib.import_module(module), function)


def find_cell(bench: dict, workload: str):
    """(cell, configuration file's contents, traffic file's contents)."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; "
                         f"there are: {', '.join(cells)}")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return (cell, load_json(ROOT, entry["file"]),
            load_json(HERE, "traffic", cell["traffic"] + ".json"))


def metrics_of(bench: dict, group: str, workload: str):
    """The metrics of ``end_to_end`` or ``per_layer`` that this cell
    reports: those that list it, and those that list no cells."""
    return [m for m in bench[group]
            if workload in m.get("workloads", [workload])]


def layer_metrics(bench: dict, workload: str, trace, facts: dict,
                  peaks: dict) -> dict:
    """Every per-layer metric of the cell through its own reader; a
    reader that finds nothing to read returns None and the metric is
    left out."""
    out = {}
    for m in metrics_of(bench, "per_layer", workload):
        spec = load_json(HERE, "layer_metrics", m["name"] + ".json")
        value = resolve(spec["reader"])(trace, facts, spec["params"],
                                        peaks)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             overrides: Optional[dict] = None,
             require_tpu: bool = True) -> Optional[dict]:
    """Run one cell and return the result line's object, or None (after
    a message on standard error) when the machine cannot run it.

    ``overrides`` and ``require_tpu`` are for the CPU tests alone:
    ``{"config": {...}, "traffic": {...}}`` is merged over the cell's
    files to give tiny sizes.  The command passes neither."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cell, config, traffic = find_cell(bench, workload)
    for part, target in (("config", config), ("traffic", traffic)):
        for key, value in (overrides or {}).get(part, {}).items():
            if isinstance(value, dict) and isinstance(target.get(key),
                                                      dict):
                target[key] = {**target[key], **value}
            else:
                target[key] = value

    import jax

    devices = jax.devices()
    d = devices[0]
    all_peaks = load_json(HERE, "peaks.json")
    if require_tpu and d.platform != "tpu":
        print(f"[bench] no TPU: jax found {len(devices)} {d.platform} "
              f"device(s); the benchmark does not fall back",
              file=sys.stderr)
        return None
    if len(devices) < cell["chips"]:
        print(f"[bench] {workload} needs {cell['chips']} chips, jax "
              f"found {len(devices)}", file=sys.stderr)
        return None
    if d.device_kind not in all_peaks and require_tpu:
        print(f"[bench] no row for device kind {d.device_kind!r} in "
              f"peaks.json", file=sys.stderr)
        return None
    peaks = all_peaks.get(d.device_kind) \
        or next(iter(all_peaks.values()))      # CPU tests only

    from apex_tpu.utils.compile_cache import configure_compile_cache

    from .common import say
    from .trace import breakdown, busy_seconds, scope_ms

    say(compile_cache=configure_compile_cache())
    say(workload=workload, kind=traffic["kind"], seed=seed,
        seconds=seconds, trace=int(trace), platform=d.platform,
        device_kind=repr(d.device_kind), devices=len(devices),
        chips=cell["chips"])
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        t0 = time.perf_counter()
        job = resolve(config["builder"])(config, traffic, seed)
        build_s = time.perf_counter() - t0
        kind = importlib.import_module(
            f"benchmarks.kinds.{traffic['kind']}")
        result = kind.run(job, traffic, seed=seed, seconds=seconds,
                          trace_dir=trace_dir, platform=d.platform,
                          peaks=peaks)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    setup_s = result.window_opened_at - _PROCESS_T0
    used = devices[:cell["chips"]]
    peak_bytes = max(((dev.memory_stats() or {}).get(
        "peak_bytes_in_use", 0) for dev in used), default=0)
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    values = dict(result.end_to_end, setup_s=setup_s)
    say(build_s=round(build_s, 1), setup_s=round(setup_s, 1),
        memory_peak_bytes=peak_bytes,
        **({"faults": json.dumps(result.faults)} if result.faults else {}))
    line = {"correct": result.correct, "attempted": result.attempted,
            "failed": result.failed}
    if not trace:
        line["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in metrics_of(bench, "end_to_end", workload)
            if m["name"] in values}
    else:
        line["metrics"] = layer_metrics(bench, workload, result.trace,
                                        result.facts, peaks)
        if result.trace is not None:
            device["busy_s"] = busy_seconds(result.trace)
            device["window_s"] = result.trace.window_s
            line["breakdown"] = breakdown(result.trace)
            # where the time sits by the program's own scopes, and the
            # three largest operations split the same way: an op kind
            # and a shape alone do not say whose work a fusion is
            say(scope_ms=json.dumps(_rounded(scope_ms(result.trace))))
            say(scope_ms_of_top_ops=json.dumps({
                name: _rounded(scope_ms(result.trace, top=4, op=name))
                for name, _ in line["breakdown"]["device_ops"][:3]}))
        if result.facts.get("roofline_bound"):
            say(roofline_bound=json.dumps(result.facts["roofline_bound"]))
    line["device"] = device
    return line


def _rounded(pairs):
    return [[name, round(ms, 3)] for name, ms in pairs]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    line = run_cell(args.workload, args.seed, args.seconds,
                    bool(args.trace))
    if line is None:
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
