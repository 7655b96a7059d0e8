#!/usr/bin/env python3
"""The chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for.  Everything is found by name:

- the cell in ``BENCHMARK.json``'s ``workloads``, and its own file
  ``chipbench/cells/<cell>.json`` (mesh, reference layout, limits);
- its configuration's file, named in ``configs``;
- its traffic mix ``chipbench/traffic/<traffic>.json``, whose ``kind``
  names the module that runs it, ``chipbench/kinds/<kind>.py``;
- the FLOP count ``chipbench/flops/<family>.py`` of the model's family;
- each per-layer metric's reader ``chipbench/metrics/<metric>.py``.

An unknown name is an error.  A run that finds no TPU, fewer chips than
the cell asks for, or a ``device_kind`` missing from ``peaks.json`` exits
with code 1 and prints no result.

Set-up (counted in ``setup_s``) builds the system, makes the weights from
the seed and runs the steps that the check follows, which also compiles
every program the window uses.  The window then runs for ``--seconds``.
With ``--trace 1`` a further short stretch runs under the profiler, and
the per-layer metrics are read from it.  The program's state is then
freed and the plain reference decides ``correct``.  The last line of
standard output is the result, and the last lines of standard error are
the numbers compared, each beside its limit.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

# A traced stretch covers at least this long and this many calls.
TRACE_SECONDS = 2.0
TRACE_CALLS = 3


def _json(path: Path) -> Any:
    if not path.is_file():
        raise LookupError(f"{path.relative_to(ROOT)} does not exist")
    return json.loads(path.read_text())


def find(entries: List[Dict], name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise LookupError(f"BENCHMARK.json has no {what} named {name!r}")


def load_module(path: Path):
    if not path.is_file():
        raise LookupError(f"{path.relative_to(ROOT)} does not exist")
    name = "chipbench_" + "".join(c if c.isalnum() else "_"
                                  for c in str(path.relative_to(HERE)))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(entry: Dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def resolve(workload: str, root: Path = ROOT) -> Dict[str, Any]:
    """Everything a run of ``workload`` needs, found by name."""
    bench = _json(root / "BENCHMARK.json")
    w = find(bench["workloads"], workload, "workload")
    c = find(bench["configs"], w["config"], "configuration")
    config = _json(root / c["file"])
    traffic = _json(HERE / "traffic" / f"{w['traffic']}.json")
    return {
        "bench": bench, "workload": w, "config": config, "traffic": traffic,
        "cell": _json(HERE / "cells" / f"{workload}.json"),
        "kind": load_module(HERE / "kinds" / f"{traffic['kind']}.py"),
        "flops": load_module(HERE / "flops" /
                             f"{config['model']['family']}.py"),
        "peaks": _json(HERE / "peaks.json"),
        "end_to_end": [m for m in bench["end_to_end"]
                       if applies(m, workload)],
        "per_layer": [dict(m, reader=load_module(
            HERE / "metrics" / f"{m['name']}.py"))
            for m in bench["per_layer"] if applies(m, workload)],
    }


def device_check(chips: int, peaks: Dict) -> tuple:
    """The cell's devices and their peaks; exits where they are not a TPU
    of a kind in the peaks table, or too few."""
    import jax
    devs = jax.devices()
    kind = devs[0].device_kind
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devs[0].platform!r} "
                         f"({kind!r})")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    if kind not in peaks["devices"]:
        raise SystemExit(f"device kind {kind!r} is not in peaks.json")
    return devs, peaks["devices"][kind]


def traced(run) -> Dict:
    """A short stretch of the window's own calls under the profiler,
    reduced."""
    import jax
    import trace_reduce
    d = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        jax.profiler.start_trace(d)
        try:
            run.traced(TRACE_SECONDS, TRACE_CALLS)
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"expected one trace file, found {files}")
        return trace_reduce.load(files[0])
    finally:
        shutil.rmtree(d, ignore_errors=True)


def per_layer(spec: Dict, ctx: Dict) -> Dict[str, Dict]:
    out = {}
    for m in spec["per_layer"]:
        v = m["reader"].read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def main(argv: Optional[List[str]] = None, *,
         chip_check: Callable = device_check) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    err = lambda s: print(s, file=sys.stderr, flush=True)  # noqa: E731

    spec = resolve(args.workload)
    w = spec["workload"]
    os.environ["REPRO_KERNEL_AUTOTUNE"] = "0"       # fixed tiles, no ~/.cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs in /tmp
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    import compare
    import trace_reduce
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs, peak = chip_check(w["chips"], spec["peaks"])
    used = devs[:w["chips"]]
    err(f"chipbench: {w['name']} seed={args.seed} on {len(devs)} x "
        f"{devs[0].device_kind!r}, compile cache {cache}")

    run = spec["kind"].Run(spec["config"], spec["traffic"], spec["cell"],
                             w["chips"], args.seed)
    run.setup()
    setup_s = time.perf_counter() - T0
    res = run.window(args.seconds)
    err(f"chipbench: window {res}")
    red = traced(run) if args.trace else None
    peak_bytes = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in used]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": max(peak_bytes)}
    result: Dict[str, Any] = {}
    if args.trace:
        print(json.dumps({"peak_bytes_in_use": {
            str(d.id): b for d, b in zip(used, peak_bytes)}}))
        if hasattr(run, "simulated"):
            print(json.dumps({"simulator_vs_chip": run.simulated(
                peak_bytes, res["seconds"] / res["steps"])}))
        busy = trace_reduce.busy_s(red)
        device["busy_s"] = statistics.fmean(busy.values()) if busy else 0.0
        device["window_s"] = trace_reduce.window_s(red)
        model = spec["config"]["model"]
        ctx = {"result": res, "chips": w["chips"], "peak": peak, "trace": red,
               "flops_per_token": spec["flops"].flops_per_token(
                   model, spec["traffic"]["seq_len"])}
        metrics = per_layer(spec, ctx)
        result["breakdown"] = {"device_ops": trace_reduce.top_ops(red),
                               "idle_gaps": trace_reduce.idle_gaps(red)}
    else:
        res["setup_s"] = setup_s
        metrics = {m["name"]: {"value": res[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"]}

    run.free()
    t_ref = time.perf_counter()
    values = run.readings(run.reference())
    err(f"chipbench: reference {time.perf_counter() - t_ref:.1f} s")
    checks = compare.check(values, spec["cell"]["limits"])
    correct = compare.passed(checks) and res["failed"] == 0
    for k, c in checks.items():
        err(f"check {k} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps({"correct": correct, "attempted": res["steps"],
                      "failed": res["failed"], "metrics": metrics,
                      "device": device, **result, "checks": checks}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
