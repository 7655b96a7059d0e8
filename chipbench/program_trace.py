#!/usr/bin/env python3
"""The program's own names in a profiler trace, and the per-layer numbers
read from them.

``trace_reduce.load`` keeps the device operations under XLA's names and the
benchmark's ``chipbench.`` host spans.  ``load`` here keeps two more
things, under keys of their own, and leaves the others as they are:

- ``program_spans``: the program's host spans (names that start with
  ``PROGRAM_PREFIX``, such as ``repro.train.data``) as [name, start,
  duration], on the device trace's clock;
- ``scopes``: for each device operation's name, its ``op_name`` path,
  which holds the ``jax.named_scope`` names the program puts around its
  layers (``repro.attention``, ``repro.mlp``, ``repro.head``,
  ``repro.loss``, ``repro.optimizer``) and the phase it ran in.  A v5e
  trace's op events carry no such path, so it comes from the HLO text of
  the compiled step (``hlo_scopes``), whose instruction names are the
  trace's op names.

A reduced trace without these keys, or whose paths hold none of the
scopes, still loads, and gives no value for the numbers that need them.

    python3 chipbench/program_trace.py --workload <cell> --seed <n> \
        --seconds <s> --out <file.json.gz>

sets the cell up as ``run.py`` does, times an untraced window, then runs
the traced stretch and its reduction, saves the reduced trace to ``--out``
and prints the numbers below, the breakdown and each step's phases.  It
stands in for ``run.py --trace 1`` until that reads ``load``; then only
the reduction and the readers stay.
"""
from __future__ import annotations

import bisect
import re
import sys
from pathlib import Path
from typing import Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import trace_reduce  # noqa: E402

PROGRAM_PREFIX = "repro."
TRAIN_CALL = "chipbench.train_call"
DATA_SPAN = "repro.train.data"
SCOPES = ("repro.attention", "repro.mlp", "repro.head", "repro.loss",
          "repro.optimizer")
# The op_name metadata of an HLO instruction, and the computation a
# fusion or call runs.
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_OPERANDS = re.compile(r"%([\w.\-]+)")


# --- reduction -----------------------------------------------------------------------

def hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """Each instruction's ``op_name`` path, from a compiled program's HLO
    text.  An instruction without one (a fusion, a bitcast) takes the path
    of the computation it calls, which is that of its root, or of its
    nearest operand that has one."""
    own: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    operands: Dict[str, List[str]] = {}
    root: Dict[str, str] = {}
    comp = None
    for line in hlo_text.splitlines():
        c = _COMPUTATION.match(line)
        if c and " = " not in line:
            comp = c.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        if line.lstrip().startswith("ROOT") and comp is not None:
            root[comp] = name
        p = _OP_NAME.search(rest)
        if p:
            own[name] = p.group(1)
        k = _CALLS.search(rest)
        if k:
            calls[name] = k.group(1)
        body = rest.split("metadata=", 1)[0]
        operands[name] = _OPERANDS.findall(body.split("(", 1)[-1])

    memo: Dict[str, Optional[str]] = {}

    def path(name: str, depth: int = 0) -> Optional[str]:
        if name in memo:
            return memo[name]
        memo[name] = None            # cut cycles
        found = own.get(name)
        if found is None and depth < 64:
            if name in calls and calls[name] in root:
                found = path(root[calls[name]], depth + 1)
            for o in operands.get(name, ()):
                if found is not None:
                    break
                found = path(o, depth + 1)
        memo[name] = found
        return found

    return {n: p for n in operands if (p := path(n)) is not None}


def load(xplane_path: str, hlo_text: Optional[str] = None) -> Dict:
    """``trace_reduce.load``'s reduced trace, with ``program_spans`` and
    ``scopes`` added, in one pass over the trace."""
    from jax.profiler import ProfileData
    devices: Dict[str, List] = {}
    spans: List = []
    program: List = []
    for plane in ProfileData.from_file(str(xplane_path)).planes:
        m = trace_reduce._DEVICE_PLANE.match(plane.name)
        host = plane.name.startswith("/host:")
        for line in plane.lines:
            if m and line.name == trace_reduce.OPS_LINE:
                devices.setdefault(f"{m.group(1)}:{m.group(2)}", []).extend(
                    [trace_reduce.op_name(e.name), e.start_ns, e.duration_ns]
                    for e in line.events)
            elif host:
                for e in line.events:
                    if e.name.startswith(trace_reduce.SPAN_PREFIX):
                        spans.append([e.name, e.start_ns, e.duration_ns])
                    elif e.name.startswith(PROGRAM_PREFIX):
                        program.append([e.name, e.start_ns, e.duration_ns])
    red = trace_reduce.reduced(devices, spans)
    scopes = hlo_scopes(hlo_text) if hlo_text else {}
    ops = {n for evs in red["devices"].values() for n, _, _ in evs}
    red["program_spans"] = sorted(program, key=lambda s: s[1])
    red["scopes"] = {n: p for n, p in scopes.items() if n in ops}
    return red


# --- names -----------------------------------------------------------------------

def phase(path: str) -> str:
    """``recompute`` for full remat's second forward, ``backward`` under a
    transpose, else ``forward``."""
    if "rematted_computation" in path:
        return "recompute"
    if "transpose(" in path:
        return "backward"
    return "forward"


def scope(path: Optional[str]) -> str:
    """The program's scope an op ran under, or ``rest``."""
    for s in SCOPES:
        if path and s in path:
            return s
    return "rest"


# --- the numbers -----------------------------------------------------------------

def steps(red: Dict) -> int:
    """The benchmark's trainer calls in the window, one step each."""
    return sum(1 for n, _, _ in red["spans"] if n == TRAIN_CALL)


def scope_s(red: Dict, pattern: str) -> float:
    """Device seconds inside the window of the ops whose path matches
    ``pattern``, averaged over devices."""
    rx = re.compile(pattern)
    hit = {n for n, p in red.get("scopes", {}).items() if rx.search(p)}
    lo, hi = red["window"]
    tot = sum(min(e, hi) - max(s, lo)
              for dev in red["devices"]
              for n, s, e in trace_reduce._ops(red, dev) if n in hit)
    return tot * 1e-9 / max(len(red["devices"]), 1)


def span_s(red: Dict, name: str) -> float:
    """Host seconds inside the window of the program's spans ``name``."""
    lo, hi = red["window"]
    return trace_reduce.length(trace_reduce.clip(
        [(s, s + d) for n, s, d in red.get("program_spans", ())
         if n == name], lo, hi)) * 1e-9


def top_ops(red: Dict, n: int = 10) -> List[List]:
    """``trace_reduce.top_ops``, each name followed by its scope and phase:
    ``fusion.591 repro.attention/recompute``, or ``rest``."""
    sc = red.get("scopes", {})
    out = []
    for name, t in trace_reduce.top_ops(red, n):
        p = sc.get(name)
        s = scope(p)
        out.append([f"{name} {s}/{phase(p)}" if s != "rest"
                    else f"{name} rest", t])
    return out


def idle_gaps(red: Dict, n: int = 10) -> List[List]:
    """``trace_reduce.idle_gaps``, with each gap cut where a program span
    starts or ends, so that a piece lies in one phase of the host's step,
    and each piece named by the innermost span of the benchmark's or the
    program's around its middle.  Uncut, a gap between two steps (the end
    of one step's ``device_get``, its log, the next batch and dispatch)
    took the name of whatever its middle fell in."""
    cuts = sorted({t for _, s, d in red.get("program_spans", ())
                   for t in (s, s + d)})
    lo, hi = red["window"]
    pieces = []
    for dev in red["devices"]:
        busy = trace_reduce.clip(
            [(s, e) for _, s, e in trace_reduce._ops(red, dev)], lo, hi)
        for s, e in trace_reduce.subtract([(lo, hi)], busy):
            edges = [s, *cuts[bisect.bisect_right(cuts, s):
                              bisect.bisect_left(cuts, e)], e]
            pieces += zip(edges, edges[1:])
    spans = red["spans"] + red.get("program_spans", [])
    named = []
    for s, e in sorted(pieces, key=lambda g: g[0] - g[1])[:n]:
        mid, inner = (s + e) / 2, None
        for name, ss, dd in spans:
            if ss <= mid <= ss + dd and (inner is None or dd < inner[1]):
                inner = (name, dd)
        named.append([inner[0] if inner else "outside the benchmark's spans",
                      (e - s) * 1e-9])
    return named


def scoped(red: Dict) -> bool:
    """Whether some op's path holds one of the program's scopes.  Not so
    where the HLO came from a build without them, such as an executable
    compiled before the scopes and handed back by the compile cache, whose
    key leaves the scope metadata out."""
    return any(scope(p) != "rest" for p in red.get("scopes", {}).values())


def _per_step_ms(pattern: str) -> Callable[[Dict], Optional[float]]:
    def read(red: Dict) -> Optional[float]:
        if not scoped(red) or not steps(red):
            return None
        return 1e3 * scope_s(red, pattern) / steps(red)
    return read


def _data_wait_share(red: Dict) -> Optional[float]:
    if not red.get("program_spans"):
        return None
    return 100.0 * span_s(red, DATA_SPAN) / trace_reduce.window_s(red)


# metric name -> reader of a reduced trace; None where it has nothing to read
METRICS: Dict[str, Callable[[Dict], Optional[float]]] = {
    "attention_ms.train": _per_step_ms(r"repro\.attention"),
    "mlp_ms.train": _per_step_ms(r"repro\.mlp"),
    "head_loss_ms.train": _per_step_ms(r"repro\.(head|loss)"),
    "optimizer_ms.train": _per_step_ms(r"repro\.optimizer"),
    "recompute_ms.train": _per_step_ms(r"rematted_computation"),
    "data_wait_share.train": _data_wait_share,
}


# --- a recording on the chip -------------------------------------------------------

def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    import glob
    import json
    import os
    import shutil
    import statistics
    import tempfile

    import run as bench

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    out = lambda d: print(json.dumps(d), flush=True)  # noqa: E731

    spec = bench.resolve(args.workload)
    w = spec["workload"]
    os.environ["REPRO_KERNEL_AUTOTUNE"] = "0"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from repro.launch.compile_cache import (enable_compile_cache,
                                            persistent_cache_disabled)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench.device_check(w["chips"], spec["peaks"])
    run = spec["kind"].Run(spec["config"], spec["traffic"], spec["cell"],
                             w["chips"], args.seed)
    run.setup()
    tr = run.tr
    n0 = len(tr.log)
    res = run.window(args.seconds)
    window = tr.log[n0:]
    slowest = max(window, key=lambda r: r["time_s"])
    out({"window": res,
         "window_compiles": sum(r["compiles"] for r in window),
         "window_gc_collections": sum(r["gc_collections"] for r in window),
         "slowest_step": {k: slowest[k] for k in (
             "step", "time_s", "data_s", "dispatch_s", "sync_s", "compiles",
             "compile_s", "gc_collections", "gc_s")}})

    d = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        n1 = len(tr.log)
        jax.profiler.start_trace(d)
        try:
            run.traced(bench.TRACE_SECONDS, bench.TRACE_CALLS)
        finally:
            jax.profiler.stop_trace()
        traced_steps = tr.log[n1:]
        batch = run.feed.batch(tr.step)
        # compiled afresh: the cache could hand back an executable whose
        # HLO lacks the scopes, or has older ones
        with jax.set_mesh(tr.mesh), persistent_cache_disabled():
            hlo = tr.step_fn.lower(tr.params, tr.opt_state,
                                   batch).compile().as_text()
        files = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)
        red = load(files[0], hlo)
    finally:
        shutil.rmtree(d, ignore_errors=True)
    trace_reduce.save(red, args.out)
    busy = statistics.fmean(trace_reduce.busy_s(red).values() or [0.0])
    k = steps(red)
    by_scope = {s: 1e3 * scope_s(red, re.escape(s)) / k for s in SCOPES}
    ops_ms = 1e3 * sum(t for _, t in trace_reduce.top_ops(red, 10 ** 6)) / k
    by_scope["rest"] = ops_ms - sum(by_scope.values())
    out({"metrics": {m: f(red) for m, f in METRICS.items()},
         "steps": k, "busy_ms_per_step": 1e3 * busy / k,
         "window_s": trace_reduce.window_s(red),
         "scope_ms_per_step": by_scope,
         "program_span_s": {n: span_s(red, n) for n in sorted(
             {s[0] for s in red["program_spans"]})},
         "traced_step_s": [r["time_s"] for r in traced_steps],
         "untraced_step_s_median": res["step_s_median"],
         "scoped_ops": len(red["scopes"]),
         "breakdown": {"device_ops": top_ops(red), "idle_gaps": idle_gaps(red)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
