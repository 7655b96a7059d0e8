"""The program's own names in a profiler trace, and the per-layer numbers
read from them.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into the
reduced form of ``trace_reduce`` (the device operations under XLA's names,
the benchmark's ``chipbench.`` host spans, the window), and keeps three
more things, under keys of their own:

- ``program_spans``: the program's host spans (names that start with
  ``PROGRAM_PREFIX``, such as ``repro.train.data``) as [name, start,
  duration], on the device trace's clock;
- ``scopes``: for each device operation's name, its ``op_name`` path,
  which holds the ``jax.named_scope`` names the program puts around its
  layers (``repro.attention``, ``repro.mlp``, ``repro.head``,
  ``repro.loss``, ``repro.optimizer``) and the phase it ran in.  A v5e
  trace's op events carry no such path, so it comes from the HLO text of
  the compiled step (``hlo_scopes``), whose instruction names are the
  trace's op names;
- ``kernels``: for each device operation that is a Mosaic kernel (a
  ``tpu_custom_call`` in the HLO), the shapes of its outputs, which tell
  apart kernels that one jitted function runs under one path (the flash
  backward's dQ kernel returns one array, its dK/dV kernel two).

A reduced trace without these keys, or whose paths hold none of the
scopes, still loads, and gives no value for the numbers that need them.
``run.py --trace 1`` reduces its trace with ``load``, given the HLO text of
the step that the window ran (``Run.step_hlo``).
"""
from __future__ import annotations

import bisect
import re
import sys
from pathlib import Path
from typing import Dict, List, Optional

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
_KERNEL = 'custom_call_target="tpu_custom_call"'
_SHAPE = re.compile(r"\w+\[([\d,]*)\]")


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


def hlo_kernels(hlo_text: str) -> Dict[str, List[List[int]]]:
    """Each Mosaic kernel call's output shapes, from a compiled program's
    HLO text: ``{"flash_attention_bwd.21": [[32, 2048, 64]]}``."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m or _KERNEL not in line:
            continue
        name, rest = m.groups()
        result = rest.split(" custom-call(", 1)[0]
        out[name] = [[int(n) for n in dims.split(",") if n]
                     for dims in _SHAPE.findall(result)]
    return out


def load(xplane_path: str, hlo_text: Optional[str] = None) -> Dict:
    """The trace at ``xplane_path``, reduced (``trace_reduce.reduced``),
    with ``program_spans``, ``scopes`` and ``kernels`` added, in one pass
    over the trace."""
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
    kernels = hlo_kernels(hlo_text) if hlo_text else {}
    ops = {n for evs in red["devices"].values() for n, _, _ in evs}
    red["program_spans"] = sorted(program, key=lambda s: s[1])
    red["scopes"] = {n: p for n, p in scopes.items() if n in ops}
    red["kernels"] = {n: k for n, k in kernels.items() if n in ops}
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
    """The longest stretches in which a device ran nothing, as [name,
    seconds].  Each gap is cut where a program span starts or ends, so
    that a piece lies in one phase of the host's step, and each piece is
    named by the innermost span of the benchmark's or the program's around
    its middle.  Uncut, a gap between two steps (the end of one step's
    ``device_get``, its log, the next batch and dispatch) took the name of
    whatever its middle fell in."""
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


def per_step_ms(red: Dict, pattern: str) -> Optional[float]:
    """Device milliseconds a step of the ops whose path matches
    ``pattern``; None where the paths hold none of the program's scopes or
    no trainer call ran."""
    if not scoped(red) or not steps(red):
        return None
    return 1e3 * scope_s(red, pattern) / steps(red)


def data_wait_share(red: Dict) -> Optional[float]:
    """The share of the window spent in the program's ``repro.train.data``
    span, in percent; None where the trace has none of its spans."""
    if not red.get("program_spans"):
        return None
    return 100.0 * span_s(red, DATA_SPAN) / trace_reduce.window_s(red)
