"""Reduction of a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
three things, on one clock in nanoseconds:

- ``devices``: for each accelerator, every XLA operation that ran on it
  (line ``XLA Ops`` of a ``/device:<kind>:<n>`` plane) as (name, start,
  duration); an event that encloses others on its line (a loop or
  conditional around its body's operations) is left out, so that time is
  counted once, where the work ran;
- ``spans``: the benchmark's own host spans (``TraceAnnotation`` names
  that start with ``SPAN_PREFIX``) as (name, start, duration);
- ``window``: (start, end) of the traced window, from the first span's
  start to the last span's end.

That reduced form is plain JSON (``save``/``load_reduced``), so a recorded
trace can be kept small and the arithmetic below tested on it.  Busy time
is the union of a device's operation intervals inside the window; a
collective's exposed time is the part of its intervals that no other
operation of that device covers.
"""
from __future__ import annotations

import gzip
import json
import re
from typing import Dict, List, Sequence, Tuple

SPAN_PREFIX = "chipbench."
OPS_LINE = "XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
# XLA's names for operations that move data between devices, with their
# asynchronous -start/-done halves and fused forms.
_COLLECTIVE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all|"
    r"ragged-all-to-all|send|recv)", re.IGNORECASE)

Interval = Tuple[float, float]


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``: the HLO
    instruction's name without its text, whose operands may name other
    operations."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def is_collective(op_name: str) -> bool:
    return bool(_COLLECTIVE.search(op_name))


def load(xplane_path: str) -> Dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(xplane_path))
    devices: Dict[str, List] = {}
    spans: List = []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices.setdefault(f"{m.group(1)}:{m.group(2)}", []).extend(
                    [op_name(e.name), e.start_ns, e.duration_ns]
                    for e in line.events)
            elif plane.name.startswith("/host:"):
                spans.extend([e.name, e.start_ns, e.duration_ns]
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return reduced(devices, spans)


def leaf_events(events: List) -> List:
    """The events that enclose no other event of the list."""
    evs = sorted((e for e in events if e[2] > 0), key=lambda e: (e[1], -e[2]))
    parent = [False] * len(evs)
    open_: List[int] = []
    for i, (_, s, d) in enumerate(evs):
        while open_ and evs[open_[-1]][1] + evs[open_[-1]][2] <= s:
            open_.pop()
        if open_ and s + d <= evs[open_[-1]][1] + evs[open_[-1]][2]:
            parent[open_[-1]] = True
        open_.append(i)
    return [e for e, p in zip(evs, parent) if not p]


def reduced(devices: Dict[str, List], spans: List) -> Dict:
    devices = {k: leaf_events(v) for k, v in devices.items()}
    spans = sorted(spans, key=lambda s: s[1])
    window = ([spans[0][1], max(s[1] + s[2] for s in spans)]
              if spans else None)
    return {"devices": {k: sorted(v, key=lambda e: e[1])
                        for k, v in sorted(devices.items())},
            "spans": spans, "window": window}


def save(red: Dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(red, f)


def load_reduced(path: str) -> Dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# --- interval arithmetic ------------------------------------------------------------

def merge(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in merge(intervals))


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of the merged ``a`` that the merged ``b`` does not cover."""
    out, b = [], merge(b)
    for s, e in merge(a):
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def _ops(red: Dict, dev: str) -> List[Tuple[str, float, float]]:
    lo, hi = red["window"]
    return [(n, s, s + d) for n, s, d in red["devices"][dev]
            if s + d > lo and s < hi]


# --- the numbers ---------------------------------------------------------------------

def window_s(red: Dict) -> float:
    lo, hi = red["window"]
    return (hi - lo) * 1e-9


def busy_s(red: Dict) -> Dict[str, float]:
    """Seconds of the window in which some operation ran, per device."""
    lo, hi = red["window"]
    return {dev: length(clip([(s, e) for _, s, e in _ops(red, dev)], lo, hi))
            * 1e-9 for dev in red["devices"]}


def collective_exposed_s(red: Dict) -> Dict[str, float]:
    """Seconds of collective operations that no other operation of the same
    device overlaps, per device; empty where no collective ran."""
    lo, hi = red["window"]
    out = {}
    for dev in red["devices"]:
        ops = _ops(red, dev)
        coll = clip([(s, e) for n, s, e in ops if is_collective(n)], lo, hi)
        if not coll:
            continue
        comp = [(s, e) for n, s, e in ops if not is_collective(n)]
        out[dev] = length(subtract(coll, comp)) * 1e-9
    return out


def top_ops(red: Dict, n: int = 10) -> List[List]:
    """The operations that took most device time inside the window, as
    [name, seconds averaged over devices]."""
    lo, hi = red["window"]
    tot: Dict[str, float] = {}
    for dev in red["devices"]:
        for name, s, e in _ops(red, dev):
            tot[name] = tot.get(name, 0.0) + (min(e, hi) - max(s, lo)) * 1e-9
    k = max(len(red["devices"]), 1)
    return [[name, t / k] for name, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(red: Dict, n: int = 10) -> List[List]:
    """The longest stretches in which a device ran nothing, as [the
    innermost host span around the gap's middle, seconds]."""
    lo, hi = red["window"]
    gaps = []
    for dev in red["devices"]:
        busy = clip([(s, e) for _, s, e in _ops(red, dev)], lo, hi)
        gaps += subtract([(lo, hi)], busy)
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid, inner = (s + e) / 2, None
        for name, ss, dd in red["spans"]:
            if ss <= mid <= ss + dd and (inner is None or dd < inner[1]):
                inner = (name, dd)
        named.append([inner[0] if inner else "outside the benchmark's spans",
                      (e - s) * 1e-9])
    return named
