"""``flash_attention_roofline.train``: the flash-attention kernels' share of
their roofline over the traced steps, in percent.

Each kernel op event in the trace is one call.  Its kind comes from its
name, which the trace takes from the jitted function that runs it:
``flash_attention_fwd.*`` is the forward kernel (full remat's recompute
runs one more), ``flash_attention_bwd.*`` the dQ kernel where the step's
HLO gives it one output and the dK/dV kernel where it gives two
(``program_trace.hlo_kernels``).  A call's rows times heads is its first
output's size over the sequence length times the head width, whatever
the layout.  A call's roofline time is the larger of its FLOPs over the
chip's bf16 peak and its bytes over the chip's HBM bandwidth
(``peaks.json``), its FLOPs and bytes those of the configuration's FLOP
module (``attention_kernels``).  The share is the sum of the events'
roofline times over the sum of their device times.

Beside the value the reader gives which of the two bounds the summed
roofline time, and for each kind its calls a step, device time and share.
Nothing where no kernel ran.  Nothing either, with a note that says why,
where a Mosaic kernel under ``repro.attention`` is not one of the three
kinds, or a call is not of the size the count is of: the count would not
be of the work that ran.
"""
import math
import re

import program_trace

_NAME = re.compile(r"^flash_attention_(fwd|bwd)\.\d+$")
_BWD = {1: "dq", 2: "dkv"}


def kinds(red):
    """Each kernel op of the trace -> ``fwd``, ``dq`` or ``dkv``, and the
    kernels under ``repro.attention`` that are none of them."""
    out, unknown = {}, []
    for name, shapes in red.get("kernels", {}).items():
        m = _NAME.match(name)
        kind = m and ("fwd" if m.group(1) == "fwd" else _BWD.get(len(shapes)))
        if kind:
            out[name] = kind
        elif m or program_trace.scope(
                red.get("scopes", {}).get(name)) == "repro.attention":
            unknown.append(f"{name} ({len(shapes)} outputs)")
    return out, unknown


def rows_heads(shape, seq, width):
    """Rows times heads of an output holding ``seq`` x ``width`` numbers
    per row and head, in any layout; None where it does not."""
    size = math.prod(shape)
    if seq not in shape or size % (seq * width):
        return None
    return size // (seq * width)


def read(ctx):
    red = ctx["trace"]
    kind, unknown = kinds(red)
    if unknown:
        return {"value": None, "note": "kernels under repro.attention that "
                "the count does not know: " + ", ".join(sorted(unknown))}
    steps = program_trace.steps(red)
    if not kind or not steps:
        return None
    model, traffic, peak = ctx["config"]["model"], ctx["traffic"], ctx["peak"]
    work = ctx["flops"].attention_kernels(model, traffic)
    seq, width = traffic["seq_len"], model["head_dim"]
    want = traffic["global_batch"] // traffic["num_micro"] * model["n_heads"]
    for name, k in sorted(kind.items()):
        out = red["kernels"][name][0]
        if rows_heads(out, seq, width) != want:
            return {"value": None, "note": f"{name} ({k}) returns {out}: not "
                    f"the call of {want} rows x heads of {seq} positions of "
                    f"width {width} that the count is of"}
    lo, hi = red["window"]
    per = {k: {"calls": 0, "device_s": 0.0, "compute_s": 0.0, "memory_s": 0.0}
           for k in set(kind.values())}
    for evs in red["devices"].values():
        for name, s, d in evs:
            if name in kind and lo <= s and s + d <= hi:
                p, w = per[kind[name]], work[kind[name]]
                p["calls"] += 1
                p["device_s"] += d * 1e-9
                p["compute_s"] += w["flops"] / peak["bf16_flops_per_s"]
                p["memory_s"] += w["bytes"] / peak["hbm_bytes_per_s"]
    spent = sum(p["device_s"] for p in per.values())
    if not spent:
        return None
    chips = len(red["devices"])
    notes = {}
    for k, p in sorted(per.items()):
        best = max(p["compute_s"], p["memory_s"])
        notes[k] = {"calls_per_step": p["calls"] / steps / chips,
                    "device_ms_per_step": 1e3 * p["device_s"] / steps / chips,
                    "roofline_share": 100.0 * best / p["device_s"],
                    "bound": ("compute" if p["compute_s"] >= p["memory_s"]
                              else "memory")}
    compute = sum(p["compute_s"] for p in per.values())
    memory = sum(p["memory_s"] for p in per.values())
    best = sum(max(p["compute_s"], p["memory_s"]) for p in per.values())
    return {"value": 100.0 * best / spent,
            "bound": "compute" if compute >= memory else "memory",
            "kernels": notes}
