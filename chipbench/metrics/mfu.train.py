"""``mfu.train``: the whole training step's share of the chips' peak.

Model FLOPs per token (``flops/<family>.py``, no recomputation counted)
times the window's trained tokens per second, over the chips' summed
bf16 peak from ``peaks.json``, in percent.  Read from the untraced window
of the run, so the profiler's own cost is not in it.
"""


def read(ctx):
    rate = ctx["result"].get("train_tokens_per_s")
    if not rate:
        return None
    peak = ctx["chips"] * ctx["peak"]["bf16_flops_per_s"]
    return 100.0 * ctx["flops_per_token"] * rate / peak
