"""``head_loss_ms.train``: device milliseconds a step of the operations
whose path in the step's HLO holds ``repro.head`` or ``repro.loss``: the
projection onto the vocabulary and the cross-entropy. Summed over every
phase, averaged over the cell's chips, per benchmark trainer call in the
traced window (``program_trace.per_step_ms``); nothing where the trace has
no op scopes.
"""
import program_trace


def read(ctx):
    return program_trace.per_step_ms(ctx["trace"], r"repro\.(head|loss)")
