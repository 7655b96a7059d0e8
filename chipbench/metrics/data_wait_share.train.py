"""``data_wait_share.train``: the share of the traced window that the host
spent in the program's ``repro.train.data`` span, fetching the next batch,
in percent (``program_trace.data_wait_share``); nothing where the trace has
none of the program's spans.
"""
import program_trace


def read(ctx):
    return program_trace.data_wait_share(ctx["trace"])
