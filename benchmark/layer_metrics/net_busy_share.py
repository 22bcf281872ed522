"""Share of the device's busy time under `gt.net.*`: the SEND / NET_RECV
mailbox rings and the NoC latency models, user and memory network
(lib/scope_trace.py)."""

from lib import scope_trace


def read(ctx):
    return scope_trace.share(ctx, lambda s: s.startswith("gt.net."))
