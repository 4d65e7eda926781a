"""Device busy milliseconds per query: the share of the trace in which an
operation ran on the device (busy = union of the device-operation
intervals, over the span from the trace's first device operation to its
last), over the rate at which the whole window completed correct queries.
A share and a rate, so neither the two clocks nor the seconds the
profiler takes to stop enter it, and a batch that lands just outside the
trace does not halve it. Needs a trace with a device plane."""


def read(ctx: dict):
    tr = ctx.get("trace")
    if not tr or not tr.get("device_plane") or not tr.get("device_span_s") \
            or not ctx.get("completed_qps"):
        return None
    return 1e3 * tr["busy_s"] / tr["device_span_s"] / ctx["completed_qps"]
