"""Share of the ring program's device time spent in collectives (the
``ppermute`` exchange and the per-round ``pmax``): collective op time over
busy time inside the harness's ``ring`` span, on the chip where it is
highest."""
from perfbench import tracing


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    spans = ctx.trace.span_intervals("ring")
    if not spans:
        return None
    shares = []
    for d in ctx.trace.devices:
        coll = busy = 0.0
        for lo, hi in spans:
            coll += tracing.op_seconds(d, lambda o: o.collective, lo, hi)
            busy += sum(e - s for s, e in d.busy(lo, hi)) / 1e9
        if busy > 0:
            shares.append(100.0 * coll / busy)
    return max(shares) if shares else None
