"""Roofline share of the FES insert sweep kernel ``bdeu_sweep_insert``: the
least time its calls require over their summed device time.

Each call's required work is ``roofline.insert_sweep_work`` at the
narrowest candidate width any sweep of the job makes (the restricted W of a
ring member, else n) and the smallest arity, so the share is a lower bound
on every call's own; the value says which bound (``hbm`` or ``compute``)
set the least time."""
from perfbench import roofline, tracing

KERNEL = "bdeu_sweep_insert"


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices or ctx.peaks is None:
        return None
    lo, hi = ctx.trace.window
    s = ctx.shapes
    nbytes, ops = roofline.insert_sweep_work(
        s["m"], min(s["insert_widths"]), s["r_min"], s["r_min"])
    least = spent = 0.0
    bound = None
    for d in ctx.trace.devices:
        calls = tracing.op_count(d, KERNEL)
        t, bound = roofline.least_seconds(nbytes, ops, ctx.peaks)
        least += calls * t
        spent += tracing.op_seconds(d, lambda o: o.name == KERNEL, lo, hi)
    if spent <= 0:
        return None
    return {"value": 100.0 * least / spent, "bound": bound}
