"""Share of the chips' busy time spent in the BDeu Pallas kernels
(``bdeu_sweep_insert``, ``bdeu_sweep_delete``, ``bdeu_count``); the rest
is XLA ops: the lgamma reduction, argmax, fusion."""
from perfbench import tracing


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    lo, hi = ctx.trace.window
    kern = sum(tracing.op_seconds(d, lambda o: o.name in tracing.KERNELS, lo, hi)
               for d in ctx.trace.devices)
    busy = sum(tracing.busy_seconds(ctx.trace))
    return 100.0 * kern / busy if kern > 0 and busy > 0 else None
