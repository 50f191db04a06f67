"""Seconds per job that JAX spent tracing, lowering and compiling inside
the window (its ``/jax/core/compile/`` monitoring events)."""


def read(ctx):
    if not ctx.jobs:
        return None
    return sum(j.compile_s for j in ctx.jobs) / len(ctx.jobs)
