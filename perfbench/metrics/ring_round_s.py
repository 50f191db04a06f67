"""Seconds per ring round: the host clock around ``ring.ring_cges`` less
the seconds JAX spent tracing and compiling inside it (the ring program is
built anew on every call), over the rounds it ran, averaged over the
jobs."""


def read(ctx):
    t = [(j.spans["ring"] - j.span_compile.get("ring", 0.0)) / j.rounds
         for j in ctx.jobs if "ring" in j.spans and j.rounds]
    return sum(t) / len(t) if t else None
