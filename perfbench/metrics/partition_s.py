"""Seconds of stage 1 (``partition.partition_edges``, host numpy) per job,
from the harness's host-clock span."""


def read(ctx):
    t = [j.spans["partition"] for j in ctx.jobs if "partition" in j.spans]
    return sum(t) / len(t) if t else None
