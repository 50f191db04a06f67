#!/usr/bin/env python3
"""The control of the correctness check: the plain reference put in the
program's place, with BDeu computed in bfloat16 (the precision below the
float32 the configurations state).

    python3 perfbench/control.py --workload pigs-cges-l4 --seeds 11,12,13

For each seed it drives one whole run of the cell (``harness.main``, a
window of one job) with the job's learner replaced by the reference in
bfloat16, and prints the run's result line: ``correct`` must come out
false, and ``checks`` holds the control's reading of every compared number.
The CPU tests plant the same learner (``tests/faults.py``).

In place of GES it learns GES from the empty graph.  In place of cGES it
takes the reference partition, makes each member's step of the last round
from the program's graphs of the round before (the same starts the check
gives the reference), and fine-tunes from the best member, every score in
bfloat16.  It needs the cell's chips for that replay; the benchmark's own
runs never run it.
"""
from __future__ import annotations

import argparse
import contextlib
import os
import sys


def learn(runner):
    """The bfloat16 reference's answer to ``runner``'s job."""
    import numpy as np

    from perfbench import reference as ref
    from perfbench.checks import Answer

    cfg, p = runner.cfg, runner.problem
    ess, maxp, maxq = float(cfg["ess"]), int(cfg["max_parents"]), int(
        cfg["max_q"])
    low = "bfloat16"
    if runner.algo == "ges":
        adj, score = ref.ges(p.data, p.arities, ess, maxp, maxq, low)
        return Answer(adj=adj, score=score)
    k, n = int(runner.traffic["k"]), p.data.shape[1]
    limit = ref.add_limit(n, k) if runner.traffic["limit"] else None
    masks = ref.partition(p.data, p.arities, k, ess)
    rounds = int(cfg["max_rounds"])
    prev = np.asarray(runner.replay(masks, rounds - 1), dtype=bool)
    members, scores = [], []
    for i in range(k):
        g, s = ref.ges(p.data, p.arities, ess, maxp, maxq, low,
                       start=ref.fuse(prev[i], prev[(i - 1) % k]),
                       allowed=masks[i], limit=limit)
        members.append(g)
        scores.append(s)
    adj, score = ref.ges(p.data, p.arities, ess, maxp, maxq, low,
                         start=members[int(np.argmax(scores))])
    return Answer(adj=adj, score=score, members=np.stack(members),
                  member_scores=np.asarray(scores), masks=masks,
                  rounds=rounds)


@contextlib.contextmanager
def planted():
    """Every job of a run answers with ``learn``; the warm-up's answer is
    kept for the window's job of the same dataset."""
    from perfbench import jobs

    real = jobs.Runner.run
    kept = {}

    def run(self):
        key = id(self.problem)
        if key not in kept:
            kept[key] = (self.problem, learn(self))
        return kept[key][1]

    jobs.Runner.run = run
    try:
        yield
    finally:
        jobs.Runner.run = real


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from perfbench.harness import main as run_cell

    rc = 0
    for seed in args.seeds.split(","):
        with planted():
            rc |= run_cell(["--workload", args.workload, "--seed", seed,
                            "--seconds", "0"])
    return rc


if __name__ == "__main__":
    sys.exit(main())
