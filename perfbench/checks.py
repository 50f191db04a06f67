"""How ``correct`` is decided: each learned DAG is judged by what it says,
and each ring member's step against the plain reference's own
(``reference.py``).

An answer is the final DAG and the BDeu the program reports for it; for
cGES also the edge partition of stage 1, the ring members' graphs and
scores of the last improving round, and the members' graphs of the round
before it (``prev``, learned again by the same entry point after the
window).  The numbers compared:

* ``score_gap``: the largest relative gap between a score the program
  reports (final DAG, each member) and the float64 BDeu of that graph.
* ``delete_gap``: the largest float64 gain that deleting one edge would
  still bring (BES ran to its end): over the final DAG's edges, and over
  each member's edges inside its own subset E_i.  0 when none gains.
* ``polish_gain``: what one more float64 GES pass (FES then BES) from the
  final DAG gains, as a share of its BDeu.  A GES optimum leaves only what
  its own BES opened up: an insertion into a family that BES changed.
* ``dag_faults``: cycles in the final DAG and the members' graphs, and
  families of the final DAG over the parent or q bound.  Exact: limit 0.
* ``mask_diff``: entries where the program's edge subsets differ from the
  reference partition.  Exact: limit 0.
* ``fusion_diff``: entries outside each member's subset E_i where its
  graph differs from the reference fusion of its own and its
  predecessor's graph of the round before.  A member's GES neither inserts
  nor deletes outside E_i, so there its graph is its fused start.  Exact:
  limit 0.
* ``member_gap``: the largest float64 BDeu by which a member's graph falls
  short of the reference's step from that fused start (GES restricted to
  E_i, FES capped at the cGES-L limit).  0 when none does.
* ``finetune_gap``: what the float64 reference fine-tune (unrestricted GES)
  from the best member (by float64 BDeu) reaches beyond the final DAG, as
  a share of its BDeu.  0 when it reaches no higher.
* ``repeat_diff``: jobs of the window whose DAG or score differ from the
  judged job's (the harness counts them).  Exact: limit 0.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from . import reference as ref

EXACT = ("dag_faults", "mask_diff", "fusion_diff", "repeat_diff")


@dataclasses.dataclass
class Answer:
    adj: np.ndarray                       # (n, n) final DAG
    score: float                          # BDeu the program reports
    members: Optional[np.ndarray] = None  # (k, n, n) member graphs
    member_scores: Optional[np.ndarray] = None
    masks: Optional[np.ndarray] = None    # (k, n, n) edge subsets used
    rounds: Optional[int] = None          # rounds the ring ran
    prev: Optional[np.ndarray] = None     # (k, n, n) the round before's

    def same(self, other: "Answer") -> bool:
        return (np.array_equal(self.adj, other.adj)
                and float(self.score) == float(other.score))


def _rel(reported: float, exact: float) -> float:
    return abs(float(reported) - exact) / abs(exact)


def numbers(problem, cfg: dict, traffic: dict, answer: Answer) -> dict:
    """The compared numbers of one answer (see the module docstring)."""
    data, ar, ess = problem.data, problem.arities, float(cfg["ess"])
    maxp, maxq = int(cfg["max_parents"]), int(cfg["max_q"])
    n = data.shape[1]
    adj = np.asarray(answer.adj, dtype=bool)
    exact = ref.graph_score(data, ar, adj, ess)
    out = {"score_gap": _rel(answer.score, exact), "delete_gap": 0.0,
           "dag_faults": ref.structure_faults(adj, ar, maxp, maxq)}
    out["delete_gap"] = max(0.0, float(ref.delete_matrix(data, ar, adj,
                                                         ess).max()))
    _, polished = ref.ges(data, ar, ess, maxp, maxq, start=adj)
    out["polish_gain"] = max(0.0, polished - exact) / abs(exact)
    if answer.masks is not None:
        want = ref.partition(data, ar, answer.masks.shape[0], ess)
        out["mask_diff"] = int((np.asarray(answer.masks, bool) != want).sum())
    if answer.members is None:
        return out

    members = np.asarray(answer.members, dtype=bool)
    masks = np.asarray(answer.masks, dtype=bool)
    prev = np.asarray(answer.prev, dtype=bool)
    k = members.shape[0]
    limit = ref.add_limit(n, k) if traffic.get("limit") else None
    off = ~np.eye(n, dtype=bool)
    out.update(fusion_diff=0, member_gap=0.0)
    exact_members = []
    for i in range(k):
        g, subset = members[i], masks[i]
        score = ref.graph_score(data, ar, g, ess)
        exact_members.append(score)
        out["score_gap"] = max(out["score_gap"],
                               _rel(answer.member_scores[i], score))
        out["dag_faults"] += int(np.diag(ref.reach(g)).any())
        gains = np.where(subset, ref.delete_matrix(data, ar, g, ess), -np.inf)
        out["delete_gap"] = max(out["delete_gap"], float(gains.max()))
        start = ref.fuse(prev[i], prev[(i - 1) % k])
        out["fusion_diff"] += int(((g != start) & ~subset & off).sum())
        _, stepped = ref.ges(data, ar, ess, maxp, maxq, start=start,
                             allowed=subset, limit=limit)
        out["member_gap"] = max(out["member_gap"], stepped - score)
    winner = members[int(np.argmax(exact_members))]
    _, tuned = ref.ges(data, ar, ess, maxp, maxq, start=winner)
    out["finetune_gap"] = max(0.0, tuned - exact) / abs(tuned)
    return out


def judge(values: dict, limits: dict) -> dict:
    """{name: {"value", "limit"}} for every compared number; exact numbers
    have the limit 0."""
    out = {}
    for name, value in values.items():
        limit = 0 if name in EXACT else limits[name]
        out[name] = {"value": value, "limit": limit}
    return out


def passed(judged: dict) -> bool:
    return all(v["value"] <= v["limit"] for v in judged.values())
