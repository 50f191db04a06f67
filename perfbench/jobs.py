"""The one general job runner: learns a DAG through the program's own entry
points, as a traffic mix (``traffic/<name>.json``) says.

A traffic mix names the ``algorithm`` and its parameters:

* ``"cges"``: stage 1 (``partition.partition_edges``, defaults), then
  ``cges(engine="jax", edge_masks=...)`` with ``k`` members and the cGES-L
  insertion limit when ``limit`` is true.
* ``"ring_cges"``: stage 1, the compiled ring ``ring.ring_cges`` with one
  member per chip, then the unrestricted ``ges_jit`` fine-tune from the
  best member of the last improving round.
* ``"ges"``: ``ges_jit`` from the empty graph with every edge allowed.

Every job passes ``GESConfig`` the configuration's ``ess``, ``max_parents``
and ``max_q`` and the traffic's ``counts_impl``, and leaves the rest at the
program's defaults.  Nothing is kept from one job to the next but the
program's compiled programs and its device copy of the data
(``ges.device_data``).

For the check, ``previous_round`` learns the members' graphs of the round
before the judged one again, through the same entry point capped at fewer
rounds: the starts of the judged members' step.
"""
from __future__ import annotations

import numpy as np

from .checks import Answer

ALGORITHMS = ("cges", "ring_cges", "ges")


class Runner:
    def __init__(self, traffic: dict, cfg: dict, problem, spans):
        from repro.core import GESConfig

        algo = traffic["algorithm"]
        if algo not in ALGORITHMS:
            raise ValueError(f"unknown algorithm {algo!r} (known: "
                             f"{', '.join(ALGORITHMS)})")
        self.algo, self.traffic, self.cfg = algo, traffic, cfg
        self.problem, self.spans = problem, spans
        self.config = GESConfig(ess=float(cfg["ess"]),
                                max_parents=int(cfg["max_parents"]),
                                max_q=int(cfg["max_q"]),
                                counts_impl=traffic["counts_impl"])
        self.mesh = None
        if algo == "ring_cges":
            from repro.launch.mesh import make_ring_data_mesh

            self.mesh = make_ring_data_mesh(int(traffic["k"]))

    def run(self) -> Answer:
        with self.spans("job"):
            return getattr(self, "_" + self.algo)()

    def _partition(self):
        from repro.core import partition

        with self.spans("partition"):
            return partition.partition_edges(
                self.problem.data, self.problem.arities,
                int(self.traffic["k"]))

    def _learn_cges(self, masks, max_rounds):
        from repro.core import cges

        p, t = self.problem, self.traffic
        return cges(p.data, p.arities, k=int(t["k"]), limit=bool(t["limit"]),
                    config=self.config, engine="jax", max_rounds=max_rounds,
                    edge_masks=masks)

    def _cges(self) -> Answer:
        masks = self._partition()
        with self.spans("cges"):
            res = self._learn_cges(masks, int(self.cfg["max_rounds"]))
        return Answer(adj=res.adj, score=res.score, members=res.ring_graphs,
                      member_scores=res.ring_graph_scores, masks=masks,
                      rounds=res.rounds)

    def _learn_ring(self, masks, max_rounds):
        from repro.core.cges import edge_add_limit
        from repro.core.ring import RingSpec, ring_cges

        p, t = self.problem, self.traffic
        k, n = int(t["k"]), p.data.shape[1]
        return ring_cges(
            p.data, p.arities, masks, self.mesh,
            RingSpec(k=k, max_rounds=max_rounds), self.config,
            add_limit=edge_add_limit(n, k) if t["limit"] else None)

    def _ring_cges(self) -> Answer:
        import jax.numpy as jnp

        from repro.core import ges_jit
        from repro.core.ges import device_data

        p = self.problem
        n = p.data.shape[1]
        masks = self._partition()
        with self.spans("ring"):
            graphs, scores, rounds = self._learn_ring(
                masks, int(self.cfg["max_rounds"]))
        with self.spans("finetune"):
            data_j, ar_j = device_data(p.data, p.arities)
            winner = graphs[int(np.argmax(scores))]
            adj, score, _, _ = ges_jit(
                data_j, ar_j, jnp.asarray(winner), jnp.ones((n, n), jnp.int8),
                add_limit=None, config=self.config,
                r_max=int(p.arities.max()))
            adj, score = np.asarray(adj), float(score)
        return Answer(adj=adj, score=score, members=graphs,
                      member_scores=scores, masks=masks, rounds=rounds)

    def _ges(self) -> Answer:
        import jax.numpy as jnp

        from repro.core import ges_jit
        from repro.core.ges import device_data

        p = self.problem
        n = p.data.shape[1]
        with self.spans("ges"):
            data_j, ar_j = device_data(p.data, p.arities)
            adj, score, _, _ = ges_jit(
                data_j, ar_j, jnp.zeros((n, n), jnp.int8),
                jnp.ones((n, n), jnp.int8), add_limit=None,
                config=self.config, r_max=int(p.arities.max()))
            adj, score = np.asarray(adj), float(score)
        return Answer(adj=adj, score=score)

    def replay(self, masks, rounds: int) -> np.ndarray:
        """(k, n, n) member graphs of the last improving round within
        ``rounds`` rounds from the edge subsets ``masks``; empty graphs for
        0 rounds."""
        if rounds <= 0:
            return np.zeros(np.shape(masks), dtype=np.int8)
        if self.algo == "cges":
            return np.asarray(self._learn_cges(masks, rounds).ring_graphs)
        return self._learn_ring(masks, rounds)[0]

    def previous_round(self, answer: Answer) -> np.ndarray:
        """The members' graphs of the round before the judged members'.
        Those come from the job's last round, or from the one before it
        when the last did not improve (its graphs are then the replay's)."""
        r = int(answer.rounds)
        prev = self.replay(answer.masks, r - 1)
        if r > 1 and np.array_equal(prev, np.asarray(answer.members)):
            prev = self.replay(answer.masks, r - 2)
        return prev

    def insert_widths(self, answer: Answer) -> list:
        """Candidate widths of the insert sweeps a job makes: each member's
        restricted width W (the widest column of any subset) and n."""
        n = self.problem.data.shape[1]
        if answer.masks is None:
            return [n]
        off = np.asarray(answer.masks, bool) & ~np.eye(n, dtype=bool)
        return [int(off.sum(axis=1).max()), n]
