"""The benchmark's datasets: the same draws as the program's family-matched
sampler, and a seed that only relabels variables and reorders instances."""
import numpy as np
import pytest

from perfbench import data

CFG = {"n": 30, "n_edges": 40, "max_parents_true": 3,
       "arity_choices": [2, 3, 4], "arity_probs": [0.6, 0.3, 0.1],
       "concentration": 0.4, "network_seed": 0, "data_seed": 1, "m": 300}


def test_same_draws_as_the_program_sampler():
    from repro.data.bn import forward_sample, random_bn

    bn = random_bn(np.random.default_rng(0), 30, 40, arity_choices=(2, 3, 4),
                   arity_probs=(0.6, 0.3, 0.1), max_parents=3,
                   concentration=0.4)
    want = forward_sample(bn, 300, np.random.default_rng(1))
    got = data.base_problem(CFG)
    assert np.array_equal(got.data, want)
    assert np.array_equal(got.arities, bn.arities)
    assert np.array_equal(data.network(CFG)[0], bn.adj)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 2**40 + 3])
def test_seed_relabels_and_reorders_only(seed):
    base = data.base_problem(CFG)
    p = data.problem(CFG, seed)
    again = data.problem(CFG, seed)
    assert np.array_equal(p.data, again.data)
    # the same columns, relabelled, with their rows reordered
    assert sorted(p.arities.tolist()) == sorted(base.arities.tolist())
    key = lambda d: sorted(map(tuple, np.sort(d, axis=0).T.tolist()))  # noqa
    assert key(p.data) == key(base.data)


def test_pigs_configuration_reproduces_the_smoke_dataset_shape():
    import json
    from perfbench.spec import BENCH_DIR

    cfg = json.loads((BENCH_DIR / "configs" / "pigs_like-m5000.json")
                     .read_text())
    assert (cfg["n"], cfg["n_edges"], cfg["m"]) == (441, 592, 5000)
    assert cfg["reduced"] == ["max_rounds"]
