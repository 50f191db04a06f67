"""The BDeu Pallas kernels compile for a TPU v5e chip at paper width.

Each case asks the installed TPU compiler to compile one kernel, through
its ops wrapper and with ``interpret=False``, for a v5e chip that is
described rather than attached — so the refusals interpret mode cannot show
(unaligned blocks, unsupported primitives, VMEM overflow) fail here, on the
CPU, before any chip run.  Widths are those of the paper's pigs (r = 3,
n = 441) and munin (r = 5, n = 1041) domains at m = 5000 and max_q = 1024;
restricted sweeps take W = n // 2, a two-member ring's candidate width.
"""
from functools import partial

import pytest
import jax
import jax.numpy as jnp

from repro.kernels.bdeu_count import contingency_counts
from repro.kernels.bdeu_sweep import (delete_marginals, sweep_counts,
                                      sweep_counts_restricted)

M, MAX_Q = 5000, 1024
DOMAINS = {"pigs": (441, 3), "munin": (1041, 5)}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e topology can be described here: {e}")
    # a described chip's programs cannot be read back from the persistent
    # cache, so keep them out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _kernel_call(kernel, n, r, spec):
    codes = spec((M,))
    n_slots = MAX_Q.bit_length() - 1
    kw = dict(max_q=MAX_Q, r_max=r, interpret=False)
    if kernel == "count":
        return partial(contingency_counts, **kw), (codes, codes)
    if kernel == "insert":
        return partial(sweep_counts, **kw), (codes, codes, spec((M, n)))
    if kernel == "insert_restricted":
        return (partial(sweep_counts_restricted, **kw),
                (codes, codes, spec((M, n)), spec((n // 2,))))
    return (partial(delete_marginals, **kw),
            (codes, codes, spec((n_slots,)), spec((n_slots,))))


@pytest.mark.parametrize("domain", sorted(DOMAINS))
@pytest.mark.parametrize("kernel", ["count", "insert", "insert_restricted",
                                    "delete"])
def test_kernel_compiles_for_v5e(one_chip, kernel, domain):
    n, r = DOMAINS[domain]

    def spec(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    fn, args = _kernel_call(kernel, n, r, spec)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
