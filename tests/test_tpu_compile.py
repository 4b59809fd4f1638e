"""The TPU v5e compiler accepts the FL hot path's kernels and programs.

Each case compiles for a v5e chip that is described, not attached
(``jax.experimental.topologies``), at the MNIST CNN's real leaf sizes and
cohort widths. Nothing runs: a pass says the chip's compiler takes the
program, not that its results are right (``chip_smoke.py`` checks those on
the chip). The topology is described only inside the module fixture, so
every test worker collects the same cases and only the one given this file
loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import mnist_cnn_task
from repro.kernels import ops

KERNELS = {
    "quantize_rows": lambda xs, w: [
        ops.quantize_rows(x, jnp.max(jnp.abs(x), axis=-1)) for x in xs
    ],
    "downcast_bf16_rows": lambda xs, w: [ops.downcast_bf16_rows(x) for x in xs],
    "fedavg_reduce": lambda xs, w: ops.fedavg_reduce(xs, w),
}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.fixture(scope="module")
def cnn_leaf_sizes():
    params = jax.eval_shape(mnist_cnn_task().init_fn, jax.random.PRNGKey(0))
    return [int(np.prod(l.shape)) for l in jax.tree.leaves(params)]


def _custom_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("rows", [1, 10, 32])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_at_cnn_leaves(kernel, rows, one_chip, cnn_leaf_sizes):
    """One program per kernel and row count over every CNN leaf; each leaf
    lowers to its own Mosaic custom call (no XLA stand-in)."""
    xs = [jax.ShapeDtypeStruct((rows, n), jnp.float32, sharding=one_chip)
          for n in cnn_leaf_sizes]
    w = jax.ShapeDtypeStruct((rows,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(KERNELS[kernel]).lower(xs, w).compile()
    assert _custom_calls(compiled) == len(cnn_leaf_sizes)


@pytest.mark.parametrize("cohort", [128, 300])
def test_fedavg_reduce_compiles_at_wide_cohorts(cohort, one_chip, cnn_leaf_sizes):
    """Cohorts past the bucket ladder narrow the tile, not the row block."""
    n = max(cnn_leaf_sizes)
    x = jax.ShapeDtypeStruct((cohort, n), jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((cohort,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda x, w: ops.fedavg_reduce(x, w)).lower(x, w).compile()
    assert _custom_calls(compiled) == 1


@pytest.mark.parametrize("n_retries", [0, 3])
def test_device_transport_round_compiles(n_retries, one_chip):
    """The device transport plane's whole round at k = 4096 rows."""
    from repro.transport import DEFAULT, LAB, RetryPolicy
    from repro.transport.des import _LinkArrays, _RetryArrays, _TcpArrays
    from repro.transport.plane import LinkPlane, RetryPlane, TcpPlane, _device_round

    k = 4096
    policy = RetryPolicy(max_retries=n_retries) if n_retries else None
    args = (
        TcpPlane.from_arrays(_TcpArrays.broadcast(DEFAULT, k)),
        LinkPlane.from_arrays(_LinkArrays.from_links([LAB] * k)),
        RetryPlane.from_arrays(_RetryArrays.broadcast(policy, k)),
        jnp.zeros(k), jnp.zeros(k), jnp.zeros(k), jnp.zeros(k, bool),
        jax.random.PRNGKey(0),
    )
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), args
    )
    compiled = _device_round.lower(
        *shapes, attempts=8, n_retries=n_retries
    ).compile()
    assert compiled.memory_analysis() is not None
