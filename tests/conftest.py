"""Test config: force JAX onto CPU with 8 virtual devices BEFORE jax import,
so mesh/sharding logic is exercised without a TPU (SURVEY.md §4)."""

from ollamamq_tpu.platform_force import force_cpu

force_cpu(8)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def tiny_cfg():
    from ollamamq_tpu.config import MODEL_CONFIGS

    return MODEL_CONFIGS["test-tiny"]


@pytest.fixture(scope="session")
def tiny_params(tiny_cfg):
    import jax
    import jax.numpy as jnp
    from ollamamq_tpu.models import llama

    return llama.init_params(tiny_cfg, jax.random.PRNGKey(0), dtype=jnp.float32)


@pytest.fixture(scope="session")
def poison_trash_page():
    """`poison(pool, page_size, layer)`: the pool with its trash page (page
    0) set, in every layer, to a large FINITE value chosen by the layer
    under test — 1e30, -1e30, the dtype's largest. The attention kernels
    read that page where a block runs past a sequence's last page, masked
    to a weight of 0 (ops/pallas/kv_contract.py), so a kernel fed the
    poisoned pool must return every bit it returns on the clean one."""
    import jax.numpy as jnp

    def poison(pool, page_size, layer):
        value = (1e30, -1e30, float(jnp.finfo(pool.dtype).max))[layer % 3]
        return pool.at[:, :page_size].set(value)

    return poison
