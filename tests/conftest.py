import pytest


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: multi-device subprocess tests")


@pytest.fixture(scope="module")
def described_chip():
    """One chip of a described, not attached, TPU v5e: a program compiles
    for it without a chip.  The topology is described here only, never
    while a module is imported: one process at a time may load the TPU
    library."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip executable is written to the cache but cannot be read back
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
