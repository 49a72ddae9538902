"""Compile-only checks of the gate kernel at each cell's packed shapes,
for one chip of a described TPU ``v5e:2x2`` (no chip attached).

- ``megascale_12k``: F = 14; the first tick after set-up sweeps all 8
  stage windows, 19,664 stragglers each (bucketed to 19,712): about 157k
  packed rows.
- ``hibench_spark_256``: F = 12; the first tick after set-up sweeps all
  64 stage windows of 2,048 tasks, the widest with up to 768 padded
  straggler rows; later ticks sweep 15-28 of them.

The topology is described inside a module fixture, never at import.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bigroots_gates import _gates_pallas


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:  # noqa: BLE001 - no TPU compiler here
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("W,R,F", [(8, 19712, 14), (1, 19712, 14),
                                   (64, 768, 12)],
                         ids=["megascale_first_tick", "megascale_tick",
                              "hibench_64_windows"])
def test_gate_kernel_compiles(one_chip, W, R, F):
    shapes = [(W, R, F), (W, R, F), (W, R, 1), (W, R, 1), (W, R, 1),
              (W, 1, F), (W, 1, F), (W, 1, F), (1, 1, F)]
    specs = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
             for s in shapes]
    compiled = _gates_pallas.lower(*specs, peer_mean=1.5, block_r=256,
                                   interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
