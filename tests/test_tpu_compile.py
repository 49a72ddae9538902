"""Compile-only checks of the device path at real widths for a TPU v5e.

No chip is attached: the TPU compiler builds for a described ``v5e:2x2``
topology, one chip of it.  These tests catch what interpret mode cannot —
a block shape Mosaic refuses, an element type it lacks — at no chip
time.  Nothing runs, so they say nothing about results or speed.

The topology is described inside a module fixture, never while a module
is imported: only one process may load the TPU library, and under
several test workers only the one running this file does.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import JAX_FEATURES
from repro.kernels.bigroots_gates import _gates_jnp, _gates_pallas
from repro.models.forecast_ssd import (
    ForecastConfig,
    forecast_init,
    forecast_step,
)

#: The fleet sweep's packed gate batch: windows, straggler rows, features.
W, R, F = 8, 2048, len(JAX_FEATURES)
#: Sequences in one forecaster tick (a 16k-node fleet).
S = 16384


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A described chip's compile is written to the persistent cache but
    # cannot be read back without the chip: keep the cache off meanwhile.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _gate_specs(dtype, sharding, rows=R):
    shapes = [(W, rows, F), (W, rows, F), (W, rows, 1), (W, rows, 1),
              (W, rows, 1), (W, 1, F), (W, 1, F), (W, 1, F), (1, 1, F)]
    return [jax.ShapeDtypeStruct(s, dtype, sharding=sharding) for s in shapes]


# (R, 256): the sweep's bucketed rows.  (32, 32): a batch of a few rows,
# which eval_gates pads to one 32-row block, the int8 output's tile.
@pytest.mark.parametrize("rows,block_r", [(R, 256), (32, 32)],
                         ids=["bucketed", "small"])
def test_gates_pallas_f32_is_mosaic_kernel(one_chip, rows, block_r):
    compiled = _gates_pallas.lower(
        *_gate_specs(jnp.float32, one_chip, rows),
        peer_mean=1.5, block_r=block_r, interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("x64", [False, True], ids=["f32", "x64"])
def test_gates_jnp_compiles(one_chip, x64):
    with jax.enable_x64(x64):
        dtype = jnp.float64 if x64 else jnp.float32
        lowered = _gates_jnp.lower(*_gate_specs(dtype, one_chip),
                                   peer_mean=1.5)
        out = lowered.out_info
        lowered.compile()
    assert out.shape == (W, R, F) and out.dtype == jnp.int8


def test_forecast_step_compiles_at_fleet_batch(one_chip):
    cfg = ForecastConfig(features=F)
    params = forecast_init(cfg, seed=0)

    def spec(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float64, sharding=one_chip)

    with jax.enable_x64(True):
        step = jax.jit(
            lambda p, x, h, up: forecast_step(p, x, h, update=up, xp=jnp))
        lowered = step.lower(
            {k: spec(np.shape(v)) for k, v in params.items()},
            spec((S, F)), spec((S, cfg.hidden, cfg.state)), spec((S,)))
        h_out, risks = lowered.out_info
        lowered.compile()
    assert h_out.shape == (S, cfg.hidden, cfg.state)
    assert risks.shape == (S,)
