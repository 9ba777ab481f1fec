"""Compile the main path for a v5e chip that is described, not attached.

The TPU compiler refuses what the CPU interpreter accepts: tiles that are
not (8, 128)-aligned, kernels whose working set overflows VMEM, programs
larger than the chip's HBM. These tests lower the Pallas kernels at the
published mule-CNN width (D = 546,484 parameters per mule) with the tiles
their ops would choose, and the one-chip ``mlmule`` replay at M=512, so
every later change is held to the chip's compiler at no chip time.

Nothing runs; a compile that passes is not a chip run. The topology is
described inside a module-scoped fixture (only the worker given this file
loads the TPU compiler), and JAX's persistent compilation cache is off
around the compiles: what they would write cannot be read back without a
chip.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.encounter_mix.kernel import (encounter_hop_pallas,
                                                encounter_mix_pallas)
from repro.kernels.mule_agg.kernel import mule_agg_pallas
from repro.launch.autotune import tuned_block_d, tuned_encounter_blocks

D = 546_484                  # CNNConfig() parameters per mule
F = 8                        # the commuter scenario's spaces
V5E_HBM_BYTES = 15.75 * 2 ** 30   # what the compiler lets one program use


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _geometry(sharding, m):
    return (_spec(sharding, (m, 2)), _spec(sharding, (m,), jnp.int32),
            _spec(sharding, (m,), jnp.bool_))


# M=1024 is the population whose streamed weight block used to hold all M
# rows and overflow VMEM; the kernels now tile the reduction over mules.
# M=1100 leaves a ragged last mule tile, masked inside the kernels.
@pytest.mark.parametrize("m", [512, 1024, 1100])
def test_mule_agg_compiles(one_chip, m):
    block_d = tuned_block_d(D)
    compiled = jax.jit(lambda a, w: mule_agg_pallas(
        a, w, block_d=block_d, interpret=False)).lower(
            _spec(one_chip, (F, m)), _spec(one_chip, (m, D))).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("m", [512, 1024, 1100])
def test_encounter_mix_compiles(one_chip, m):
    block_m, block_d = tuned_encounter_blocks(m, D)
    compiled = jax.jit(lambda p, a, act, w: encounter_mix_pallas(
        p, a, act, w, radius=0.15, block_m=block_m, block_d=block_d,
        interpret=False)).lower(*_geometry(one_chip, m),
                                _spec(one_chip, (m, D))).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("m", [512, 1024, 1100])
def test_ring_hop_compiles(one_chip, m):
    block_m, block_d = tuned_encounter_blocks(m, D)
    row0 = _spec(one_chip, (), jnp.int32)
    compiled = jax.jit(lambda p, a, act, r0, w: encounter_hop_pallas(
        p, a, act, r0, p, a, act, r0 + m, w, radius=0.15, block_m=block_m,
        block_d=block_d, interpret=False)).lower(
            *_geometry(one_chip, m), row0, _spec(one_chip, (m, D))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_mlmule_replay_fits_one_chip(one_chip):
    """The one-chip replay ``chip_smoke.py`` runs: M=512 mule CNNs at their
    published widths, compiled with the state donated."""
    import chip_smoke
    from repro.scenarios.engine import (dense_colocation,
                                        get_compiled_chunk_replay)

    w = chip_smoke.make_workload(0, 512, 24)
    state = jax.eval_shape(lambda: chip_smoke.initial_state(w))
    gen = dense_colocation(w.colocation)
    fn = get_compiled_chunk_replay(
        state, gen, gen.arrays(), w.batch_fn, w.context, w.key, w.train_fn,
        w.pcfg, method="mlmule", eval_every=None, eval_fn=None,
        chunk_len=gen.n_steps, donate=True)
    place = lambda x: _spec(one_chip, x.shape, x.dtype)
    compiled = fn.lower(jax.tree.map(place, state),
                        _spec(one_chip, (gen.n_mules,), jnp.int32),
                        _spec(one_chip, (), jnp.int32),
                        jax.tree.map(place, gen.arrays()), None,
                        jax.tree.map(place, w.context),
                        place(w.key)).compile()
    mem = compiled.memory_analysis()
    used = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert used < V5E_HBM_BYTES, f"{used / 2 ** 30:.2f} GiB"
    assert mem.alias_size_in_bytes > 0          # the donated state aliases
