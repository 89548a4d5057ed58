"""Compiles for TPU v5e, without a chip: the served kernels at the shapes
the engine serves, and the fused PQ dispatch over 1,048,576 ids.

The TPU compiler is installed here and compiles for a described, not
attached, chip: what Mosaic or XLA would refuse on the chip (a block not
aligned to the (8, 128) tiling, a DMA slice narrower than a tile, more
device memory than one chip has) fails here. Nothing runs, so these say
nothing about results or times.

The topology is described only inside a module fixture, never at
import: one process at a time may load the TPU library, and every
pytest worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import search
from repro.kernels import ops
from repro.kernels.l2_gather.kernel import l2_gather
from repro.kernels.pq_adc.kernel import pq_adc
from repro.kernels.row_gather.kernel import row_gather

N = 1 << 20          # ids: the smallest one-chip share chip_smoke.py serves
B, BEAM, R, POOL = 256, 16, 32, 64
D, M, K = 96, 48, 256
HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def chip():
    """A described v5e chip, with the persistent compile cache off (a
    compile for a described chip is written to it but cannot be read
    back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_l2_gather_compiles(chip):
    _compile(lambda t, i, q: l2_gather(t, i, q),
             _spec(chip, (N, D), jnp.float32),
             _spec(chip, (B, BEAM * R), jnp.int32),
             _spec(chip, (B, D), jnp.float32))


def test_pq_adc_compiles(chip):
    _compile(lambda c, lut, i: pq_adc(c, lut, i),
             _spec(chip, (N, M), jnp.uint8),
             _spec(chip, (B, M, K), jnp.float32),
             _spec(chip, (B, BEAM * R), jnp.int32))


def test_row_gather_compiles(chip):
    _compile(lambda t, h, i: row_gather(t, h, i),
             _spec(chip, (N, R), jnp.int32),
             _spec(chip, (N,), jnp.int32),
             _spec(chip, (B, BEAM), jnp.int32))


@pytest.mark.parametrize("filtered", [False, True],
                         ids=["unfiltered", "filtered"])
def test_fused_pq_dispatch_fits_one_chip(chip, monkeypatch, filtered):
    """The served fused PQ dispatch, traced down the kernel branch the
    TPU backend takes, over a 1,048,576-id index with every topology row
    resident: it compiles with the kernels inside and fits 16 GiB."""
    monkeypatch.setattr(ops, "use_kernels", lambda: True)
    rounds = 8
    C = BEAM * R
    dispatch = jax.jit(search._pq_fused_dispatch.__wrapped__,
                       static_argnames=("beam", "id_bound"))
    res = ((_spec(chip, (B, POOL), jnp.int32),
            _spec(chip, (B, POOL), jnp.float32)) if filtered else None)
    fmask = _spec(chip, (N,), jnp.bool_) if filtered else None
    compiled = dispatch.lower(
        _spec(chip, (B, POOL), jnp.int32), _spec(chip, (B, POOL), jnp.float32),
        _spec(chip, (B, POOL), jnp.bool_), _spec(chip, (B, BEAM), jnp.int32),
        _spec(chip, (), jnp.int32), _spec(chip, (B, rounds, C), jnp.int32),
        _spec(chip, (N, R), jnp.int32), _spec(chip, (N,), jnp.int32),
        _spec(chip, (N, M), jnp.uint8), _spec(chip, (B, M, K), jnp.float32),
        _spec(chip, (N,), jnp.bool_), _spec(chip, (), jnp.int32),
        beam=BEAM, id_bound=N, res=res, fmask=fmask).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2     # row_gather + pq_adc
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, total
