"""Compile each Pallas kernel at its real widths for a described TPU v5e.

Nothing runs: the TPU compiler installed with jaxlib compiles for a chip
that is described, not attached, and refuses what the chip would refuse
(unaligned blocks, primitives Mosaic cannot lower, too much VMEM).  The
interpret-mode tests in test_kernels.py cannot see any of that.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every pytest worker
imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: an
    executable compiled for a described chip is written but can never be
    read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_gemma3_1b(one_chip):
    """gemma3-1b: 4 query heads on 1 kv head of 256, a 2048-token prompt,
    the local layers' 512-token window."""
    from repro.kernels.flash_attention import ops
    q = _spec(one_chip, (1, 2048, 4, 256), jnp.bfloat16)
    kv = _spec(one_chip, (1, 2048, 1, 256), jnp.bfloat16)
    _assert_kernel(ops.flash_attention.lower(
        q, kv, kv, causal=True, window=512).compile())


def test_ssd_scan_mamba2_130m(one_chip):
    """mamba2-130m: 24 SSD heads of 64, state 128, chunk 128, 2048 tokens."""
    from repro.kernels.ssd_scan import ops
    B, S, H, P, N = 1, 2048, 24, 64, 128
    f32 = jnp.float32
    _assert_kernel(ops.ssd_chunked.lower(
        _spec(one_chip, (B, S, H, P), f32), _spec(one_chip, (B, S, H), f32),
        _spec(one_chip, (H,), f32), _spec(one_chip, (B, S, 1, N), f32),
        _spec(one_chip, (B, S, 1, N), f32), chunk=128).compile())


def test_quant_64mib(one_chip):
    """Quantize and dequantize a 64 MiB f32 payload (the wire-compression
    all-reduce at its largest message)."""
    from repro.kernels.quant import ops
    n = (64 << 20) // 4
    compiled = ops.quantize.lower(_spec(one_chip, (n,), jnp.float32)).compile()
    _assert_kernel(compiled)
    q_shape, s_shape = jax.eval_shape(
        ops.quantize, jax.ShapeDtypeStruct((n,), jnp.float32))
    _assert_kernel(ops.dequantize.lower(
        _spec(one_chip, q_shape.shape, q_shape.dtype),
        _spec(one_chip, s_shape.shape, s_shape.dtype),
        shape=(n,), dtype=jnp.float32).compile())


def test_swe_step_chip_smoke_mesh(one_chip):
    """The SWE element update over the bight mesh of chip_smoke.py's
    four-chip phase, on one partition (86,578 elements).  At the one-chip
    phase's 872,167 elements the kernel needs 16.6 MiB of scoped VMEM, over
    the 16 MiB limit."""
    from repro.kernels.swe_step import ops
    E = 86_578
    f32 = jnp.float32
    _assert_kernel(ops.swe_step.lower(
        _spec(one_chip, (E, 3), f32), _spec(one_chip, (E, 3, 3), f32),
        _spec(one_chip, (E, 3), f32), _spec(one_chip, (E, 3), f32),
        _spec(one_chip, (E, 3), jnp.int32), _spec(one_chip, (E,), f32),
        _spec(one_chip, (E,), f32), 1.0, dt=1e-4).compile())
