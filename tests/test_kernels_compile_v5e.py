"""The repo's own TPU kernels compiled for a described v5e, without the
chip: what the Pallas interpreter cannot refuse (a slice off the
tiling, a strided read Mosaic does not take, too much fast memory) the
chip's compiler refuses here, in seconds.  Nothing runs, so nothing
here says a word about results or times.

The topology is described inside a fixture, never while a module is
imported (one process at a time may load the TPU's library; see the
`on-chip-measurement` guide), and every such test lives in this one
file."""
import os

import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import ssm


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:1x1",
            chip_config_name="default", chips_per_host_bounds=(1, 1, 1),
            num_slices=1)
    except Exception as e:
        pytest.skip("no v5e topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to jax's persistent
    cache and cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("chunk", [64, 16, 128])
def test_the_delta_rules_kernels_compile_at_the_cells_width(
        one_chip, no_compile_cache, chunk):
    """Akk and Aqk and their gradient for one layer of the Kimi cell
    (4,096 chunks of 64 tokens, a head of 128), and the smallest and
    the largest chunk the kernels take."""
    cells, dim = 4096 * 64 // chunk, 128

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)
    operands = [shape(cells, chunk, dim)] * 3
    products = ssm._intra_kernels(chunk, dim)
    text = jax.jit(products).lower(*operands).compile().as_text()
    assert text.count("tpu_custom_call") == 1

    def gradient(k, q, G, dakk, daqk):
        return jax.vjp(products, k, q, G)[1]((dakk, daqk))
    text = jax.jit(gradient).lower(
        *operands, *[shape(cells, chunk, chunk)] * 2).compile().as_text()
    # the backward kernel alone: nothing it is handed comes from the
    # forward one
    assert text.count("tpu_custom_call") == 1


def test_the_delta_rules_solve_compiles_to_no_block_inversion(
        one_chip, no_compile_cache):
    """One layer's solve of the Kimi cell (4,096 systems of 64 and a
    right-hand side of 256) and its gradient: XLA's triangular solve
    compiles to a `custom-call` that inverts the diagonal blocks row by
    row, the solve by halving to none."""
    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)
    operands = shape(1, 128, 32, 64, 64), shape(1, 128, 32, 64, 256)

    def xla(system, rhs):
        return jax.lax.linalg.triangular_solve(
            system, rhs, left_side=True, lower=True, unit_diagonal=True)

    def both(solve):
        return jax.grad(lambda s, b: jnp.sum(jnp.sin(solve(s, b))), (0, 1))
    for f, calls in ((xla, True), (ssm._unit_lower_solve, False)):
        text = jax.jit(both(f)).lower(*operands).compile().as_text()
        assert ("InvertDiagBlocksLowerTriangular" in text) == calls


@pytest.mark.parametrize("chunk", [64, 16, 128])
def test_the_delta_rules_carry_compiles_to_one_kernel_a_pass(
        one_chip, no_compile_cache, chunk):
    """The carry of one layer of the Kimi cell (8,192 tokens as chunks
    of 64, 32 heads of 128, bfloat16 operands), and the smallest and
    the largest chunk the kernel takes: the states and delta, and their
    gradient, one kernel forward and one backward, and no loop of
    XLA's (the scan's carrier compiles to a `while` a pass)."""
    nc, heads, dim = 8192 // chunk, 32, 128

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    w = k = shape(1, nc, heads, chunk, dim)
    u = shape(1, nc, heads, chunk, dim, dtype=jnp.float32)
    decay = shape(1, nc, heads, dim, dtype=jnp.float32)
    g_s = shape(1, nc, heads, dim, dim, dtype=jnp.float32)
    for kernel, calls, loops in ((True, 1, 0), (False, 0, 1)):
        carry = ssm._delta_carrier(kernel)
        text = jax.jit(carry).lower(w, u, k, decay).compile().as_text()
        assert (text.count("tpu_custom_call"), text.count(" while(")) \
            == (calls, loops)

        def gradient(w, u, k, decay, g_s, g_delta):
            return jax.vjp(carry, w, u, k, decay)[1]((g_s, g_delta))
        text = jax.jit(gradient).lower(w, u, k, decay, g_s, u).compile(
        ).as_text()
        assert (text.count("tpu_custom_call"), text.count(" while(")) \
            == (2 * calls, 2 * loops)
