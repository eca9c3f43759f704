"""The main path's Pallas kernels compile for a TPU v5e at the serving
deployment's widths (50,000 × 20 features, 50 trees, 64 bins, 4 classes).

Nothing runs: each kernel is lowered and compiled for a described — not
attached — ``v5e:2x2`` chip, which raises what the chip's compiler would
raise (unaligned blocks, unsupported primitives, too much scoped VMEM).
The topology is described inside a fixture, never at import, so only the
test worker that runs this file loads the TPU compiler.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.jax_ops import swlc_block_topk
from repro.kernels.block_prox.block_prox import (BLOCK_Q, BLOCK_W, T_CHUNK,
                                                 block_prox_pallas,
                                                 block_vmem_bytes)
from repro.kernels.histogram.histogram import (hist_vmem_bytes,
                                               histogram_pallas,
                                               moments_pallas)
from repro.kernels.leaf_route.leaf_route import route_pallas

N, D, T, BINS, CLASSES, MOMENTS = 50_000, 20, 50, 64, 4, 3
TILE, NODES = 512, 64          # forest/training.py: _JAX_TILE, _JAX_NODE_CHUNK


@pytest.fixture(scope="module")
def one_chip():
    """A described v5e chip, with the persistent compile cache off (a
    compile for a described chip is written to it but cannot be read
    back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_block_prox_compiles_for_v5e(one_chip):
    """Dense proximity block, 256 query rows against the 50k reference
    rows (the pallas engine's kernel_block / topk / squared row sums)."""
    s = lambda shape, dt: _shape(one_chip, shape, dt)
    compiled = jax.jit(block_prox_pallas).lower(
        s((256, T), jnp.int32), s((256, T), jnp.float32),
        s((N, T), jnp.int32), s((N, T), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("trees", [100, 500])
@pytest.mark.parametrize("op", ["block", "topk"])
def test_block_kernels_compile_at_any_tree_count(one_chip, op, trees):
    """A 1,024-row query batch against 100,000 reference rows at 100 and
    500 trees (the top-k cells' shape): the kernel walks the trees in
    chunks, so it compiles within ``block_vmem_bytes`` — the scoped-VMEM
    limit it hands the compiler — whatever T.  Before the trees were
    tiled, T = 100 asked for 18.21M against v5e's 16M."""
    s = lambda shape, dt: _shape(one_chip, shape, dt)
    nq, nw = 1024, 100_000
    query = (s((nq, trees), jnp.int32), s((nq, trees), jnp.float32))
    if op == "block":
        fn = block_prox_pallas
        args = query + (s((nw, trees), jnp.int32), s((nw, trees), jnp.float32))
    else:
        # the reference side as the engine stages it: transposed, padded
        t_pad = -(-trees // T_CHUNK) * T_CHUNK
        nw_pad = -(-nw // BLOCK_W) * BLOCK_W
        fn = lambda a, b, c, d: swlc_block_topk(a, b, c, d, k=10, n_ref=nw)
        args = query + (s((t_pad, nw_pad), jnp.int32),
                        s((t_pad, nw_pad), jnp.float32))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert block_vmem_bytes(BLOCK_Q, BLOCK_W, T_CHUNK) <= 16 << 20
    if op == "topk":
        assert [o.shape for o in compiled.out_info] == [(nq, 10)] * 2


@pytest.mark.parametrize("kind", ["histogram", "moments"])
def test_histogram_compiles_within_vmem_estimate(one_chip, kind):
    """The trainer's histogram call at its chunk shape compiles with the
    ``hist_vmem_bytes`` estimate as the compiler's scoped-VMEM limit, so
    the estimate covers what the compiler allocates, and it covers the
    buffers ``memory_analysis`` reports."""
    s = lambda shape, dt: _shape(one_chip, shape, dt)
    rows = 8 * TILE
    if kind == "histogram":
        est = hist_vmem_bytes(TILE, D, NODES, BINS, CLASSES)
        fn = lambda xb, nd, y, w: histogram_pallas(
            xb, nd, y, w, NODES, BINS, CLASSES, tile=TILE, vmem_budget=est)
        args = (s((rows, D), jnp.int32), s((rows,), jnp.int32),
                s((rows,), jnp.int32), s((rows,), jnp.float32))
    else:
        est = hist_vmem_bytes(TILE, D, NODES, BINS, MOMENTS)
        fn = lambda xb, nd, wm: moments_pallas(
            xb, nd, wm, NODES, BINS, MOMENTS, tile=TILE, vmem_budget=est)
        args = (s((rows, D), jnp.int32), s((rows,), jnp.int32),
                s((rows, MOMENTS), jnp.float32))
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert est >= mem.temp_size_in_bytes + mem.output_size_in_bytes
    assert est <= 16 << 20          # v5e's default scoped-VMEM limit


def test_leaf_route_refused_on_v5e(one_chip):
    """The routing kernel's per-sample gathers from the node tables are
    what the v5e compiler does not lower; on a TPU the kernel raises that
    error (exact device routing is open work, so OOS routing stays on
    the host).  Should this start to compile, device routing can be
    considered — update this test then."""
    s = lambda shape, dt: _shape(one_chip, shape, dt)
    m = 4096
    fn = lambda x, f, t, l, r, i: route_pallas(x, f, t, l, r, i,
                                               max_depth=28)
    with pytest.raises(Exception, match="gather"):
        jax.jit(fn).lower(
            s((N, D), jnp.float32), s((T, m), jnp.int32),
            s((T, m), jnp.float32), s((T, m), jnp.int32),
            s((T, m), jnp.int32), s((T, m), jnp.int32)).compile()
