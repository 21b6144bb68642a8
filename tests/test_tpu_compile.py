"""Every Pallas kernel compiles for a TPU v5e at real widths.

The TPU compiler ships with jaxlib, so each test compiles its kernel for a
*described* v5e chip (``jax.experimental.topologies``) — no chip attached —
and asserts that the Mosaic kernel is in the compiled HLO
(``tpu_custom_call``).  This catches what interpret mode cannot: block
shapes the TPU tiling refuses, layouts Mosaic cannot match, VMEM overuse.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every pytest
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.distill_loss import (distill_loss_bwd_pallas,
                                        distill_loss_fwd_pallas)
from repro.kernels.era_sharpen import (era_sharpen_pallas,
                                       weighted_era_sharpen_pallas)
from repro.kernels.ssd_chunk import ssd_chunk_pallas

F32 = jnp.float32


@pytest.fixture(scope="module")
def chip():
    """One chip of a described v5e:2x2 host, with the persistent compile
    cache off (an entry compiled here cannot be read back without a
    chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this jaxlib
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _hlo(fn, chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("kernel", ["era", "weighted_era", "weighted_mean"])
def test_era_kernels_compile(chip, kernel):
    """ERA mean+sharpen at the paper's K=100 clients, |o_r|=1000, C=10."""
    K, N, C = 100, 1000, 10
    if kernel == "era":
        fn = lambda p: era_sharpen_pallas(p, 0.1, interpret=False)
        hlo = _hlo(fn, chip, ((K, N, C), F32))
    else:
        fn = lambda p, w: weighted_era_sharpen_pallas(
            p, w, 0.1, sharpen=kernel == "weighted_era", interpret=False)
        hlo = _hlo(fn, chip, ((K, N, C), F32), ((K,), F32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("dtype", [jnp.bfloat16, F32])
def test_distill_loss_compiles_at_qwen_vocab(chip, dtype):
    """Forward and backward at N=1024 rows over qwen1.5's 151,936-token
    vocabulary, which no lane-aligned vocab block divides."""
    N, V = 1024, 151_936
    fwd = lambda z, t: distill_loss_fwd_pallas(z, t, interpret=False)
    assert "tpu_custom_call" in _hlo(fwd, chip, ((N, V), dtype),
                                     ((N, V), dtype))
    bwd = lambda z, t, lz, tm, g: distill_loss_bwd_pallas(
        z, t, lz, tm, g, interpret=False)
    assert "tpu_custom_call" in _hlo(bwd, chip, ((N, V), dtype),
                                     ((N, V), dtype), ((N,), F32),
                                     ((N,), F32), ((1,), F32))


def test_distill_loss_vjp_compiles(chip):
    """The custom_vjp wrapper the training path differentiates."""
    N, V = 1024, 151_936
    g = lambda z, t: jax.grad(lambda z_: ops.distill_loss(
        z_, t, interpret=False))(z)
    hlo = _hlo(g, chip, ((N, V), jnp.bfloat16), ((N, V), jnp.bfloat16))
    assert hlo.count("tpu_custom_call") >= 2


def test_ssd_chunk_compiles_at_mamba2_widths(chip):
    """mamba2-2.7b: chunk Q=256, 80 heads of P=64, state N=128, one group;
    M=16 chunk-batches (a 4096-token sequence)."""
    M, Q, H, P, G, N = 16, 256, 80, 64, 1, 128
    fn = lambda x, dt, dA, b, c: ssd_chunk_pallas(x, dt, dA, b, c,
                                                  interpret=False)
    hlo = _hlo(fn, chip, ((M, Q, H, P), jnp.bfloat16), ((M, Q, H), F32),
               ((M, Q, H), F32), ((M, Q, G, N), jnp.bfloat16),
               ((M, Q, G, N), jnp.bfloat16))
    assert "tpu_custom_call" in hlo


def test_mnist_cnn_prediction_compiles(chip):
    """The paper round's prediction leg: 10 clients' MNIST CNNs (vmapped)
    on a 1000-image open batch, softmax and the ERA kernel.  With the
    softmax fused into the forward, the v5e compiler overflowed its
    stack here."""
    from repro.core.aggregation import era
    from repro.core.client import predict_probs
    from repro.models.smallnets import apply_mnist_cnn, init_mnist_cnn
    put = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), t)
    w, s = jax.eval_shape(lambda k: jax.vmap(init_mnist_cnn)(
        jax.random.split(k, 10)), jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((1000, 28, 28, 1), F32)
    fn = lambda w, s, x: era(jax.vmap(lambda a, b: predict_probs(
        apply_mnist_cnn, a, b, x))(w, s), 0.1, use_kernel=True,
        interpret=False)
    hlo = jax.jit(fn).lower(put(w), put(s), put(x)).compile().as_text()
    assert "tpu_custom_call" in hlo
