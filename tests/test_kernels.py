"""Per-kernel validation: shape/dtype sweeps, allclose vs the pure-jnp
oracles in repro/kernels/ref.py (kernels run in interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.distill_loss import distill_loss_fwd_pallas
from repro.kernels.era_sharpen import era_sharpen_pallas
from repro.kernels.ssd_chunk import ssd_chunk_pallas


@pytest.mark.parametrize("K,N,C", [(2, 8, 10), (10, 64, 46), (5, 16, 512),
                                   (3, 32, 151)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("T", [0.1, 0.5, 1.0])
def test_era_sharpen_sweep(rng, K, N, C, dtype, T):
    p = jax.nn.softmax(jax.random.normal(rng, (K, N, C)), -1).astype(dtype)
    out = era_sharpen_pallas(p, T, interpret=True)
    exp = ref.era_sharpen_ref(p, T)
    np.testing.assert_allclose(out, exp, atol=5e-3 if dtype == jnp.bfloat16
                               else 1e-6)


def test_era_sharpen_op_blocks(rng):
    # N not divisible by default block: op must adapt
    p = jax.nn.softmax(jax.random.normal(rng, (4, 6, 33)), -1)
    out = ops.era_sharpen(p, 0.1)
    np.testing.assert_allclose(out, ref.era_sharpen_ref(p, 0.1), atol=1e-6)


@pytest.mark.parametrize("N,block_n", [(100, 8), (1, 8), (13, 8), (5, 4),
                                       (9, 16)])
def test_era_sharpen_nondivisible_rows(rng, N, block_n):
    # regression: N % block_n != 0 used to assert; the kernel now pads the
    # row axis and slices the tail back off
    p = jax.nn.softmax(jax.random.normal(rng, (3, N, 21)), -1)
    out = era_sharpen_pallas(p, 0.1, block_n=block_n, interpret=True)
    assert out.shape == (N, 21)
    np.testing.assert_allclose(out, ref.era_sharpen_ref(p, 0.1), atol=1e-6)


def test_era_sharpen_nondivisible_under_jit(rng):
    p = jax.nn.softmax(jax.random.normal(rng, (2, 100, 17)), -1)
    out = jax.jit(lambda x: era_sharpen_pallas(x, 0.1, interpret=True))(p)
    np.testing.assert_allclose(out, ref.era_sharpen_ref(p, 0.1), atol=1e-6)


@pytest.mark.parametrize("N", [1, 100, 1000])
def test_era_kernel_path_any_open_batch(rng, N):
    """Acceptance pin: era(use_kernel=True) handles open-batch sizes that
    don't divide its row block (1, 100, 1000 with block_n=8)."""
    from repro.core import aggregation as agg
    p = jax.nn.softmax(jax.random.normal(rng, (3, N, 17)), -1)
    np.testing.assert_allclose(agg.era(p, 0.1, use_kernel=True),
                               agg.era(p, 0.1), atol=1e-5)


def test_era_kernel_interpret_resolution(monkeypatch):
    """use_kernel=True must not silently interpret off-CPU: the default
    (interpret=None) resolves to interpret mode on CPU only."""
    from repro.kernels import era_sharpen as es
    assert es.resolve_interpret(True) is True
    assert es.resolve_interpret(False) is False
    monkeypatch.setattr(es.jax, "default_backend", lambda: "cpu")
    assert es.resolve_interpret(None) is True
    monkeypatch.setattr(es.jax, "default_backend", lambda: "tpu")
    assert es.resolve_interpret(None) is False


# ------------------------------------------------- weighted ERA kernel ------
@pytest.mark.parametrize("K,N,C", [(2, 8, 10), (10, 64, 46), (5, 16, 512),
                                   (3, 31, 151)])
@pytest.mark.parametrize("T", [0.1, 0.5])
def test_weighted_era_sharpen_sweep(rng, K, N, C, T):
    """The fused weighted mean+sharpen kernel vs the jnp reference, fp32
    tolerance, including a zero-weight row and a non-divisible N."""
    from repro.kernels.era_sharpen import weighted_era_sharpen_pallas
    k1, k2 = jax.random.split(rng)
    p = jax.nn.softmax(jax.random.normal(k1, (K, N, C)) * 2, -1)
    w = jax.random.uniform(k2, (K,)).at[0].set(0.0)
    w = w / jnp.sum(w)
    out = weighted_era_sharpen_pallas(p, w, T, interpret=True)
    np.testing.assert_allclose(out, ref.weighted_era_sharpen_ref(p, w, T),
                               atol=1e-6)
    mean = weighted_era_sharpen_pallas(p, w, sharpen=False, interpret=True)
    np.testing.assert_allclose(
        mean, ref.weighted_era_sharpen_ref(p, w, sharpen=False), atol=1e-6)


def test_weighted_era_zero_weight_client_contributes_exactly_nothing(rng):
    """Acceptance pin: a zero-weight (absent) client's logits must not
    perturb the aggregate by a single bit — even when they are garbage."""
    from repro.kernels.era_sharpen import weighted_era_sharpen_pallas
    p = jax.nn.softmax(jax.random.normal(rng, (4, 9, 12)), -1)
    w = jnp.array([0.0, 0.5, 0.5, 0.0])
    garbage = p.at[0].set(1e30).at[3].set(-1e30)
    a = weighted_era_sharpen_pallas(p, w, 0.1, interpret=True)
    b = weighted_era_sharpen_pallas(garbage, w, 0.1, interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("N,block_n", [(100, 8), (1, 8), (13, 8), (9, 16)])
def test_weighted_era_nondivisible_rows(rng, N, block_n):
    from repro.kernels.era_sharpen import weighted_era_sharpen_pallas
    p = jax.nn.softmax(jax.random.normal(rng, (3, N, 21)), -1)
    w = jnp.array([0.2, 0.5, 0.3])
    out = weighted_era_sharpen_pallas(p, w, 0.1, block_n=block_n,
                                      interpret=True)
    assert out.shape == (N, 21)
    np.testing.assert_allclose(out, ref.weighted_era_sharpen_ref(p, w, 0.1),
                               atol=1e-6)


def test_aggregate_with_weights_routes_weighted_kernel(rng, monkeypatch):
    """Acceptance pin: aggregate(..., use_kernel=True) with weights must hit
    the fused weighted kernel (not the einsum+softmax fallback), and match
    it."""
    from repro.core import aggregation as agg
    calls = []
    orig = ops.weighted_era_sharpen_pallas
    monkeypatch.setattr(ops, "weighted_era_sharpen_pallas",
                        lambda *a, **k: calls.append(1) or orig(*a, **k))
    p = jax.nn.softmax(jax.random.normal(rng, (4, 8, 10)) * 2, -1)
    w = jnp.array([1.0, 2.0, 0.0, 1.0])
    for method in ("weighted_era", "era", "sa"):
        out = agg.aggregate(p, method, 0.1, weights=w, use_kernel=True,
                            interpret=True)
        exp = agg.aggregate(p, method, 0.1, weights=w)
        np.testing.assert_allclose(out, exp, atol=1e-6)
    assert len(calls) == 3
    # the LLM-shaped 4-D stack stays on the einsum path (kernel is 3-D)
    p4 = jax.nn.softmax(jax.random.normal(rng, (3, 2, 4, 8)), -1)
    out4 = agg.weighted_era(p4, jnp.ones((3,)), 0.1, use_kernel=True)
    np.testing.assert_allclose(out4, agg.weighted_era(p4, jnp.ones((3,)), 0.1),
                               atol=1e-6)
    assert len(calls) == 3


def test_masked_dsfl_round_uses_weighted_kernel(rng, monkeypatch):
    """DSFLAlgorithm(use_kernel=True): the masked (sim) round's aggregation
    routes through the fused weighted kernel."""
    import dataclasses
    from repro.core.algorithms import DSFLAlgorithm
    from repro.core.engine import FedEngine
    from repro.core.protocol import DSFLConfig
    from repro.data.pipeline import build_image_task
    from repro.models.smallnets import apply_tiny_mlp, init_tiny_mlp
    calls = []
    orig = ops.weighted_era_sharpen_pallas
    monkeypatch.setattr(ops, "weighted_era_sharpen_pallas",
                        lambda *a, **k: calls.append(1) or orig(*a, **k))
    task = build_image_task(seed=0, K=4, n_private=80, n_open=40, n_test=20,
                            distribution="non_iid")
    hp = DSFLConfig(rounds=1, local_epochs=1, distill_epochs=1, batch_size=20,
                    open_batch=20, aggregation="era")
    algo = DSFLAlgorithm(apply_tiny_mlp, hp, use_kernel=True)
    eng = FedEngine(algo)
    mask = jnp.array([1.0, 0.0, 1.0, 1.0])
    eng.on_ctx = lambda r, ctx: dataclasses.replace(ctx, mask=mask)
    eng.run(eng.init(lambda k: init_tiny_mlp(k), task), task, rounds=1)
    assert calls, "masked round fell back to einsum+softmax"
    # absent client still gets exactly zero aggregation weight
    assert float(eng.last_metrics["agg_weights"][1]) == 0.0


def test_weighted_era_all_zero_weights_fall_back_to_uniform(rng):
    """All-zero reliability weights must degrade to plain ERA (uniform
    weights), not sharpen a zero mean into a uniform teacher."""
    from repro.core import aggregation as agg
    p = jax.nn.softmax(jax.random.normal(rng, (4, 8, 10)) * 2, -1)
    out = agg.weighted_era(p, jnp.zeros((4,)), 0.1)
    np.testing.assert_allclose(out, agg.era(p, 0.1), atol=1e-5)
    # and NOT the sharpened-zero-mean (exactly uniform) failure mode
    assert float(jnp.max(jnp.abs(np.asarray(out) - 1.0 / p.shape[-1]))) > 0.1


@pytest.mark.parametrize("N,V,bn,bv", [(32, 128, 8, 32), (64, 1024, 16, 256),
                                       (128, 512, 128, 512), (8, 64, 8, 16),
                                       (40, 1000, 16, 256), (13, 300, 8, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
def test_distill_loss_sweep(rng, N, V, bn, bv, dtype):
    """The last two shapes pad both the row and the vocab axis."""
    k1, k2 = jax.random.split(rng)
    z = (jax.random.normal(k1, (N, V)) * 4).astype(dtype)
    t = jax.nn.softmax(jax.random.normal(k2, (N, V)), -1).astype(dtype)
    losses, logz = distill_loss_fwd_pallas(z, t, block_n=bn, block_v=bv,
                                           interpret=True)
    exp = ref.distill_loss_ref(z, t)
    atol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(losses, exp, atol=atol, rtol=1e-3)
    x = z.astype(jnp.float32)
    lz = jax.nn.logsumexp(x, axis=-1)
    np.testing.assert_allclose(logz, lz, atol=1e-4, rtol=1e-5)


def test_distill_loss_grad_matches_ref(rng):
    k1, k2 = jax.random.split(rng)
    z = jax.random.normal(k1, (64, 256)) * 3
    t = jax.nn.softmax(jax.random.normal(k2, (64, 256)), -1)
    g = jax.grad(lambda z_: ops.distill_loss(z_, t))(z)
    ge = ref.distill_loss_grad_ref(z, t, jnp.float32(1.0))
    np.testing.assert_allclose(g, ge, atol=1e-6)


def test_distill_loss_grad_matches_autodiff_of_ref(rng):
    k1, k2 = jax.random.split(rng)
    z = jax.random.normal(k1, (32, 96)) * 2
    t = jax.nn.softmax(jax.random.normal(k2, (32, 96)), -1)
    g_kernel = jax.grad(lambda z_: ops.distill_loss(z_, t))(z)
    g_auto = jax.grad(lambda z_: jnp.mean(ref.distill_loss_ref(z_, t)))(z)
    np.testing.assert_allclose(g_kernel, g_auto, atol=1e-5)


@pytest.mark.parametrize("N,V", [(300, 3000), (257, 2049)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
def test_distill_loss_padded_loss_and_grad_match_ref(rng, N, V, dtype):
    """Through `ops.distill_loss` at its default 256 x 2048 blocks, which
    divide neither axis: both shapes pad to 512 x 4096, so padded rows and
    columns must add nothing to the loss and receive no gradient."""
    k1, k2 = jax.random.split(rng)
    z = (jax.random.normal(k1, (N, V)) * 3).astype(dtype)
    t = jax.nn.softmax(jax.random.normal(k2, (N, V)), -1).astype(dtype)
    loss, g = jax.value_and_grad(lambda z_: ops.distill_loss(z_, t))(z)
    assert g.shape == (N, V) and g.dtype == dtype
    np.testing.assert_allclose(loss, jnp.mean(ref.distill_loss_ref(z, t)),
                               rtol=1e-5)
    ge = ref.distill_loss_grad_ref(z, t, jnp.float32(1.0))
    # the kernel rounds its f32 gradient to the input dtype once (gradients
    # of ~1e-6 are float16 subnormals, spaced by its smallest subnormal)
    fi = jnp.finfo(dtype)
    rtol = 1e-5 if dtype == jnp.float32 else float(fi.eps)
    np.testing.assert_allclose(np.asarray(g, np.float32), ge, rtol=rtol,
                               atol=max(1e-9, float(fi.smallest_subnormal)))


@pytest.mark.parametrize("M,Q,H,P,G,N", [
    (2, 8, 4, 8, 1, 8), (3, 16, 4, 8, 2, 8), (1, 32, 8, 16, 4, 16),
    (4, 16, 6, 8, 3, 4)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_ssd_chunk_sweep(rng, M, Q, H, P, G, N, dtype):
    ks = jax.random.split(rng, 4)
    x = jax.random.normal(ks[0], (M, Q, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (M, Q, H))).astype(dtype)
    dA = (-dt * 0.3).astype(dtype)
    B = jax.random.normal(ks[2], (M, Q, G, N), dtype)
    C = jax.random.normal(ks[3], (M, Q, G, N), dtype)
    y = ssd_chunk_pallas(x, dt, dA, B, C, interpret=True)
    exp = ref.ssd_chunk_ref(x, dt, dA, B, C)
    np.testing.assert_allclose(y, exp, atol=1e-4, rtol=1e-4)


def test_ssd_kernel_inside_mamba(rng):
    from repro.models.base import ModelConfig
    from repro.models.ssm import init_mamba, mamba_forward
    cfg = ModelConfig(name="s", arch_type="ssm", n_layers=2, d_model=64,
                      n_heads=0, n_kv_heads=0, d_ff=0, vocab=97, ssm_state=16,
                      ssm_head_dim=16, ssm_chunk=8, dtype="float32")
    p = init_mamba(rng, cfg)
    x = jax.random.normal(rng, (2, 16, 64))
    y_ref = mamba_forward(p, cfg, x)
    y_ker = mamba_forward(p, cfg, x, kernel_fn=ops.ssd_chunk)
    np.testing.assert_allclose(y_ref, y_ker, atol=1e-5)
