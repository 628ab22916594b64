"""Ulysses (all-to-all) sequence parallelism — exactness vs full
attention, parity with ring attention (including bit-identical dropout
masks), composition with dp/tp, gradients, dispatch, and the CLI."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_vit_paper_replication_tpu import parallel
from pytorch_vit_paper_replication_tpu.configs import MeshConfig


def _qkv(seed, b, t, h, d):
    ks = jax.random.split(jax.random.key(seed), 3)
    return tuple(jax.random.normal(k, (b, t, h, d)) for k in ks)


def test_ulysses_exact(devices):
    """Ulysses over the 'seq' axis equals full attention (h=8 divides)."""
    mesh = parallel.make_mesh(MeshConfig(data=1, model=1, seq=8))
    q, k, v = _qkv(0, 2, 64, 8, 16)
    ref = jax.nn.dot_product_attention(q, k, v)
    out = parallel.make_ulysses_attention(mesh)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


def test_ulysses_with_dp_and_tp(devices):
    """Composes with DP and TP on a 2x2x2 mesh (heads sharded over model
    AND re-split over seq)."""
    mesh = parallel.make_mesh(MeshConfig(data=2, model=2, seq=2))
    q, k, v = _qkv(1, 4, 32, 4, 16)
    ref = jax.nn.dot_product_attention(q, k, v)
    out = parallel.make_ulysses_attention(mesh, head_axis="model")(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


def test_ulysses_matches_ring(devices):
    """The two SP strategies compute the same attention (deterministic)."""
    mesh = parallel.make_mesh(MeshConfig(data=2, model=1, seq=4))
    q, k, v = _qkv(2, 2, 64, 4, 16)
    out_u = parallel.make_ulysses_attention(mesh)(q, k, v)
    out_r = parallel.make_ring_attention(mesh)(q, k, v)
    np.testing.assert_allclose(np.asarray(out_u), np.asarray(out_r),
                               rtol=1e-4, atol=1e-5)


def test_ulysses_gradient(devices):
    """all_to_all is differentiable; backward equals full attention's."""
    mesh = parallel.make_mesh(MeshConfig(data=2, model=1, seq=4))
    q, k, v = _qkv(3, 2, 32, 4, 16)
    uly = parallel.make_ulysses_attention(mesh)

    def loss_u(args):
        return jnp.sum(jnp.sin(uly(*args)))

    def loss_f(args):
        return jnp.sum(jnp.sin(jax.nn.dot_product_attention(*args)))

    g_u = jax.grad(loss_u)((q, k, v))
    g_f = jax.grad(loss_f)((q, k, v))
    for name, a, b in zip("qkv", g_u, g_f):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-4, err_msg=f"d{name}")


def test_ulysses_dropout_mask_identical_to_ring(devices):
    """The LOAD-BEARING noise claim: for one seed, ulysses and ring drop
    the exact same attention-weight elements (both hash GLOBAL
    coordinates), so switching SP strategy never changes the training
    noise. Recovered via the v=identity trick (q=k=0 -> output rows ARE
    the dropped weight rows)."""
    rate, b, h, t = 0.25, 2, 4, 64
    rng = jax.random.key(5)
    z = jnp.zeros((b, t, h, t), jnp.float32)
    eye = jnp.broadcast_to(jnp.eye(t, dtype=jnp.float32)[None, :, None, :],
                           (b, t, h, t))
    mesh = parallel.make_mesh(MeshConfig(data=2, model=1, seq=4))
    w_u = np.asarray(parallel.make_ulysses_attention(
        mesh, dropout_rate=rate, dropout_rng=rng,
        deterministic=False)(z, z, eye))
    w_r = np.asarray(parallel.make_ring_attention(
        mesh, dropout_rate=rate, dropout_rng=rng,
        deterministic=False)(z, z, eye))
    np.testing.assert_array_equal(w_u > 0, w_r > 0)
    np.testing.assert_allclose(w_u, w_r, rtol=1e-5)
    frac = 1.0 - (w_u > 0).mean()
    assert abs(frac - 0.25) < 0.02


def test_ulysses_rejects_indivisible_heads(devices):
    """h=2 on seq=4: a clear error from the op (the DISPATCH falls back
    to XLA instead — next test)."""
    mesh = parallel.make_mesh(MeshConfig(data=2, model=1, seq=4))
    q, k, v = _qkv(4, 2, 32, 2, 16)
    with pytest.raises(ValueError, match="divisible"):
        parallel.make_ulysses_attention(mesh)(q, k, v)


def test_dispatch_ulysses_and_head_fallback(devices):
    """on_mesh(sp_impl='ulysses') routes through the all-to-all
    path when heads divide, and warns+falls back to the gathered XLA path
    when they don't — never a crash mid-model."""
    import warnings

    from pytorch_vit_paper_replication_tpu.ops.attention import (
        dot_product_attention)
    from pytorch_vit_paper_replication_tpu.ops import on_mesh

    mesh = parallel.make_mesh(MeshConfig(data=2, model=1, seq=4))
    q, k, v = _qkv(5, 2, 32, 4, 16)
    ref = jax.nn.dot_product_attention(q, k, v)
    with on_mesh(mesh, sp_impl="ulysses"):
        out = dot_product_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)

    qs, ks_, vs = _qkv(6, 2, 32, 2, 16)  # h=2 not divisible by 4
    with on_mesh(mesh, sp_impl="ulysses"):
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            out2 = dot_product_attention(qs, ks_, vs)
    assert any("ulysses" in str(x.message) for x in w)
    np.testing.assert_allclose(
        np.asarray(out2), np.asarray(jax.nn.dot_product_attention(
            qs, ks_, vs)), rtol=2e-2, atol=2e-2)


def test_cli_trains_with_ulysses(devices, tmp_path):
    """--sp-impl ulysses end-to-end through the CLI. ViT-S/16 (6 heads,
    divisible by seq=2) with gap pooling for an even token count."""
    from pytorch_vit_paper_replication_tpu.train import main as train_main

    results = train_main([
        "--synthetic", "--preset", "ViT-S/16", "--image-size", "32",
        "--patch-size", "16", "--pool", "gap", "--dtype", "float32",
        "--attention", "xla", "--epochs", "1", "--batch-size", "8",
        "--mesh-data", "4", "--mesh-seq", "2", "--sp-impl", "ulysses",
        "--metrics-jsonl", str(tmp_path / "m.jsonl"),
    ])
    assert len(results["train_loss"]) == 1
    assert math.isfinite(results["train_loss"][0])
