"""The plain float32 reference agrees with the program's model at a tiny
size: closely in float32, and within the chip tolerance in bfloat16."""

import numpy as np
import pytest

from benchmark.lib import reference_vit


@pytest.mark.parametrize("dtype,limit", [("float32", 1e-4),
                                         ("bfloat16",
                                          reference_vit.TOLERANCE)])
def test_reference_agrees_with_the_programs_model(dtype, limit):
    import jax

    from pytorch_vit_paper_replication_tpu.configs import ViTConfig
    from pytorch_vit_paper_replication_tpu.models import ViT

    cfg = ViTConfig(image_size=32, patch_size=8, num_layers=3, num_heads=4,
                    embedding_dim=64, mlp_size=128, num_classes=10,
                    dtype=dtype)
    model = ViT(cfg)
    x = np.random.default_rng(0).random((4, 32, 32, 3), dtype=np.float32)
    params = jax.jit(model.init)(jax.random.key(0), x[:1])["params"]
    # Biases and LN offsets are zero at init: perturb every leaf, so that
    # a term the reference leaves out cannot hide.
    leaves, tree = jax.tree.flatten(params)
    rng = np.random.default_rng(1)
    params = jax.tree.unflatten(tree, [
        np.asarray(a) + 0.05 * rng.standard_normal(a.shape).astype(
            np.float32) for a in leaves])
    got = np.asarray(model.apply({"params": params}, x, False), np.float32)
    want = np.asarray(reference_vit.forward(
        params, x, patch_size=cfg.patch_size, ln_epsilon=cfg.ln_epsilon))
    assert got.shape == want.shape == (4, 10)
    assert reference_vit.agreement(got, want) < limit


def test_agreement_is_in_units_of_the_references_spread():
    want = np.array([[0.0, 2.0], [4.0, 2.0]])     # std = sqrt(2)
    assert reference_vit.agreement(want + 0.1, want) == pytest.approx(
        0.1 / np.sqrt(2.0))


def test_log_rows_recover_logits_up_to_a_constant():
    logits = np.array([[1.0, -2.0, 0.5]])
    probs = np.exp(logits) / np.exp(logits).sum()
    assert np.allclose(reference_vit.log_rows(probs),
                       logits - logits.mean())
