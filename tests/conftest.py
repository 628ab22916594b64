"""Test harness: run everything on a virtual 8-device CPU mesh.

Standard JAX trick for exercising sharding/collective code without TPUs
(SURVEY.md §4d): force the host platform and split it into 8 virtual
devices. Must happen before jax initializes, hence module scope here.
"""

import os

# The suite runs on the CPU whatever the environment presets (a TPU host
# exports JAX_PLATFORMS=tpu,cpu); the chip is exercised by chip_smoke.py,
# one process per chip, not by pytest.
os.environ["JAX_PLATFORMS"] = "cpu"
# A compile cache placed from outside is for the chip's programs, and it
# overrides every --compile-cache-dir: the cold/warm tests need the
# directories they name.
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture()
def rng():
    return jax.random.key(0)


@pytest.fixture(scope="session")
def tiny_config():
    """A ViT small enough for CPU tests but structurally identical to B/16."""
    from pytorch_vit_paper_replication_tpu.configs import ViTConfig

    return ViTConfig(image_size=32, patch_size=8, num_layers=2, num_heads=2,
                     embedding_dim=32, mlp_size=64, num_classes=3,
                     dtype="float32", attention_impl="xla")


@pytest.fixture(scope="session")
def synthetic_folder(tmp_path_factory):
    from pytorch_vit_paper_replication_tpu.data import (
        make_synthetic_image_folder)

    root = tmp_path_factory.mktemp("dataset")
    train_dir, test_dir = make_synthetic_image_folder(
        root, train_per_class=6, test_per_class=3, image_size=32)
    return train_dir, test_dir


@pytest.fixture()
def np_rng():
    return np.random.default_rng(0)
