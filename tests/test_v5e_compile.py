"""Compile for the chip without the chip (ISSUE 21).

The installed libtpu describes a v5e host with no hardware present, and
``jit(...).lower(<avals sharded over its devices>).compile()`` then runs
XLA:TPU and Mosaic for real. Nothing executes — this proves only that
the program the trainer builds for a four-chip host is one the compiler
accepts, which on the CPU (Pallas in interpret mode, plain HLO) no test
can see: XLA refuses to partition a Mosaic call, so the kernels have to
arrive already wrapped per shard.

The kernel dispatch reads ``jax.default_backend()``, which is ``cpu``
in this process, so the test patches it — exactly what it is standing
in for.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pytorch_vit_paper_replication_tpu import engine, parallel
from pytorch_vit_paper_replication_tpu.configs import TrainConfig, ViTConfig
from pytorch_vit_paper_replication_tpu.models import ViT
from pytorch_vit_paper_replication_tpu.ops.partition import mosaic_calls
from pytorch_vit_paper_replication_tpu.optim import make_optimizer
from pytorch_vit_paper_replication_tpu.telemetry import device_trace


@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        # Without it libtpu asks a metadata server that is not there.
        mp.setenv("TPU_SKIP_MDS_QUERY", "1")
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2",
            chip_config_name="default", chips_per_host_bounds=(2, 2, 1),
            num_slices=1)
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return topo.devices


def _lower_train_step(devices, cfg, *, dp, tp, batch):
    """The trainer's own step builder, lowered for `devices` from avals
    alone (two-layer ViT-B/16 width unless `cfg` says otherwise)."""
    mesh = Mesh(np.array(devices).reshape(dp, tp, 1, 1), parallel.AXES)
    model = ViT(cfg)
    tx = make_optimizer(TrainConfig(batch_size=batch), 100)
    size = cfg.image_size

    def abstract_state():
        params = model.init(jax.random.key(0),
                            jnp.zeros((1, size, size, 3)))["params"]
        return engine.TrainState.create(
            apply_fn=model.apply, params=params, tx=tx,
            rng=jax.random.key(0, impl="unsafe_rbg"))

    state = jax.eval_shape(abstract_state)
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        state, parallel.state_shardings(state, mesh))
    rows = NamedSharding(mesh, P("data"))
    example = {
        "image": jax.ShapeDtypeStruct((batch, size, size, 3), jnp.float32,
                                      sharding=rows),
        "label": jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=rows)}
    return parallel.make_parallel_train_step(state, mesh).lower(
        state, example)


@pytest.fixture(scope="module")
def dp4_step(v5e_2x2):
    """ViT-B/16's width, two layers, global batch 1024 on a dp=4 mesh:
    the lowered step's text and, from the file's one compile, the
    optimized HLO's."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        lowered = _lower_train_step(
            v5e_2x2, ViTConfig(num_layers=2, num_classes=3), dp=4, tp=1,
            batch=1024)
        return lowered.as_text(), lowered.compile().as_text()


def test_dp4_train_step_compiles_with_per_shard_mosaic_calls(dp4_step):
    """The step lowers with one fused fwd and one bwd Mosaic call per
    layer, each over the PER-SHARD rows (256 images x 197 tokens), and
    the v5e compiler takes it."""
    lowered_text, hlo = dp4_step
    calls = mosaic_calls(lowered_text)
    assert sorted(name for name, _ in calls) == [
        "lnmlp_bwd"] * 2 + ["lnmlp_fwd"] * 2
    assert {shape for _, shape in calls} == {(256 * 197, 768)}
    assert hlo.count('custom_call_target="tpu_custom_call"') == 4
    assert "all-reduce" in hlo   # the gradient sum over 'data'


def test_compiled_step_keeps_the_scopes_and_the_kernels_names(dp4_step):
    """What telemetry/device_trace.py joins a captured op to: the
    optimized HLO's ``op_name`` s hold the modules' names, the
    ``named_scope`` s of the attention core and of the train step, and
    the kernels' ``name=`` — metadata of the same four Mosaic calls."""
    lowered_text, hlo = dp4_step
    assert "_kernel" not in "".join(n for n, _ in mosaic_calls(lowered_text))
    program = device_trace.parse_scopes(hlo)
    assert program["module"] == "jit_train_step"
    paths = set(program["scopes"].values())
    for scope in ("jvp(loss)", "/optimizer/", "/metrics/", "patch_embedding",
                  "/msa/norm/", "/msa/qkv/", "/msa/attn_core/", "/msa/out/",
                  "transpose(jvp(ViT))/backbone/encoder_block_1/msa/"
                  "attn_core/", "/encoder_norm/", "/head/"):
        assert any(scope in path for path in paths), scope
    kernels = sorted(
        device_trace.kernel_name({"name": name, "scope": scope})
        for name, scope in program["scopes"].items()
        if name.startswith("lnmlp") and "pallas_call" in scope)
    assert kernels == ["lnmlp_bwd"] * 2 + ["lnmlp_fwd"] * 2
    # nearly every path of an instruction (the program's arguments are
    # named after the state's leaves) has a layer of the table
    layers = [device_trace.classify(path)[0] for path in paths
              if path.startswith("jit(")]
    assert layers.count("other") < 0.05 * len(layers)


def test_dp2_tp2_step_lowers_hidden_sliced_mlp_and_per_shard_flash(
        v5e_2x2, monkeypatch):
    """A mesh with a model axis takes the hidden-sliced core MLP kernel
    (fc1 columns halved), and a forced flash attention runs over the
    shard's batch x its half of the heads — lowered only; the dp=4 test
    pays for the one compile."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = ViTConfig(num_layers=1, num_classes=3, image_size=384,
                    attention_impl="flash", attn_dropout=0.1)
    calls = mosaic_calls(_lower_train_step(
        v5e_2x2, cfg, dp=2, tp=2, batch=64).as_text())
    # MLP: the core kernels (not lnmlp_*), over the data shard's 32
    # images x 577 tokens padded up to whole 256-row blocks.
    mlp_rows = -(-32 * 577 // 256) * 256
    # Flash: q folded to [images x local heads, tokens padded, head_dim].
    q = (32 * 6, 768, 64)
    assert sorted(calls) == sorted([
        ("mlp_fwd", (mlp_rows, 768)), ("mlp_bwd", (mlp_rows, 768)),
        ("flash_fwd", q), ("flash_bwd_dq", q), ("flash_bwd_dkv", q)])
