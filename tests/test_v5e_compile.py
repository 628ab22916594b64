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

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (
    Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding)

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


def _entry(hlo):
    """The entry computation's instructions (an op fused into another is
    inside that fusion's computation, not here)."""
    return hlo[hlo.index("\nENTRY "):].splitlines()


def _copies_beside_the_core(hlo, batch, tokens=197, width=None):
    """Instructions of the entry computation that copy a ``[batch,
    tokens, ...]`` array (``[batch, tokens, width]`` where a width is
    given) under the scope ``attn_core``: a layout change between a
    projection and the kernel that is a pass over HBM of its own (one
    fused into a GEMM's operand is inside that fusion's computation,
    not here)."""
    shape = r"= \w+\[%d,%d," % (batch, tokens) + (
        "" if width is None else r"%d\]" % width)
    return [line for line in _entry(hlo)
            if re.search(r" (copy|transpose)\(", line)
            and "/attn_core/" in line and re.search(shape, line)]


def test_dp4_train_step_compiles_with_per_shard_mosaic_calls(dp4_step):
    """The step lowers with a forward and a backward Mosaic call per
    layer for the MLP half-block and for the attention core, each over
    the PER-SHARD operand (256 images x 197 tokens of rows; the packed
    qkv projection of 256 images), and the v5e compiler takes it."""
    lowered_text, hlo = dp4_step
    calls = mosaic_calls(lowered_text)
    assert sorted(name for name, _ in calls) == (
        ["attn_short_bwd"] * 2 + ["attn_short_fwd"] * 2
        + ["lnmlp_bwd"] * 2 + ["lnmlp_fwd"] * 2)
    assert {shape for name, shape in calls if name.startswith("lnmlp")} == {
        (256 * 197, 768)}
    assert {shape for name, shape in calls if name.startswith("attn")} == {
        (256, 197, 3 * 768)}
    assert hlo.count('custom_call_target="tpu_custom_call"') == 8
    assert "all-reduce" in hlo   # the gradient sum over 'data'


def test_compiled_step_holds_no_logits_and_no_layout_copies(dp4_step):
    """What the kernel is for: no ``[B, H, T, T]`` tensor anywhere in the
    optimized HLO, nothing under ``msa`` outside norm / qkv / attn_core /
    out (the q, k, v slices and their layout copies: ``msa_glue``), and
    no copy beside the kernels - the flat projections let the compiler
    write the kernel's layout from the GEMM itself and read its results
    the same way."""
    _, hlo = dp4_step
    assert not re.search(r"\[256,12,197,197\]", hlo)
    program = device_trace.parse_scopes(hlo)
    assert not [path for path in program["scopes"].values()
                if device_trace.classify(path)[0] == "msa_glue"]
    assert not _copies_beside_the_core(hlo, 256)


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
        (device_trace.kernel_name({"name": name, "scope": scope}), scope)
        for name, scope in program["scopes"].items()
        if name.startswith(("lnmlp", "attn_short"))
        and "pallas_call" in scope)
    assert [name for name, _ in kernels] == (
        ["attn_short_bwd"] * 2 + ["attn_short_fwd"] * 2
        + ["lnmlp_bwd"] * 2 + ["lnmlp_fwd"] * 2)
    # the attention pair under the core's scope, forward and backward
    for name, scope in kernels[:4]:
        assert "/msa/attn_core/" in scope, scope
        assert ("transpose(jvp(ViT))" in scope) == name.endswith("bwd"), scope
        assert device_trace.classify(scope)[0] == "attn_core"
    # nearly every path of an instruction (the program's arguments are
    # named after the state's leaves) has a layer of the table
    layers = [device_trace.classify(path)[0] for path in paths
              if path.startswith("jit(")]
    assert layers.count("other") < 0.05 * len(layers)


def test_one_chip_l16_width_layer_compiles(v5e_2x2, monkeypatch):
    """ViT-L/16's width on one chip (16 heads of 64, 1024 wide, bs 96):
    eight slabs of heads, a batch that is no multiple of 128 - the
    compiler lays that projection out token-minor where B/16's is
    batch-minor - and the same kernel pair, no logits, no copies beside
    it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = ViTConfig(num_layers=1, num_classes=3, embedding_dim=1024,
                    num_heads=16, mlp_size=4096)
    lowered = _lower_train_step(v5e_2x2[:1], cfg, dp=1, tp=1, batch=96)
    calls = mosaic_calls(lowered.as_text())
    assert sorted(calls) == sorted([
        ("attn_short_fwd", (96, 197, 3072)),
        ("attn_short_bwd", (96, 197, 3072)),
        # rows padded up to whole 256-row blocks
        ("lnmlp_fwd", (18944, 1024)), ("lnmlp_bwd", (18944, 1024))])
    hlo = lowered.compile().as_text()
    assert not re.search(r"\[96,16,197,197\]", hlo)
    assert not _copies_beside_the_core(hlo, 96)


@pytest.mark.parametrize("shape,dtype", [
    ((32, 512, 3, 12, 64), jnp.bfloat16),    # 3 images a grid step
    ((16, 540, 3, 12, 64), jnp.bfloat16),    # 1: the longest at Dh = 64
    ((16, 577, 3, 8, 128), jnp.bfloat16),    # one head in flight
    ((8, 512, 3, 12, 64), jnp.float32),
], ids=str)
def test_attention_kernels_compile_at_the_longest_lengths_planned(
        v5e_2x2, shape, dtype):
    """Where ``short_attention.plan`` still finds room, Mosaic does too:
    forward and backward compile inside the VMEM limit they are given."""
    from pytorch_vit_paper_replication_tpu.ops import short_attention

    b, t, _, _, dh = shape
    assert short_attention.plan(b, t, dh, jnp.dtype(dtype).itemsize)
    qkv = jax.ShapeDtypeStruct(
        shape, dtype, sharding=SingleDeviceSharding(v5e_2x2[0]))
    compiled = jax.jit(jax.grad(lambda x: jnp.sum(
        short_attention.short_attention(x, interpret=False).astype(
            jnp.float32)))).lower(qkv).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2


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
        ("flash_fwd", q), ("flash_bwd", q)])


# ------------------------------------------------------- the token model
# The cell's step program by ``memory_analysis``, GiB (the parent of PR 28
# compiled to 13.54).
STEP_GIB = 13.9
def _lower_lm_step(devices, cfg, *, dp, batch, seq_len):
    """The trainer's step builder for a token model, from avals alone."""
    mesh = Mesh(np.array(devices).reshape(dp, 1, 1, 1), parallel.AXES)
    model = ViT(cfg)
    tx = make_optimizer(TrainConfig(batch_size=batch), 100)

    def abstract_state():
        params = model.init(jax.random.key(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
        return engine.TrainState.create(
            apply_fn=model.apply, params=params, tx=tx,
            rng=jax.random.key(0, impl="unsafe_rbg"))

    state = jax.eval_shape(abstract_state)
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        state, parallel.state_shardings(state, mesh))
    rows = NamedSharding(mesh, P("data"))
    ids = jax.ShapeDtypeStruct((batch, seq_len), jnp.int32, sharding=rows)
    return parallel.make_parallel_train_step(state, mesh).lower(
        state, {"tokens": ids, "label": ids})


@pytest.mark.parametrize("kind,window", [("causal", 0),
                                         ("causal_window", 4096)])
def test_flash_kernels_compile_at_the_token_cells_shapes(v5e_2x2, kind,
                                                        window):
    """T = 16,384, 28 query heads over 4 key/value heads of 128: whole k
    and v of a head in VMEM (4 MiB each, beyond the default scoped limit),
    loop bounds computed from the block's position, heads read as column
    blocks of the projection: the forward and the one backward kernel
    compile, the backward with k and v whole, the two float32 slabs in
    which it sums dk and dv over a key/value head's query blocks and its
    group of 7, and their results in VMEM at once (64 MiB asked)."""
    from pytorch_vit_paper_replication_tpu.ops.flash_attention import (
        flash_attention)

    one = SingleDeviceSharding(v5e_2x2[0])
    q = jax.ShapeDtypeStruct((1, 16384, 28, 128), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((1, 16384, 4, 128), jnp.bfloat16, sharding=one)
    compiled = jax.jit(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, kind=kind, window=window, interpret=False).astype(
        jnp.float32)), argnums=(0, 1, 2))).lower(q, kv, kv).compile()
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
    # no transposed copy of q (or of its gradient) around the kernels
    assert not re.search(r"bf16\[28,16384,128\]", hlo)
    # dk and dv leave the kernel once a key/value head: none per query
    # head in float32 for XLA to sum over the group
    assert not re.search(r"f32\[1,16384,3584\]", hlo)


def test_token_models_step_compiles_and_fits_one_chip(v5e_2x2, monkeypatch):
    """The cell ``st21b_train_16k``'s step, as the trainer builds it: one
    16,384-token sequence through SmallThinker's period of four layers at
    every published width. It names the flash kernels once a layer and
    the grouped products of the held experts (two chunks of tokens, each
    a loop of passes whose body is lowered once; the first product taken
    again in the backward pass), compiles for the v5e, fits 15.75 GiB
    with room, without rematerialisation, and holds no activation array
    of the routed layer's worst-case rows: its passes run over
    ``moe.buffer_rows`` rows."""
    from pytorch_vit_paper_replication_tpu.configs import LM_PRESETS
    from pytorch_vit_paper_replication_tpu.ops import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = LM_PRESETS["smallthinker-21b-a3b-ep4"]()
    lowered = _lower_lm_step(v5e_2x2[:1], cfg, dp=1, batch=1,
                             seq_len=cfg.max_seq_len)
    names = [name for name, _ in mosaic_calls(lowered.as_text())]
    assert {n: names.count(n) for n in set(names)} == {
        "flash_fwd": 4, "flash_bwd": 4,
        "moe_gmm_fwd": 24, "moe_gmm_dx": 16, "moe_gmm_dw": 16}
    compiled = lowered.compile()
    m = compiled.memory_analysis()
    held = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)
    assert held < STEP_GIB * 2**30, held / 2**30
    hlo = compiled.as_text()
    paths = set(device_trace.parse_scopes(hlo)["scopes"].values())
    layers = [device_trace.classify(path)[0] for path in paths
              if path.startswith("jit(")]
    for layer in ("moe_router", "moe_dispatch", "moe_experts",
                  "moe_combine"):
        assert layer in layers, layer
    for scope in ("/mlp/while/body/moe_dispatch/",
                  "/mlp/while/body/moe_experts/",
                  "/mlp/while/body/moe_combine/",
                  "patch_embedding/token_embedding", "/head/head/",
                  "/head/loss/", "/msa/attn_core/"):
        assert any(scope in path for path in paths), scope
    # (the rotary embedding's scope, ``msa/rope``, is in the lowered text
    # only: the compiler fuses it into its neighbours, and a fusion
    # carries one path)
    assert "/msa/rope/" in lowered.as_text(debug_info=True)
    assert layers.count("other") < 0.05 * len(layers)
    # 8,192 tokens x 6 a chunk: 53,248 rows in the worst case, 20,480 a
    # pass. Of the worst case only the int32 tables are left (one column).
    pairs = cfg.max_seq_len // 2 * cfg.experts_per_token
    worst = moe.worst_case_rows(pairs, cfg.num_experts_held, moe.ROW_TILE)
    rows = moe.buffer_rows(pairs, cfg.num_experts_held, cfg.num_experts,
                           moe.ROW_TILE)
    assert (worst, rows) == (53248, 20480)
    tables = -(-worst // rows) * rows
    wide = set(re.findall(r"\w+\[(?:%d|%d),\d+[\],]" % (worst, tables),
                          hlo))
    assert not wide, wide
    assert re.search(r"bf16\[%d,2560\]" % rows, hlo)
    # the weight gradients go from pass to pass and from chunk to chunk
    # where they lie: no copy of one is made
    assert not re.search(r"= f32\[16,(?:2560,1536|768,2560)\]\S* copy\(",
                         hlo)


def test_token_model_is_partitioned_per_shard_on_a_data_mesh(v5e_2x2,
                                                             monkeypatch):
    """dp = 4, two sequences a chip at the tiny preset's depth and the
    cell's head size: the routed layer and the flash kernels arrive
    wrapped per shard (lowered only)."""
    from pytorch_vit_paper_replication_tpu.configs import LM_PRESETS

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = LM_PRESETS["lm-tiny"](
        num_layers=1, max_seq_len=1024, head_dim_override=128,
        embedding_dim=256, expert_width=128, attention_impl="flash")
    calls = mosaic_calls(_lower_lm_step(v5e_2x2, cfg, dp=4, batch=8,
                                        seq_len=1024).as_text())
    assert {name for name, _ in calls} == {
        "flash_fwd", "flash_bwd", "moe_gmm_fwd", "moe_gmm_dx",
        "moe_gmm_dw"}
    assert {shape for name, shape in calls if name == "flash_fwd"} == {
        (2, 1024, 4 * 128)}


# ------------------------------------------------- latent attention (PR 32)
# The cell ``glm47f_train_16k``'s step held 14.28 GiB when PR 32 compiled
# it (10.53 of them parameters, gradients and moments): the bound leaves
# a fifth of a GiB, and a block kept whole for the backward pass (1 GB)
# passes it.
MLA_STEP_GIB = 14.5


def test_flash_kernels_compile_at_head_size_256(v5e_2x2):
    """T = 16,384, 20 ungrouped heads of 256 (the latent attention's
    materialised heads): k and v of a head are 8 MiB each, the backward
    keeps them, its two float32 sums (16 MiB each) and their results in
    VMEM at once, 100.3 MiB by the compiler's count: over the 100 MiB
    the limit was capped at until PR 32, inside the 112 it asks now."""
    from pytorch_vit_paper_replication_tpu.ops.flash_attention import (
        flash_attention)

    one = SingleDeviceSharding(v5e_2x2[0])
    x = jax.ShapeDtypeStruct((1, 16384, 20, 256), jnp.bfloat16, sharding=one)
    compiled = jax.jit(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, kind="causal", interpret=False).astype(jnp.float32)),
        argnums=(0, 1, 2))).lower(x, x, x).compile()
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
    # heads are column blocks of [B, T, H x 256]: no transposed copy
    assert not re.search(r"bf16\[20,16384,256\]", hlo)


@pytest.fixture(scope="module")
def mla_step(v5e_2x2):
    """The cell ``glm47f_train_16k``'s step as the trainer builds it,
    lowered and compiled once for the file: the lowered step and the
    compiled one."""
    from pytorch_vit_paper_replication_tpu.configs import LM_PRESETS

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        cfg = LM_PRESETS["glm-4.7-flash-ep8"]()
        lowered = _lower_lm_step(v5e_2x2[:1], cfg, dp=1, batch=1,
                                 seq_len=cfg.max_seq_len)
        return lowered, lowered.compile()


def test_latent_attention_models_step_compiles_and_fits_one_chip(mla_step):
    """The cell ``glm47f_train_16k``'s step, as the trainer builds it: one
    16,384-token sequence through GLM-4.7-Flash's leading dense layer, 4
    routed layers and the multi-token-prediction module at every
    published width. Six blocks name the flash kernels once each — the
    blocks take q, k and v again from the latents in the backward pass,
    and the core's forward is NOT taken again — and the five routed ones
    the grouped products (65,536 pairs: two chunks of tokens). It compiles
    for the v5e and fits 15.75 GiB with that one recomputation and no
    other; every new scope is in the compiled step, and next to nothing
    falls outside the table's rows."""
    lowered, compiled = mla_step
    names = [name for name, _ in mosaic_calls(lowered.as_text())]
    assert {n: names.count(n) for n in set(names)} == {
        "flash_fwd": 6, "flash_bwd": 6,
        "moe_gmm_fwd": 30, "moe_gmm_dx": 20, "moe_gmm_dw": 20}
    m = compiled.memory_analysis()
    held = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)
    assert held < MLA_STEP_GIB * 2**30, held / 2**30
    paths = set(device_trace.parse_scopes(compiled.as_text())[
        "scopes"].values())
    layers = [device_trace.classify(path)[0] for path in paths
              if path.startswith("jit(")]
    for layer in ("mla_q", "mla_kv", "moe_shared", "mtp_merge", "mtp_block",
                  "mtp_head", "moe_router", "moe_experts", "attn_core"):
        assert layer in layers, layer
    for scope in ("/msa/qkv/q_up/", "/msa/qkv/kv_up/",
                  "checkpoint/rematted_computation/msa/qkv/",
                  "/mlp/moe_shared/shared/", "/mlp/dense/",
                  "/mtp/patch_embedding/mtp_merge/eh_proj/",
                  "/mtp/encoder_block_5/checkpoint/msa/attn_core/flash_bwd/",
                  "/mtp/head/head/", "/mtp/head/loss/"):
        assert any(scope in path for path in paths), scope
    assert layers.count("other") < 0.01 * len(layers)


def test_latent_attention_hands_the_kernels_q_k_v_where_they_read_them(
        mla_step):
    """q, k, v and ``o`` lie as column blocks of ``[1, 16384, 20 x 256]``
    from the latent products to the flash kernels and back, forward,
    recomputed and as cotangents: the compiled step's entry computation
    copies no such array under ``attn_core`` (PR 32's step held 60: q, k,
    v before the forward call and ``o`` after it, q, k, v again in the
    recomputation, dq, dk, dv after the backward call, six blocks), and
    nothing under ``msa`` results in a ``[1, 16384, 20, 256]`` or
    ``[1, 16384, 20, 448]`` array, whose heads XLA:TPU lays on sublanes
    (PR 32's held 78: the 4-D products, the rotary concatenations, the
    slices)."""
    _, compiled = mla_step
    hlo = compiled.as_text()
    copies = _copies_beside_the_core(hlo, 1, 16384, 5120)
    assert not copies, (len(copies), copies[0][:300])
    four_d = [line for line in _entry(hlo) if "/msa/" in line and re.search(
        r"= \(?\w+\[1,16384,20,(?:256|448)\]", line)]
    assert not four_d, (len(four_d), four_d[0][:300])
    # the kernels themselves read and write the flat layout
    assert re.search(r"= \(bf16\[1,16384,5120\]\S*, f32\[20,1,16384\]\S*\) "
                     r"custom-call\(", hlo)


# ------------------------------- attention an indexer selects (PR 34)
# The cell ``keye2_train_16k``'s step held 12.15 GiB when PR 34 compiled
# it (9.82 of them parameters, gradients and moments). Kept as one int8
# ``[T, T]`` a layer the selection alone adds 1.5 GiB, and the step did
# not fit at all before it was kept a bit a pair (17.1 GiB). Since PR 35
# (the alignment loss's pass as two kernels, the loss tied to the block's
# result and a block's weight gradients to its input's) it holds 11.74.
DSA_STEP_GIB = 12.6


@pytest.fixture(scope="module")
def dsa_step(v5e_2x2):
    """The cell ``keye2_train_16k``'s step as the trainer builds it,
    lowered and compiled once for the file."""
    from pytorch_vit_paper_replication_tpu.configs import LM_PRESETS

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        cfg = LM_PRESETS["keye-vl-2.0-30b-a3b-ep8"]()
        lowered = _lower_lm_step(v5e_2x2[:1], cfg, dp=1, batch=1,
                                 seq_len=cfg.max_seq_len)
        return lowered, lowered.compile()


def test_indexed_attention_models_step_compiles_and_fits_one_chip(dsa_step):
    """One 16,384-token sequence through 6 of Keye-VL-2.0-30B-A3B's
    layers at every published width. Every layer names the flash kernels
    once each way, reading the selection as an int8 strip a query block
    (Mosaic takes it; the forward holds k, v and the strip in VMEM), the
    alignment loss's two kernels (PR 35) and the grouped products of its
    16 held experts; the blocks take their projections again in the
    backward pass and neither the selection, nor the core's forward, nor
    the loss's pass. It compiles for the v5e and fits 15.75 GiB with
    room; every new scope is in the compiled step."""
    lowered, compiled = dsa_step
    names = [name for name, _ in mosaic_calls(lowered.as_text())]
    assert {n: names.count(n) for n in set(names)} == {
        "flash_fwd": 6, "flash_bwd": 6,
        "indexer_loss_fwd": 6, "indexer_loss_bwd": 6,
        "moe_gmm_fwd": 18, "moe_gmm_dx": 12, "moe_gmm_dw": 12}
    m = compiled.memory_analysis()
    held = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)
    assert held < DSA_STEP_GIB * 2**30, held / 2**30
    hlo = compiled.as_text()
    paths = set(device_trace.parse_scopes(hlo)["scopes"].values())
    layers = [device_trace.classify(path)[0] for path in paths
              if path.startswith("jit(")]
    for layer in ("indexer/proj", "indexer/scores", "indexer/select",
                  "indexer_loss", "attn_core", "moe_router", "moe_experts"):
        assert layer in layers, layer
    for scope in ("/indexer/scores/", "/indexer/select/",
                  "/msa/indexer_loss/", "/msa/attn_core/flash_fwd/",
                  "/checkpoint/msa/attn_core/flash_bwd/",
                  "checkpoint/rematted_computation/msa/qkv/"):
        assert any(scope in path for path in paths), scope
    # kept for the backward pass, not taken again
    for scope in ("rematted_computation/msa/while",
                  "rematted_computation/msa/indexer_loss",
                  "rematted_computation/msa/attn_core/flash_fwd"):
        assert not any(scope in path for path in paths), scope
    assert layers.count("other") < 0.01 * len(layers)
    # the selection reaches the kernels as bytes and is kept as bits; no
    # 32-bit copy of it is made for them
    assert re.search(r"s8\[1,16384,16384\]", hlo)
    assert re.search(r"u8\[16384,2048\]", hlo)
    assert not re.search(r"= [su]32\[1,16384,16384\]\S* (?:convert|copy)\(",
                         hlo)


def test_the_alignment_losss_pass_leaves_no_chunk_of_rows_in_hbm(dsa_step):
    """The loss's pass is two kernels over the causal blocks (PR 35):
    under ``/msa/indexer_loss/`` the compiled step holds neither the
    head-mean probabilities' logits ``f32[1,8,512,16384]`` nor an array
    of the indexer's heads ``[1,16,512,16384]`` (PR 34's step held both,
    a chunk of 512 query rows over every key), the selection's own take
    of the scores is where it was, and the step still fits."""
    _, compiled = dsa_step
    hlo = compiled.as_text()
    of_the_loss = [line for line in hlo.splitlines()
                   if "/msa/indexer_loss/" in line]
    assert of_the_loss
    wide = [line for line in of_the_loss if re.search(
        r"f32\[1,8,512,16384\]|\w+\[1,16,512,16384\]", line)]
    assert not wide, (len(wide), wide[0][:300])
    assert sum("/msa/indexer_loss/indexer_loss_fwd" in line
               or "/msa/indexer_loss/indexer_loss_bwd" in line
               for line in of_the_loss) >= 12
    assert "/indexer/scores/" in hlo
    assert not any("/indexer/scores/" in line for line in of_the_loss)
    m = compiled.memory_analysis()
    held = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)
    assert held < DSA_STEP_GIB * 2**30, held / 2**30


def test_the_selection_is_turned_once_a_layer_for_both_kernels(dsa_step):
    """The forward flash kernel holds its block transposed since PR 37
    and reads the selection as ``[keys, queries]`` strips, the
    transposed array the alignment loss's kernels read: XLA takes it
    once a layer for the two (the same expression, merged), and the
    backward pass once more for ``flash_bwd``. The compiled step's entry
    computation makes a full-size int8 ``[1, 16384, 16384]`` array 12
    times; the parent's made it 18 times (6 copies into the row layout
    its forward read, 6 transposes for the loss and 6 for the
    backward)."""
    _, compiled = dsa_step
    made = [line for line in _entry(compiled.as_text()) if re.search(
        r"= \(?[^=]*s8\[1,16384,16384\]\S* (?!bitcast|get-tuple-element|"
        r"parameter|tuple|while|opt-barrier|custom-call)[\w-]+\(", line)]
    assert len(made) <= 12, (len(made), made[0][:300])
    assert not any("/msa/indexer_loss/transpose" in line for line in made)


def test_flash_kernels_compile_with_a_selection_a_row(v5e_2x2):
    """T = 16,384, 32 query heads over 4 key/value heads of 128 and an
    int8 ``[T, T]`` selection: both kernels compile with the strip of it
    (8 MiB a query block, twice for the pipeline) beside k and v."""
    from pytorch_vit_paper_replication_tpu.ops.flash_attention import (
        flash_attention)

    one = SingleDeviceSharding(v5e_2x2[0])
    q = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((1, 16384, 4, 128), jnp.bfloat16, sharding=one)
    mask = jax.ShapeDtypeStruct((1, 16384, 16384), jnp.int8, sharding=one)
    compiled = jax.jit(jax.grad(lambda q, k, v, m: jnp.sum(
        flash_attention(q, k, v, kind="causal", mask=m[:, None],
                        interpret=False, return_lse=True)[0].astype(
            jnp.float32)), argnums=(0, 1, 2))).lower(q, kv, kv,
                                                     mask).compile()
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
    assert not re.search(r"bf16\[32,16384,128\]", hlo)


# ------------------------------------------- gated short convolutions (LFM2)
# The cell ``lfm2_train_8k``'s step held 12.40 GiB when it was first
# compiled (three sequences of 8,192 tokens; 5.25 GiB of parameters and moments,
# 7.16 of temporaries), no remat.
CONV_STEP_GIB = 12.9


@pytest.fixture(scope="module")
def conv_step(v5e_2x2):
    """The cell ``lfm2_train_8k``'s step as the trainer builds it,
    lowered and compiled once for the file."""
    from pytorch_vit_paper_replication_tpu.configs import LM_PRESETS

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        cfg = LM_PRESETS["lfm2-24b-a2b-ep8"]()
        lowered = _lower_lm_step(v5e_2x2[:1], cfg, dp=1, batch=3,
                                 seq_len=cfg.max_seq_len)
        return lowered, lowered.compile()


def test_conv_models_step_compiles_and_fits_one_chip(conv_step):
    """Three 8,192-token sequences through LFM2-24B-A2B's cut at every
    published width: the one attention layer names the flash pair at head
    size 64 (heads folded: 32 over 8 key/value heads), the four routed
    blocks the grouped products of their 8 held experts (two chunks of
    12,288 tokens a block, each a loop of passes lowered once; the first
    product taken again in the backward pass), and the conv mixers no
    kernel: their mix is XLA's. It compiles for the v5e and fits 15.75
    GiB with room; the mixer's three scopes are in the compiled step,
    under ``msa_glue`` by the frozen table and never ``other``."""
    from benchmark.lib import scopes

    lowered, compiled = conv_step
    names = [name for name, _ in mosaic_calls(lowered.as_text())]
    assert {n: names.count(n) for n in set(names)} == {
        "flash_fwd": 1, "flash_bwd": 1,
        "moe_gmm_fwd": 24, "moe_gmm_dx": 16, "moe_gmm_dw": 16}
    m = compiled.memory_analysis()
    held = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)
    assert held < CONV_STEP_GIB * 2**30, held / 2**30
    hlo = compiled.as_text()
    paths = set(device_trace.parse_scopes(hlo)["scopes"].values())
    layers = [device_trace.classify(path)[0] for path in paths
              if path.startswith("jit(")]
    for layer in ("conv_proj", "conv_mix", "attn_core", "moe_router",
                  "moe_experts", "head_loss"):
        assert layer in layers, layer
    mixer = [path for path in paths if "/msa/conv/" in path]
    assert mixer and all(scopes.classify(path)[0] == "msa_glue"
                         for path in mixer)
    assert layers.count("other") < 0.01 * len(layers)
    # the tied head reads the table as it lies: no transposed copy of it
    assert not re.search(r"= \w+\[2048,8192\]\S* (?:copy|transpose)\(", hlo)
