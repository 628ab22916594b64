"""The token model whose layers mix Mamba-2 state-space layers with
attention (``--preset ssm-tiny``: granite-4.0-h-micro's blocks at a size
for tests) on the CPU: the chunked scan's kernel pair (``ops/ssd.py``,
``ssd_fwd`` / ``ssd_bwd`` under the Pallas interpreter) and its gradient
against a plain token-by-token recurrence, the model against its plain
reference (``benchmark/lib/reference_ssm.py``, which takes the scan in its
quadratic form), a planted fault in the carry between chunks, the
state-carry counter, Granite's multipliers, and the parameter and FLOP
counts of the cut against hand counts.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference_ssm
from pytorch_vit_paper_replication_tpu import engine
from pytorch_vit_paper_replication_tpu.configs import (LM_PRESETS, PRESETS,
                                                       TrainConfig, ViTConfig)
from pytorch_vit_paper_replication_tpu.models import ViT
from pytorch_vit_paper_replication_tpu.ops import ssd
from pytorch_vit_paper_replication_tpu.optim import make_optimizer

T = 48      # three chunks of ssm-tiny's 16


# ------------------------------------------------------------- the scan
def _recurrence(x, dt, a, bb, cc, d):
    """``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + d
    x_t``, one position at a time (float32, every head its own state)."""
    h, g = x.shape[2], bb.shape[2]
    bh, ch = (jnp.repeat(v, h // g, axis=2) for v in (bb, cc))

    def step(state, inputs):
        xt, dtt, bt, ct = inputs
        state = (jnp.exp(dtt * a)[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None] * bt[..., None, :])
        return state, (jnp.einsum("bhpn,bhn->bhp", state, ct)
                       + d[:, None] * xt)

    first = lambda v: jnp.moveaxis(v, 1, 0)
    _, ys = jax.lax.scan(
        step, jnp.zeros(x.shape[:1] + (h, x.shape[3], bb.shape[3])),
        (first(x), first(dt), first(bh), first(ch)))
    return jnp.moveaxis(ys, 0, 1)


def _scan_inputs(b, t, h, p, g, n, slow, seed=0):
    """Seeded raw inputs: ``dt`` and ``A`` as the mixer makes them, from a
    raw step, ``dt_bias`` and ``A_log`` (slow: decays 400 times slower)."""
    ks = jax.random.split(jax.random.key(seed), 7)
    a_log = jnp.log(jax.random.uniform(ks[2], (h,), minval=1, maxval=16))
    return dict(
        x=jax.random.normal(ks[0], (b, t, h, p)),
        raw=jax.random.normal(ks[1], (b, t, h)),
        a_log=a_log - (6.0 if slow else 0.0),
        bb=jax.random.normal(ks[3], (b, t, g, n)),
        cc=jax.random.normal(ks[4], (b, t, g, n)),
        d=jax.random.normal(ks[5], (h,)),
        dt_bias=jnp.full((h,), -3.0)), jax.random.normal(ks[6], (b, t, h, p))


def _through(scan, x, raw, a_log, bb, cc, d, dt_bias):
    return scan(x, jax.nn.softplus(raw + dt_bias), -jnp.exp(a_log), bb, cc, d)


def _pulled(scan, args, gy):
    """``(y, the gradient of sum(gy * y) at every input)``, one program."""
    y, pull = jax.vjp(lambda a: _through(scan, **a), args)
    return y, pull(gy)[0]


@pytest.mark.parametrize("b,t,h,g,chunk,slow", [
    (1, 10, 2, 1, 16, False),     # T < chunk
    (2, 32, 4, 1, 8, False),      # T a multiple of the chunk
    (2, 37, 4, 1, 8, True),       # T not a multiple: a partial last chunk
    (1, 40, 16, 1, 16, True),     # one group, two head blocks of 8
    (2, 40, 16, 2, 16, False),    # two groups of 8 heads, a block each
    (1, 64, 8, 1, 16, True),      # slow decays: the carry does the work
    (1, 21, 32, 2, 8, True),      # two groups of two blocks each
])
def test_the_chunked_scan_and_its_gradient_equal_the_recurrence(
        b, t, h, g, chunk, slow):
    """Outputs and the seven gradients (``x``, the raw step through the
    softplus, ``A_log`` through ``A``, ``B``, ``C``, ``D`` and ``dt_bias``)
    of the kernels equal those of the plain recurrence: every one of the
    scan's six cotangents reaches them. A head block never straddles two
    groups (``head_block`` takes a divisor of a group's heads), so the
    cases split groups into blocks, never heads across them."""
    args, gy = _scan_inputs(b, t, h, 4, g, 5, slow)
    chunked = lambda *v: ssd.ssd(*v, chunk)
    with jax.default_matmul_precision("highest"):
        (got, got_g), (want, want_g) = (
            jax.jit(functools.partial(_pulled, s))(args, gy)
            for s in (chunked, _recurrence))
    np.testing.assert_allclose(got, want, atol=2e-5 * float(
        jnp.abs(want).max()))
    grads = (got_g, want_g)
    assert len(grads[0]) == 7
    for name in args:
        w = grads[1][name]
        assert float(jnp.abs(w).max()) > 0, name
        np.testing.assert_allclose(grads[0][name], w, atol=2e-5 * float(
            jnp.abs(w).max()), err_msg=name)


def _one_of(args, i):
    """Sequence ``i`` of a batch's inputs (the per-head ones shared)."""
    return {k: (v[i:i + 1] if v.ndim > 1 else v) for k, v in args.items()}


def test_the_sequences_of_a_batch_share_no_state():
    """Two sequences scanned together give what each gives alone, and
    each state starts at 0 at its first position: outputs, the per-
    sequence gradients (``x``, the raw step, ``B``, ``C``) and the shared
    ones (``A_log``, ``D``, ``dt_bias``: the sum of the two alone)."""
    args, gy = _scan_inputs(2, 40, 4, 4, 1, 5, slow=True, seed=3)
    run = jax.jit(functools.partial(_pulled, lambda *v: ssd.ssd(*v, 16)))
    both, both_g = run(args, gy)
    alone = [run(_one_of(args, i), gy[i:i + 1]) for i in range(2)]
    for i in range(2):
        np.testing.assert_allclose(both[i:i + 1], alone[i][0], rtol=1e-6,
                                   atol=1e-6)
    for name, g in both_g.items():
        want = (jnp.concatenate([a[1][name] for a in alone]) if g.ndim > 1
                else alone[0][1][name] + alone[1][1][name])
        np.testing.assert_allclose(g, want, rtol=1e-5, atol=1e-5 * float(
            jnp.abs(want).max()), err_msg=name)


def test_the_kernels_run_per_shard_on_a_data_mesh(devices):
    """Traced under a two-device data mesh, the scan runs per shard (XLA
    partitions no Mosaic call; ``tests/test_v5e_compile.py`` compiles it
    so for the chip): the output and every gradient are the plain
    recurrence's, ``A``'s and ``D``'s summed over the shards."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pytorch_vit_paper_replication_tpu.ops import partition

    args, gy = _scan_inputs(2, 24, 4, 4, 1, 5, slow=True, seed=4)
    loss = lambda scan, a: jnp.sum(gy * _through(scan, **a))
    with jax.default_matmul_precision("highest"):
        want, want_g = jax.jit(jax.value_and_grad(
            functools.partial(loss, _recurrence)))(args)
    mesh = jax.sharding.Mesh(np.array(devices[:2]), ("data",))
    rows = NamedSharding(mesh, P("data"))
    placed = {k: jax.device_put(v, rows if v.ndim > 1 else
                                NamedSharding(mesh, P()))
              for k, v in args.items()}
    with partition.on_mesh(mesh), jax.default_matmul_precision("highest"):
        got, got_g = jax.jit(jax.value_and_grad(functools.partial(
            loss, lambda *v: ssd.ssd(*v, 8))))(placed)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for name in args:
        w = want_g[name]
        np.testing.assert_allclose(got_g[name], w, atol=2e-5 * float(
            jnp.abs(w).max()), err_msg=name)


def test_the_head_block_adapts_to_the_shapes():
    """A program's heads: 8 of Granite's 64 heads of 64 (512 lane-dense
    columns); never more than a group holds, and whole lanes where the
    head size allows them."""
    assert ssd.head_block(64, 64, 1) == 8
    assert ssd.head_block(8, 16, 1) == 8         # ssm-tiny
    assert ssd.head_block(16, 64, 2) == 8
    assert ssd.head_block(12, 64, 1) == 6        # 384 columns
    assert ssd.head_block(6, 64, 3) == 2         # a group of two
    assert ssd.head_block(64, 128, 1) == 4
    assert ssd.head_block(64, 256, 1) == 2
    assert ssd.head_block(4, 1024, 1) == 1       # a head wider than 512
    assert ssd.head_block(10, 48, 1) == 5        # no lane-dense divisor


def test_the_convolution_is_causal_and_its_gradient_autodiffs():
    """``causal_conv``: position t reads positions t - K + 1 .. t of its
    own sequence only; its written-out gradient is autodiff's."""
    ks = jax.random.split(jax.random.key(1), 4)
    v = jax.random.normal(ks[0], (2, 13, 6))
    taps, bias = jax.random.normal(ks[1], (4, 6)), jax.random.normal(
        ks[2], (6,))
    plain = lambda v, taps, bias: jax.nn.silu(sum(
        taps[i] * jnp.pad(v, ((0, 0), (3 - i, 0), (0, 0)))[:, :13]
        for i in range(4)) + bias)
    np.testing.assert_allclose(ssd.causal_conv(v, taps, bias),
                               plain(v, taps, bias), rtol=1e-5, atol=1e-6)
    moved = v.at[1, 7].add(1.0)
    diff = ssd.causal_conv(moved, taps, bias) - ssd.causal_conv(v, taps, bias)
    assert float(jnp.abs(diff[0]).max()) == 0.0
    assert float(jnp.abs(diff[1, :7]).max()) == 0.0
    assert float(jnp.abs(diff[1, 11:]).max()) == 0.0
    g = ks[3]
    g = jax.random.normal(g, (2, 13, 6))
    got = jax.grad(lambda *a: jnp.sum(g * ssd.causal_conv(*a)),
                   argnums=(0, 1, 2))(v, taps, bias)
    want = jax.grad(lambda *a: jnp.sum(g * plain(*a)), argnums=(0, 1, 2))(
        v, taps, bias)
    for x, y in zip(got, want):
        np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-5)


def test_the_state_carry_is_its_definition():
    """``state_carry``: the mean over sequences, heads and chunks of
    ``exp(sum over the chunk of dt A)``, a partial last chunk summing the
    positions it has."""
    dt = np.abs(np.random.default_rng(0).normal(0.02, 0.01, (2, 40, 3)))
    a = np.array([-1.0, -4.0, -0.5])
    want = np.mean([np.exp(np.sum(dt[s, lo:lo + 16, h]) * a[h])
                    for s in range(2) for h in range(3)
                    for lo in (0, 16, 32)])
    got = float(ssd.state_carry(jnp.asarray(dt, jnp.float32),
                                jnp.asarray(a, jnp.float32), 16))
    assert got == pytest.approx(want, rel=1e-5)
    assert float(ssd.state_carry(jnp.zeros((1, 40, 3)), jnp.asarray(a),
                                 16)) == 1.0


# ---------------------------------------------------------- the model
def _tiny(**kw):
    # float32 compute: the comparison is of the mathematics; four heads
    # of 16 (one kernel block), so that the interpreter's kernels stay
    # small
    return LM_PRESETS["ssm-tiny"](dtype="float32", ssm_heads=4, **kw)


def _params(model, cfg, key=1):
    ids = jax.random.randint(jax.random.key(0), (2, T + 1), 0,
                             cfg.vocab_size)

    @jax.jit
    def draw(key):
        params = model.init(key, ids[:, :-1])["params"]
        # scales, biases and the skip not at their initial values
        return jax.tree.map(
            lambda a: a + 0.05 * jax.random.normal(
                jax.random.key(a.size), a.shape), params)

    return draw(jax.random.key(key)), ids[:, :-1], ids[:, 1:]


@pytest.fixture(scope="module")
def tiny():
    cfg = _tiny()
    model = ViT(cfg)
    return (cfg, model) + _params(model, cfg)


def _probed(model, params, tokens):
    return jax.jit(lambda p, x: model.apply(
        {"params": p}, x, False, mutable=["ssm_probe"]))(params, tokens)


def test_logits_and_the_mixers_equal_the_reference(tiny):
    cfg, model, params, tokens, _ = tiny
    fields = dataclasses.asdict(cfg)
    got, sown = _probed(model, params, tokens)
    hid, mixed = reference_ssm.hidden(params, np.asarray(tokens), fields,
                                      mixers=(0, 2))
    want = reference_ssm.logits(params, hid, fields)
    assert got.shape == (2, T, cfg.vocab_size) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=1e-4 * float(jnp.std(want)))
    probe = sown["ssm_probe"]["backbone"]
    assert sorted(probe) == ["encoder_block_0", "encoder_block_2"]
    for layer in (0, 2):
        w = mixed[layer]
        np.testing.assert_allclose(
            probe[f"encoder_block_{layer}"]["msa"]["out"][0], w,
            atol=1e-4 * float(jnp.std(w)))


def test_the_loss_and_every_gradient_leaf_equal_the_reference(tiny):
    cfg, model, params, tokens, labels = tiny
    fields = dataclasses.asdict(cfg)

    def program(p):
        return model.apply({"params": p}, tokens, True, labels=labels,
                           mutable=["ssm_stats"])[0][0]

    with jax.default_matmul_precision("highest"):
        got, got_g = jax.jit(jax.value_and_grad(program))(params)
    want, want_g = jax.jit(jax.value_and_grad(
        lambda p: reference_ssm.loss(p, tokens, labels, fields)))(params)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    flat_got = jax.tree_util.tree_leaves_with_path(got_g)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_g))
    # the tied table and the final norm; a Mamba mixer has 9 leaves (its
    # norm, in_proj, taps, conv bias, A_log, D, dt_bias, the gated norm's
    # scale, out_proj), the attention block 3 (norm, qkv, out); each
    # dense feed-forward 4
    assert len(flat_got) == len(flat_want) == 2 + 2 * (9 + 4) + (3 + 4)
    for path, g in flat_got:
        w = flat_want[path]
        name = jax.tree_util.keystr(path)
        assert float(jnp.abs(w).max()) > 0, name
        np.testing.assert_allclose(
            g, w, atol=2e-5 * float(jnp.abs(w).max()) + 1e-8, err_msg=name)


def test_the_references_gradient_a_block_at_a_time_is_autodiffs(tiny):
    """``reference_ssm.gradients`` (each block's forward taken again
    inside its own pull-back, the size at which the chip's check takes the
    reference's step) gives the loss and every leaf that ``jax.grad`` of
    the reference's loss gives, the loss over part of the positions
    too."""
    cfg, _, params, tokens, labels = tiny
    fields = dataclasses.asdict(cfg)
    counted = np.arange(T) < T // 2
    for kw in ({}, {"counted": counted}):
        want, want_g = jax.jit(jax.value_and_grad(
            lambda p: reference_ssm.loss(p, tokens, labels, fields,
                                         **kw)))(params)
        got, got_g = reference_ssm.gradients(
            params, np.asarray(tokens), np.asarray(labels), fields, **kw)
        np.testing.assert_allclose(got, want, rtol=1e-6)
        flat_want = dict(jax.tree_util.tree_leaves_with_path(want_g))
        for path, g in jax.tree_util.tree_leaves_with_path(got_g):
            w = flat_want.pop(path)
            np.testing.assert_allclose(
                g, w, atol=1e-5 * float(jnp.abs(w).max()) + 1e-9,
                err_msg=jax.tree_util.keystr(path))
        assert not flat_want


def _start_rms(got, want, chunk):
    """The drivers' mixer reading at the first positions of each chunk
    after the first, in units of the reference's spread."""
    from benchmark.drivers.train_ssm import START_POSITIONS

    pos = np.arange(got.shape[1])
    at = (pos >= chunk) & (pos % chunk < START_POSITIONS)
    diff = (np.asarray(got) - np.asarray(want))[:, at]
    return float(np.sqrt(np.mean(diff ** 2)) / np.std(np.asarray(want)))


def test_a_state_dropped_between_chunks_shows_at_the_chunks_starts(
        tiny, monkeypatch):
    """The planted fault: the program reads no state back at a chunk's
    start (what crossed from the chunk before is lost: ``ssd_fwd``'s
    state scratch zeroed before each of its programs). The mixer
    comparison the benchmark's driver makes at the first positions of
    each chunk after the first tells it, where the scan's own reading is
    float32's rounding. (Decays slowed, so that states outlive a chunk as
    the slow heads' do.)"""
    from benchmark.drivers.train_ssm import SSM_RMS_TOLERANCE

    cfg, model, params, tokens, _ = tiny
    fields = dataclasses.asdict(cfg)
    slow = jax.tree_util.tree_map_with_path(
        lambda path, a: a - 4.0 if "A_log" in jax.tree_util.keystr(path)
        else a, params)
    want = reference_ssm.hidden(slow, np.asarray(tokens), fields,
                                mixers=(0,))[1][0]
    honest = _probed(model, slow, tokens)[1]["ssm_probe"]["backbone"][
        "encoder_block_0"]["msa"]["out"][0]
    assert _start_rms(honest, want, cfg.ssm_chunk) < 1e-4
    real = ssd._fwd_kernel

    def no_carry(*refs, **kw):
        # the state scratch, the kernel's last ref, zeroed before every
        # program: no chunk reads what the one before it left
        refs[-1][...] = jnp.zeros(refs[-1].shape, refs[-1].dtype)
        real(*refs, **kw)

    monkeypatch.setattr(ssd, "_fwd_kernel", no_carry)
    # the kernels' calls are traced once a shape: trace them again with
    # the fault, and again without it for the tests after this one
    jax.clear_caches()
    try:
        faulty = ViT(cfg).apply({"params": slow}, tokens, False,
                                mutable=["ssm_probe"])[1]["ssm_probe"][
            "backbone"]["encoder_block_0"]["msa"]["out"][0]
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert _start_rms(faulty, want, cfg.ssm_chunk) > 2 * SSM_RMS_TOLERANCE
    # the first chunk has nothing to carry: the fault leaves it alone
    np.testing.assert_allclose(faulty[:, :cfg.ssm_chunk],
                               honest[:, :cfg.ssm_chunk], rtol=1e-5,
                               atol=1e-6)


def test_train_step_learns_and_counts(tiny):
    cfg, model, params, tokens, labels = tiny
    tx = make_optimizer(TrainConfig(batch_size=2), 100)
    state = engine.TrainState.create(apply_fn=model.apply, params=params,
                                     tx=tx, rng=jax.random.key(2))
    batch = {"tokens": tokens, "label": labels}
    # lowered once: the text is read below, the compiled step is run
    lowered = jax.jit(engine.make_train_step()).lower(state, batch)
    step = lowered.compile()
    seen = []
    for _ in range(5):
        state, m = step(state, batch)
        seen.append(m)
    assert float(seen[-1]["loss_sum"]) < float(seen[0]["loss_sum"])
    # the step's counter is the layers' mean of the sown carries
    sown = jax.jit(lambda p: model.apply(
        {"params": p}, tokens, True, labels=labels,
        mutable=["ssm_stats"])[1]["ssm_stats"])(params)
    carries = [float(v[0]) for v in jax.tree.leaves(
        sown, is_leaf=lambda v: isinstance(v, tuple))]
    assert len(carries) == 2 and all(0 < c < 1 for c in carries)
    assert float(seen[0]["ssm_state_carry"]) == pytest.approx(
        np.mean(carries), rel=1e-5)
    text = lowered.as_text(debug_info=True)
    for scope in ("/msa/ssm/in_proj/", "/msa/ssm/conv/", "/msa/ssm/scan/",
                  "/msa/ssm/gate_norm/", "/msa/ssm/out_proj/", "/msa/norm/",
                  "/msa/attn_core/", "/mlp/dense/"):
        assert scope in text, scope


def test_the_counter_reaches_step_telemetry_and_the_registry():
    from pytorch_vit_paper_replication_tpu.telemetry import (
        HELP_TEXT, INSTRUMENTS, StepTelemetry, TelemetryRegistry)

    reg = TelemetryRegistry()
    tel = StepTelemetry(None, registry=reg, sample_every=1)
    tel.step(data_wait_s=0.0, exec_s=0.1, images=1, step=1, blocked=True,
             counters={"ssm_state_carry": 0.0191})
    assert reg.snapshot()["gauges"]["tel_ssm_state_carry"] == 0.0191
    assert "ssm_state_carry" in engine.LM_COUNTERS
    assert "tel_ssm_state_carry" in INSTRUMENTS
    assert "tel_ssm_state_carry" in HELP_TEXT


BLOCK = "jit(train_step)/jvp(ViT)/backbone/encoder_block_0"


@pytest.mark.parametrize("path,row", [
    (f"{BLOCK}/msa/ssm/in_proj/dot_general", "ssm_proj"),
    (f"{BLOCK}/msa/ssm/out_proj/dot_general", "ssm_proj"),
    (f"{BLOCK}/msa/ssm/conv/mul", "ssm_conv"),
    ("jit(train_step)/transpose(jvp(ViT))/backbone/encoder_block_2/msa/"
     "ssm/scan/while/body/dot_general", "ssm_scan"),
    (f"{BLOCK}/msa/ssm/gate_norm/rsqrt", "ssm_norm"),
])
def test_device_trace_rows_of_the_new_scopes(path, row):
    """The trainer's table has a row for each part of the mixer, the
    benchmark's finer table the same rows, and the frozen table reads
    the mixer under ``msa_glue`` (it keeps the attention's module name),
    never ``other``."""
    from benchmark.lib import scopes, scopes_ssm
    from pytorch_vit_paper_replication_tpu.telemetry import device_trace

    assert device_trace.classify(path)[0] == row
    assert scopes_ssm.row_of(path) == row
    assert scopes.classify(path)[0] == "msa_glue"


# --------------------------------------------------------- multipliers
def test_the_multipliers_default_to_nothing_on_every_other_preset():
    """Every preset but Granite's keeps the four at their defaults, under
    which no op is added (the accepted presets' step text is pinned by
    ``tests/test_conv.py::test_every_accepted_preset_lowers_to_the_parents
    _text``)."""
    defaults = (1.0, 1.0, 1.0, None)
    for name, make in {**PRESETS, **LM_PRESETS}.items():
        cfg = make()
        got = (cfg.embedding_multiplier, cfg.residual_multiplier,
               cfg.logits_scaling, cfg.attn_scale)
        if name in ("granite-4.0-h-micro-pp4", "ssm-tiny"):
            assert got != defaults, name
        else:
            assert got == defaults, name
            assert not any(cfg.layer_mixer(i) == "ssm"
                           for i in range(cfg.num_layers)), name


@pytest.mark.parametrize("field,value", [
    ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
    ("logits_scaling", 1.0), ("attn_scale", None)])
def test_a_multiplier_at_its_default_lowers_to_the_same_text(field, value,
                                                             tiny):
    """ssm-tiny with one of the four at its default lowers to the text of
    a model built with that field left out: the default adds no op."""
    cfg, _, params, tokens, _ = tiny
    at_default = cfg.replace(**{field: value})
    fields = {f.name: getattr(at_default, f.name)
              for f in dataclasses.fields(at_default) if f.name != field}
    left_out = ViTConfig(**fields)
    text = lambda c: jax.jit(lambda p, x: ViT(c).apply(
        {"params": p}, x, False)).lower(params, tokens).as_text()
    assert text(at_default) == text(left_out)
    if value is not None:
        assert text(at_default) != text(cfg)


def test_the_multipliers_scale_what_they_name(tiny):
    """``logits_scaling`` divides the logits; ``attn_scale`` at the
    default's value is the default; ``embedding_multiplier`` and
    ``residual_multiplier`` are the reference's (the reference tests above
    run at 12 / 0.22 / 8 / 1/32)."""
    cfg, model, params, tokens, _ = tiny
    logits = lambda c: ViT(c).apply({"params": params}, tokens, False)
    np.testing.assert_allclose(logits(cfg.replace(logits_scaling=1.0)),
                               8.0 * logits(cfg), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        logits(cfg.replace(attn_scale=cfg.head_dim ** -0.5)),
        logits(cfg.replace(attn_scale=None)), rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError, match="multipliers"):
        LM_PRESETS["conv-tiny"](residual_multiplier=0.5)
    with pytest.raises(ValueError, match="multipliers"):
        ViTConfig(embedding_multiplier=2.0)


# ------------------------------------------------------------- hand counts
def test_parameters_of_the_cut():
    """The cut counted by hand and from the shapes the model makes:
    772,160,448 parameters, 16 bytes each: 12.35 GB = 11.51 GiB."""
    model = ViT(LM_PRESETS["granite-4.0-h-micro-pp4"]())
    shapes = jax.eval_shape(model.init, jax.random.key(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    count = lambda tree: sum(int(np.prod(a.shape))
                             for a in jax.tree.leaves(tree))
    bb = shapes["backbone"]
    mixer = (2048 * 8512 + 4 * 4352 + 4352 + 3 * 64 + 4096
             + 4096 * 2048)
    ffn = 3 * 2048 * 8192
    attention = 2048 * 48 * 64 + 32 * 64 * 2048
    assert (mixer, ffn, attention) == (25_847_232, 50_331_648, 10_485_760)
    assert count(bb["encoder_block_0"]["msa"]) == mixer + 2048
    assert count(bb["encoder_block_0"]) == mixer + ffn + 2 * 2048 \
        == 76_182_976
    assert count(bb["encoder_block_5"]) == attention + ffn + 2 * 2048 \
        == 60_821_504
    assert "head" not in shapes                    # tied: one table
    total = 9 * 76_182_976 + 60_821_504 + 12544 * 2048 + 2048
    assert count(shapes) == total == 772_160_448
    assert total * 16 / 2**30 == pytest.approx(11.51, abs=0.01)
    published = 36 * 76_182_976 + 4 * 60_821_504 + 100352 * 2048 + 2048
    assert published / 1e9 == pytest.approx(3.19, abs=0.005)


def test_flop_count_against_a_hand_count():
    """Forward MFLOP a token of the cut at 16,384 tokens, by part, and the
    step's TFLOP."""
    from pytorch_vit_paper_replication_tpu.telemetry import flops

    cfg = LM_PRESETS["granite-4.0-h-micro-pp4"]()
    t = 16384
    projections = 2 * 2048 * 8512 + 2 * 4096 * 2048
    taps = 2 * 4 * 4352
    scan = (2 * (t // 256) * (256 * 257 // 2) * (128 + 4096)
            + 2 * 2 * t * 4096 * 128) / t
    dense = 3 * 2 * 2048 * 8192
    attention = 2 * 2048 * 48 * 64 + 2 * 2048 * 2048
    core = 2 * 2 * (t * (t + 1) // 2) * 32 * 64 / t
    head = 2 * 2048 * 12544
    by_hand = 9 * (projections + taps + scan) + 10 * dense + attention \
        + core + head
    assert flops.forward_flops_per_sequence(cfg) / t == pytest.approx(
        by_hand, rel=1e-12)
    for part, mflop in ((projections, 51.6), (taps, 0.03), (scan, 3.18),
                        (9 * (projections + taps + scan), 493.7),
                        (10 * dense, 1006.6), (attention, 21.0),
                        (core, 67.1), (head, 51.4), (by_hand, 1639.8)):
        assert part / 1e6 == pytest.approx(mflop, abs=0.05)
    assert flops.train_step_flops_per_sequence(cfg) / 1e12 \
        == pytest.approx(80.6, abs=0.05)


def test_presets_state_every_published_width():
    cfg = LM_PRESETS["granite-4.0-h-micro-pp4"]()
    assert (cfg.embedding_dim, cfg.num_heads, cfg.kv_heads, cfg.head_dim,
            cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups,
            cfg.ssm_conv_kernel, cfg.ssm_chunk, cfg.dense_width,
            cfg.ln_epsilon) == (2048, 32, 8, 64, 64, 64, 128, 1, 4, 256,
                                8192, 1e-5)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling, cfg.attn_scale) == (12.0, 0.22, 8.0,
                                                    0.015625)
    assert (cfg.num_layers, cfg.dense_layers, cfg.num_experts,
            cfg.vocab_size, cfg.max_seq_len, cfg.tie_embedding,
            cfg.attn_bias, cfg.rope_layout, cfg.remat) == (
        10, 10, 0, 12544, 16384, True, False, (), True)
    assert [cfg.layer_mixer(i) for i in range(10)] == [
        "ssm"] * 5 + ["attention"] + ["ssm"] * 4
    assert not any(cfg.layer_rope(i) for i in range(10))
    for bad in (dict(ssm_state=0), dict(ssm_groups=3), dict(ssm_chunk=0)):
        with pytest.raises(ValueError, match="state-space layers"):
            cfg.replace(**bad)
    with pytest.raises(ValueError, match="dense_layers"):
        cfg.replace(dense_layers=9)
    with pytest.raises(ValueError, match="mixer_layout"):
        cfg.replace(mixer_layout=(3,))
