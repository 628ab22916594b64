"""What ``correct`` asks of a step's kernels, shown on lowered text; the
kernels' names; and the attention core's least work by hand."""

import pytest

from benchmark.lib import flops, harness, kernels, scopes


def lowered(**calls) -> str:
    """StableHLO text as ``jitted.lower(...).as_text()`` gives it on the
    TPU (one line per Mosaic call, copied from a v5e lowering of the
    B/16 step with its 13 KB ``backend_config`` cut), around two ops
    that are no kernels."""
    line = (
        '    %193:2 = stablehlo.custom_call @tpu_custom_call(%188, %180) '
        '{{backend_config = "{{\\22custom_call_config\\22: ...}}", '
        'kernel_name = "{name}", mhlo.frontend_attributes = '
        '{{kernel_metadata = "{{}}"}}, operand_layouts = [dense<0> : '
        'tensor<1xindex>, dense<[1, 0]> : tensor<2xindex>], result_layouts '
        '= [dense<[1, 0]> : tensor<2xindex>]}} : (tensor<3xi32>, '
        'tensor<50432x768xbf16>) -> (tensor<50432x768xbf16>, '
        'tensor<50432x3072xbf16>)')
    body = [line.format(name=name) for name, n in calls.items()
            for _ in range(n)]
    return "\n".join([
        "module @jit_train_step {",
        '    %7 = stablehlo.custom_call @Sharding(%6) {backend_config = ""} '
        ': (tensor<8xf32>) -> tensor<8xf32>',
        *body,
        "    %9 = stablehlo.dot_general %7, %8 : tensor<8xf32>", "}"])


B16 = {"lnmlp_fwd": 12, "lnmlp_bwd": 12}
FLASH = {"flash_fwd": 12, "flash_bwd_dq": 12, "flash_bwd_dkv": 12}


@pytest.mark.parametrize("found,ok,unnamed", [
    # every named kernel, as often as the cell says
    (B16, True, {}),
    # ... and kernels the cell's author did not foresee: correct, listed
    ({**B16, **FLASH}, True, FLASH),
    # one backward call missing (the old count would have taken 23 MLP
    # calls and one flash call for 24)
    ({"lnmlp_fwd": 12, "lnmlp_bwd": 11, "flash_fwd": 1}, False,
     {"flash_fwd": 1}),
    # a named kernel's count off by one, upwards
    ({"lnmlp_fwd": 13, "lnmlp_bwd": 12}, False, {}),
    # the XLA fallback of the MLP: no named kernel at all
    (FLASH, False, FLASH),
    # another MLP kernel in the named one's place (the core under a
    # model axis): another program
    ({"mlp_fwd": 12, "mlp_bwd": 12}, False, {"mlp_fwd": 12, "mlp_bwd": 12}),
    ({}, False, {}),
], ids=["all-named", "extra-unnamed", "one-missing", "one-over",
        "xla-fallback", "other-mlp-kernel", "no-kernel"])
def test_correct_counts_the_kernels_the_cell_names(found, ok, unnamed):
    text = lowered(**found)
    assert kernels.kernel_counts(text) == dict(sorted(found.items()))
    assert kernels.check_kernels(kernels.kernel_counts(text), B16) == (
        ok, unnamed)


def test_a_cell_that_names_no_kernel_takes_any():
    # how --rehearsal passes: it expects what it finds, and on the CPU
    # the interpreter leaves no Mosaic call
    assert kernels.check_kernels({}, {}) == (True, {})
    assert kernels.check_kernels(FLASH, FLASH) == (True, {})


# ---- the family form: what the token cell's file and the ViT cells'
# files ask, on the steps a later PR may bring (ISSUE 30's table).
TOKEN = {"flash_fwd": 4, "flash_bwd*": [4, 8], "moe_gmm_fwd": 24,
         "moe_gmm_dx": 16, "moe_gmm_dw": 16}
MOE = {"moe_gmm_fwd": 24, "moe_gmm_dx": 16, "moe_gmm_dw": 16}
VIT = {**B16, "attn_short_fwd": 12, "attn_short_bwd": 12}


@pytest.mark.parametrize("expect,found,ok,unnamed", [
    # today's token step: two backward kernels a layer, 8 calls
    (TOKEN, {"flash_fwd": 4, "flash_bwd_dq": 4, "flash_bwd_dkv": 4, **MOE},
     True, {}),
    # a backward of one call a layer, whatever it is called
    (TOKEN, {"flash_fwd": 4, "flash_bwd": 4, **MOE}, True, {}),
    (TOKEN, {"flash_fwd": 4, "flash_bwd_fused": 4, **MOE}, True, {}),
    # no flash backward at all: the fall-back to XLA
    (TOKEN, {"flash_fwd": 4, **MOE}, False, {}),
    # a layer without its backward kernel
    (TOKEN, {"flash_fwd": 4, "flash_bwd": 3, **MOE}, False, {}),
    # three backward calls a layer: another program
    (TOKEN, {"flash_fwd": 4, "flash_bwd_dq": 4, "flash_bwd_dk": 4,
             "flash_bwd_dv": 4, **MOE}, False, {}),
    # the forward is exact: one layer fell back
    (TOKEN, {"flash_fwd": 3, "flash_bwd_dq": 4, "flash_bwd_dkv": 4, **MOE},
     False, {}),
    # the grouped products are exact
    (TOKEN, {"flash_fwd": 4, "flash_bwd": 4, **MOE, "moe_gmm_dx": 15},
     False, {}),
    # a member of a named family is named; another kernel is listed
    (TOKEN, {"flash_fwd": 4, "flash_bwd_dq": 4, "flash_bwd_dkv": 4, **MOE,
             "rope_fwd": 4}, True, {"rope_fwd": 4}),
    # a kernel with a key of its own counts there, not in a family
    ({"flash_bwd_dq": 4, "flash_bwd*": [4, 4]},
     {"flash_bwd_dq": 4, "flash_bwd_dkv": 4}, True, {}),
    # a ViT step as the program makes it, and one whose attention fell
    # back to XLA in the backward pass, in the forward pass, in both
    (VIT, VIT, True, {}),
    (VIT, {**B16, "attn_short_fwd": 12}, False, {}),
    (VIT, {**B16, "attn_short_bwd": 12}, False, {}),
    (VIT, B16, False, {}),
    (VIT, {**VIT, "attn_short_bwd": 11}, False, {}),
], ids=["token-today-8", "token-one-call-a-layer", "token-any-member-name",
        "token-no-backward", "token-backward-3", "token-backward-12",
        "token-forward-3", "token-gmm-off", "token-unnamed-listed",
        "own-key-before-family", "vit-as-made", "vit-no-attn-bwd",
        "vit-no-attn-fwd", "vit-xla-attention", "vit-attn-bwd-short"])
def test_a_family_asks_how_many_calls_not_which(expect, found, ok, unnamed):
    text = lowered(**found)
    counts = kernels.kernel_counts(text)
    assert kernels.check_kernels(counts, expect) == (ok, unnamed)
    # what a run prints beside its limits: the calls under each key
    calls = kernels.compared_calls(counts, expect)
    assert set(calls) == {f"calls.{key}" for key in expect}
    assert all(limit == expect[key[len("calls."):]]
               for key, (_, limit) in calls.items())
    assert sum(n for n, _ in calls.values()) + sum(unnamed.values()) == \
        sum(found.values())


CELLS = sorted((harness.BENCH / "workloads").glob("*.json"))


@pytest.mark.parametrize("path", CELLS, ids=lambda p: p.stem)
def test_every_cell_names_its_kernels_once_per_layer(path):
    """A ViT cell: the MLP half-block pair and (a train cell on the
    TPU's default path) the short-attention pair, one call a layer each,
    exactly. The token cell: the flash forward exactly, the flash
    backward as a family of one or two calls a layer, and the routed
    layer's grouped products by its two chunks (the cell's ``notes``)."""
    cell, config = harness.load_cell(path.stem)
    expect = cell[cell["driver"]]["expect_kernels"]
    layers = config["model"]["num_layers"]
    if config["model"].get("vocab_size"):
        assert expect == {
            "flash_fwd": layers, "flash_bwd*": [layers, 2 * layers],
            "moe_gmm_fwd": 6 * layers, "moe_gmm_dx": 4 * layers,
            "moe_gmm_dw": 4 * layers}
        return
    named = set(kernels.MLP_KERNELS) | {"attn_short_fwd", "attn_short_bwd"}
    assert set(expect) <= named and not any(k.endswith("*") for k in expect)
    assert set(expect.values()) == {layers}
    train = cell["driver"] == "train"
    assert ("lnmlp_bwd" in expect) is train
    assert ("attn_short_bwd" in expect) is ("attn_short_fwd" in expect) \
        is train


@pytest.mark.parametrize("path", CELLS, ids=lambda p: p.stem)
def test_todays_step_passes_its_cells_check_and_a_fall_back_fails(path):
    """The kernels the program's step holds today (PERF.md, Findings PR
    29: read from the lowered steps on the chip) pass each cell's file;
    the same step with any one named kernel or family gone fails."""
    cell, config = harness.load_cell(path.stem)
    expect = cell[cell["driver"]]["expect_kernels"]
    layers = config["model"]["num_layers"]
    today = {"flash_fwd": layers, "flash_bwd_dq": layers,
             "flash_bwd_dkv": layers, "moe_gmm_fwd": 6 * layers,
             "moe_gmm_dx": 4 * layers, "moe_gmm_dw": 4 * layers} \
        if config["model"].get("vocab_size") else \
        {key: layers for key in expect}
    assert kernels.check_kernels(today, expect) == (True, {})
    for key in expect:
        prefix = key.rstrip("*")
        gone = {k: n for k, n in today.items() if not k.startswith(prefix)}
        assert kernels.check_kernels(gone, expect)[0] is False, key


def test_kernel_names_equal_the_programs():
    from pytorch_vit_paper_replication_tpu.ops.partition import mosaic_calls

    text = lowered(**B16, **FLASH, mlp_fwd=1)
    theirs = {}
    for name, shape in mosaic_calls(text):
        assert shape == (50432, 768)
        theirs[name] = theirs.get(name, 0) + 1
    assert kernels.kernel_counts(text) == dict(sorted(theirs.items()))


@pytest.mark.parametrize("ev,name", [
    # from the scope: the segment pallas_call(name=) puts before it
    ({"name": "shard_map.7", "scope": "jit(train_step)/jvp(ViT)/backbone/"
      "encoder_block_0/mlp/lnmlp_fwd/pallas_call"}, "lnmlp_fwd"),
    ({"name": "flash_fwd.12", "scope": "jit(train_step)/jvp(ViT)/backbone"
      "/encoder_block_3/msa/attn_core/flash_fwd/pallas_call"}, "flash_fwd"),
    # no scope: the instruction's name without its number (the traces
    # of PR 22 called the kernel mlp.<n>, and shard_map.<n> on a mesh)
    ({"name": "lnmlp_fwd.12", "scope": ""}, "lnmlp_fwd"),
    ({"name": "mlp.36", "scope": ""}, "mlp"),
])
def test_a_mosaic_calls_kernel_is_named_by_the_program(ev, name):
    assert scopes.kernel_name(ev) == name
    assert (name in kernels.MLP_KERNELS) is name.startswith("lnmlp")


def test_attention_core_cost_by_hand():
    # B/16, batch 256: 12 heads of 64, T 197, 12 layers. The issue's hand
    # figures: 1.10 TFLOP and 11.2 GB a step, 13.6 ms at 819 GB/s.
    b, h, t, dh = 256, 12, 197, 64
    cost = kernels.attention_core_cost(b, h, t, dh, layers=12)
    assert cost["flops"] == 12 * 6 * 2 * b * h * t * t * dh
    assert cost["bytes"] == 12 * 12 * b * h * t * dh * 2
    assert cost["flops"] / 1e12 == pytest.approx(1.10, abs=0.005)
    assert cost["bytes"] / 1e9 == pytest.approx(11.2, abs=0.05)
    least = kernels.roofline_seconds(cost, flops.peaks("TPU v5 lite"))
    assert least["bound"] == "memory"
    assert least["seconds"] * 1e3 == pytest.approx(13.62, abs=0.005)
    assert least["compute_s"] * 1e3 == pytest.approx(5.58, abs=0.005)
    # L/16 at batch 96 moves the same 36,864 [197,197] matrices a step:
    # 96 x 16 heads x 24 layers = 256 x 12 x 12.
    assert kernels.attention_core_cost(96, 16, t, dh, layers=24) == cost
