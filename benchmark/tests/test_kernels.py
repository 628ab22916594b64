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


@pytest.mark.parametrize("path", sorted(
    (harness.BENCH / "workloads").glob("*.json")), ids=lambda p: p.stem)
def test_every_cell_names_mlp_kernels_once_per_layer(path):
    cell, config = harness.load_cell(path.stem)
    expect = cell[cell["driver"]]["expect_kernels"]
    layers = config["model"]["num_layers"]
    assert set(expect) <= set(kernels.MLP_KERNELS)
    assert set(expect.values()) == {layers}
    assert ("lnmlp_bwd" in expect) is (cell["driver"] == "train")


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
