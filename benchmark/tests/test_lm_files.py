"""What ``test_files.py::test_config_file`` and ``test_copies.py::
test_config_file_is_the_programs_preset`` hold for the ViT
configurations, held for a token model's: the file builds the program's
model, it equals the program's preset (``configs.LM_PRESETS``, which the
ViT-only test does not look in), the copied FLOP count equals the
program's, the file keeps every published width, and the cell's file
names what the driver reads."""

import dataclasses
import json

import numpy as np
import pytest

from benchmark.lib import flops_lm, harness, kernels_lm

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
LM_CONFIGS = sorted(
    p for p in (harness.BENCH / "configs").glob("*.json")
    if harness.load_json(p)["model"].get("vocab_size"))


def test_there_is_a_token_model_configuration():
    assert [p.stem for p in LM_CONFIGS] == ["smallthinker-21b-a3b-ep4"]


@pytest.mark.parametrize("path", LM_CONFIGS, ids=lambda p: p.stem)
def test_config_file_builds_the_programs_preset(path):
    from pytorch_vit_paper_replication_tpu.configs import LM_PRESETS

    config = harness.load_json(path)
    cfg, model = harness.build_model(config)
    assert cfg == LM_PRESETS[config["program_preset"]]()
    tiny = harness.load_cell("st21b_train_16k", rehearsal=True)[1]
    assert harness.build_model(tiny)[0] == LM_PRESETS["lm-tiny"]()
    assert len(config["source"]) <= 200
    assert {"published", "assumed", "deployment", "reduced"} <= set(config)
    assert "4 chips share each layer" in config["deployment"]


@pytest.mark.parametrize("path", LM_CONFIGS, ids=lambda p: p.stem)
def test_flop_count_equals_the_programs(path):
    from pytorch_vit_paper_replication_tpu.telemetry import flops as theirs

    config = harness.load_json(path)
    cfg, _ = harness.build_model(config)
    for t in (16384, 8192, 1000):
        assert flops_lm.train_step_flops_per_sequence(config["model"], t) \
            == theirs.train_step_flops_per_sequence(cfg, t)
    per_token = flops_lm.forward_flops_per_sequence(
        config["model"], 16384) / 16384
    assert per_token / 1e6 == pytest.approx(705.9, abs=0.1)
    # the attention core is 38% of it, as PERF.md says of the cell
    core = kernels_lm.attention_core_cost(config["model"], 16384, 1)
    assert core["flops"] / 3 / 16384 / 1e6 == pytest.approx(271.6, abs=0.1)
    assert flops_lm.visible_pairs(16384, 4096) / flops_lm.visible_pairs(
        16384) == pytest.approx(0.4375, abs=1e-3)


@pytest.mark.parametrize("path", LM_CONFIGS, ids=lambda p: p.stem)
def test_every_published_number_is_in_the_file_unless_reduced(path):
    """The contract's rule for a model of the catalog: the file holds
    every number of the catalog entry's ``config`` under the same key,
    and only the keys in ``reduced`` differ; no width is among them."""
    config = harness.load_json(path)
    try:
        rows = [json.loads(line) for line in open(CATALOG)]
    except OSError:
        pytest.skip("no catalog here")
    row = next(r for r in rows if r["source_url"] == config["source"])
    differ = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differ == set(config["reduced"]) == {
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size"}
    m, src = config["model"], row["config"]
    assert (m["embedding_dim"], m["num_heads"], m["num_kv_heads"],
            m["head_dim_override"], m["expert_width"], m["num_experts"],
            m["experts_per_token"], m["sliding_window"], m["rope_theta"],
            m["max_seq_len"], m["ln_epsilon"]) == (
        src["hidden_size"], src["num_attention_heads"],
        src["num_key_value_heads"], src["head_dim"],
        src["moe_ffn_hidden_size"], 64, src["moe_num_active_primary_experts"],
        src["sliding_window_size"], src["rope_theta"],
        src["max_position_embeddings"], src["rms_norm_eps"])
    period = len(m["rope_layout"])
    assert m["rope_layout"] == src["rope_layout"][:period]
    assert m["sliding_window_layout"] == src["sliding_window_layout"][:period]
    assert m["num_layers"] % period == 0
    assert (m["num_layers"], m["experts_held"], m["vocab_size"]) == (
        config["num_hidden_layers"], config["moe_num_primary_experts"],
        config["vocab_size"])
    assert config["published"]["vocab_size"] == src["vocab_size"]
    # the floors of a model_config cut
    assert m["experts_held"] >= 8 and m["num_layers"] >= 4
    assert m["vocab_size"] * 8 >= src["vocab_size"]


def test_parameters_and_bytes_of_the_cut():
    """656.5 M parameters, 10.50 GB at 16 bytes a parameter."""
    import jax
    import jax.numpy as jnp

    cfg, model = harness.build_model(harness.load_json(LM_CONFIGS[0]))
    shapes = jax.eval_shape(model.init, jax.random.key(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    n = sum(int(jnp.prod(jnp.array(a.shape)))
            for a in jax.tree.leaves(shapes))
    assert n == 656_529_920
    assert dataclasses.asdict(cfg)["experts_held"] == 16


def test_cell_file_names_what_the_driver_reads():
    from benchmark.drivers import train_lm

    cell, config = harness.load_cell("st21b_train_16k")
    p = cell[cell["driver"]]
    assert set(p) >= {"batch_per_chip", "seq_len", "recipe", "rng_impl",
                      "pool_batches", "successors", "remat",
                      "expect_kernels", "work_seeds"}
    assert p["batch_per_chip"] == 1 and p["seq_len"] == 16384 \
        == config["model"]["max_seq_len"]
    # the forward and the grouped products by name, the flash backward
    # as a family: one or two calls for each of the 4 layers
    assert set(p["expect_kernels"]) == {
        "flash_fwd", "flash_bwd*", "moe_gmm_fwd", "moe_gmm_dx",
        "moe_gmm_dw"}
    assert p["expect_kernels"]["flash_bwd*"] == [4, 8]
    # between the program's largest reading and the fp8 control's
    assert 0.01075 < train_lm.LOGITS_RMS_TOLERANCE < 0.523
    pool = train_lm.make_pool(2**31 + 7, 2, 1, 64, 256, 4)
    again = train_lm.make_pool(2**31 + 7, 2, 1, 64, 256, 4)
    assert all((a["tokens"] == b["tokens"]).all()
               for a, b in zip(pool, again))
    assert (pool[0]["tokens"][:, 1:] == pool[0]["label"][:, :-1]).all()
    assert pool[0]["tokens"].max() < 256
    # one rank-to-row map for the whole pool, as the trainer's stream:
    # every sequence has the same most frequent id
    top = [np.bincount(b["tokens"].ravel(), minlength=256).argmax()
           for b in train_lm.make_pool(5, 4, 1, 4096, 256, 4)]
    assert len(set(top)) == 1


@pytest.mark.parametrize("seed", [0, 1, 7301, 52003, 2**31 + 7])
def test_every_seed_does_the_same_work_in_another_order(seed):
    """The step's time follows the load that the draw of the weights
    puts on the held experts, so ``--seed`` takes one of the cell's
    measured draws and orders its batches: the same seed the same run,
    any seed one of the listed draws with every batch fed once a
    cycle."""
    from benchmark.drivers import train_lm

    p = harness.load_cell("st21b_train_16k")[0]["train_lm"]
    work, order = train_lm.work_of(p, seed)
    assert (work, order) == train_lm.work_of(p, seed)
    assert work == p["work_seeds"][seed % len(p["work_seeds"])]
    assert sorted(order) == list(range(p["pool_batches"]))
    # a cell that lists no draws takes everything from the seed
    free = {k: v for k, v in p.items() if k != "work_seeds"}
    assert train_lm.work_of(free, seed) == (seed, order)


def test_seeds_differ_in_the_order_and_not_in_the_draws_they_can_take():
    from benchmark.drivers import train_lm

    p = harness.load_cell("st21b_train_16k")[0]["train_lm"]
    seen = [train_lm.work_of(p, s) for s in range(2**31, 2**31 + 48)]
    assert {w for w, _ in seen} == set(p["work_seeds"])
    assert len({tuple(o) for _, o in seen}) > 12     # of 24 orders
