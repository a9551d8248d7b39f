"""A configuration's ``checkpoint_seed`` (the weights are the
configuration's, the batches ``--seed``'s), the expert layers' loads beside
their round (``chipbench/expert_round.py``, ``expert_round_margin_pct.train``)
and the re-pointed ``round_rows_peak_mb.train``: the two streamed drivers at
toy size on the CPU, and the readers on counts and texts written by hand."""

from __future__ import annotations

import hashlib
import importlib
import json
import os
import shutil
import time
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import expert_round, harness

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CHECKPOINT = 5200000007
TOYS = {
    "streamed": ("toy_streamed", "toy.streamed", "chipbench.seeded_nemotron_h"),
    "streamed_lm": ("toy_streamed_lm", "toy.streamed_lm", "chipbench.seeded_glm4_moe_lite"),
}


def _digest(tree) -> str:
    import jax

    said = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(tree):
        said.update(np.asarray(leaf).tobytes())
    return said.hexdigest()


def _toy(tmp_path, toy_dir, checkpoint_seed):
    """The toy's files copied to ``tmp_path``, its configuration with or
    without the key, and its manifest with the files' new places."""
    root = os.path.join(str(tmp_path), "keyed" if checkpoint_seed is not None else "plain")
    shutil.copytree(os.path.join(HERE, toy_dir), root)
    manifest = harness.load_json(root, "manifest.json")
    manifest["end_to_end"] = [
        {k: v for k, v in m.items() if k != "workloads"}
        for m in harness.load_json(ROOT, "BENCHMARK.json")["end_to_end"]]
    entry = manifest["configs"][0]
    entry["file"] = os.path.join("configs", os.path.basename(entry["file"]))
    if checkpoint_seed is not None:
        path = os.path.join(root, entry["file"])
        config = harness.load_json(path)
        config["checkpoint_seed"] = checkpoint_seed
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(config, fh)
    return manifest, root


def _recorded_run(monkeypatch, manifest, root, cell, seeded_name, seed):
    """One untraced run; what the seeded module was asked for and gave,
    and what the reference was handed."""
    import jax

    seeded_module = importlib.import_module(seeded_name)
    config = harness.load_json(root, manifest["configs"][0]["file"])
    follow_at, _, follow_name = config["reference"]["follow_rounds"].rpartition(".")
    reference_module = importlib.import_module(follow_at)
    seen = {"params": [], "segments": set(), "batches": [], "reference": []}
    make_params, make_segment = seeded_module.make_params, seeded_module.make_segment
    make_batches, follow = seeded_module.make_token_batches, getattr(reference_module, follow_name)

    def params_recorded(shapes, seed_, arch):
        made = make_params(shapes, seed_, arch)
        seen["params"].append((seed_, _digest(made)))
        return made

    def segment_recorded(shapes, seed_, segment, arch):
        seen["segments"].add(seed_)
        return make_segment(shapes, seed_, segment, arch)

    def batches_recorded(seed_, **kwargs):
        made = make_batches(seed_, **kwargs)
        seen["batches"].append((seed_, _digest(made)))
        return made

    def follow_recorded(arch, params0, batches, **kwargs):
        seen["reference"].append((_digest(params0), _digest([x for x, _ in batches])))
        return follow(arch, params0, batches, **kwargs)

    with monkeypatch.context() as patched:
        patched.setattr(seeded_module, "make_params", params_recorded)
        patched.setattr(seeded_module, "make_segment", segment_recorded)
        patched.setattr(seeded_module, "make_token_batches", batches_recorded)
        patched.setattr(reference_module, follow_name, follow_recorded)
        line = harness.run_cell(
            manifest, cell, seed=seed, seconds=0.3, trace=False, devices=jax.devices()[:1],
            t_process=time.perf_counter(), files_root=root, emit=lambda text: None)
    assert line["correct"] is True
    return seen


@pytest.mark.parametrize("toy", sorted(TOYS))
def test_a_stated_checkpoint_seeds_the_weights_and_the_run_seed_everything_else(
        toy, tmp_path, monkeypatch, capsys):
    toy_dir, cell, seeded_name = TOYS[toy]
    manifest, root = _toy(tmp_path, toy_dir, CHECKPOINT)
    first = _recorded_run(monkeypatch, manifest, root, cell, seeded_name, 2**31 + 41)
    start = [json.loads(l) for l in capsys.readouterr().out.splitlines() if '"run": "start"' in l]
    assert (start[0]["seed"], start[0]["weights_seed"]) == (2**31 + 41, CHECKPOINT)
    second = _recorded_run(monkeypatch, manifest, root, cell, seeded_name, 77)
    for seen, seed in ((first, 2**31 + 41), (second, 77)):
        # the program's weights, and params0 made again for the reference: both the checkpoint's
        assert [s for s, _ in seen["params"]] == [CHECKPOINT, CHECKPOINT]
        assert seen["segments"] == {CHECKPOINT}  # the change's starting weights too
        assert [s for s, _ in seen["batches"]] == [seed]
        # program and reference are handed the same arrays
        (reference_params, reference_xs), = seen["reference"]
        assert reference_params == seen["params"][0][1] == seen["params"][1][1]
        assert len(reference_xs) == 64
    assert first["params"] == second["params"]  # the same weights on two --seeds
    assert first["batches"][0][1] != second["batches"][0][1]  # and other batches
    assert first["reference"][0][1] != second["reference"][0][1]


@pytest.mark.parametrize("toy", sorted(TOYS))
def test_a_configuration_without_the_key_draws_the_weights_it_drew_before(
        toy, tmp_path, monkeypatch, capsys):
    import jax

    toy_dir, cell, seeded_name = TOYS[toy]
    manifest, root = _toy(tmp_path, toy_dir, None)
    seed = 2**31 + 43
    seen = _recorded_run(monkeypatch, manifest, root, cell, seeded_name, seed)
    start = [json.loads(l) for l in capsys.readouterr().out.splitlines() if '"run": "start"' in l]
    assert (start[0]["seed"], start[0]["weights_seed"]) == (seed, seed)
    assert [s for s, _ in seen["params"]] == [seed, seed] and seen["segments"] == {seed}
    # byte for byte what the seeded module gives for --seed, called as the parent called it
    config = harness.load_json(root, manifest["configs"][0]["file"])
    kwargs = dict(config["model"]["kwargs"])
    if "held_experts" in kwargs:
        kwargs["held_experts"] = tuple(kwargs["held_experts"])
    shapes = jax.eval_shape(
        lambda: harness.resolve(config["model"]["factory"])(0, **kwargs).params)
    before = importlib.import_module(seeded_name).make_params(
        shapes, seed, config["reference"]["arch"])
    assert _digest(before) == seen["params"][0][1] == seen["reference"][0][0]


def test_the_context_takes_the_checkpoint_seed_from_the_configuration_alone():
    def ctx(config, seed):
        return harness.Ctx(manifest={}, cell={"name": "c"}, config=config, mix={}, seed=seed,
                           seconds=0, trace=False, devices=[], t_process=0.0)

    assert ctx({}, 11).weights_seed == 11
    assert ctx({"checkpoint_seed": 5200000003}, 11).weights_seed == 5200000003
    assert ctx({"checkpoint_seed": 5200000003}, 12).weights_seed == 5200000003
    real = harness.load_json(ROOT, "chipbench", "configs", "nemotron3-nano-ep16-ps.json")
    assert isinstance(real["checkpoint_seed"], int) and "checkpoint" in real["assumed"]["weights"]
    for name in ("qwen3-next-ep16-ps", "xing4-29b-ep8-ps", "lfm2-24b-ep8-ps",
                 "smallthinker-21b-ep8-ps", "resnet18-cifar-ps"):
        other = harness.load_json(ROOT, "chipbench", "configs", name + ".json")
        assert "checkpoint_seed" not in other


# -- the loads beside the round ---------------------------------------------------


@pytest.mark.parametrize("count, rows, want", [
    (500, 512, 2.34375), (762, 512, 48.828125), (256, 512, 50.0), (1030, 512, 1.171875),
    (512, 512, 0.0), (10, 512, 98.046875), (900, 1024, 12.109375)])
def test_the_margin_is_the_distance_to_the_nearest_whole_round(count, rows, want):
    assert expert_round.margin_pct([count], [count], [rows]) == pytest.approx(want)


def test_the_margin_is_the_least_over_the_layers_and_nought_where_a_layer_straddles():
    # four layers, the third the nearest: 926 of 512 is 98 tokens under 1024
    assert expert_round.margin_pct(
        [250, 600, 880, 200], [290, 762, 926, 237], [512] * 4) == pytest.approx(100 * 88 / 512)
    # a layer's passes on both sides of a whole round: an edge inside its own spread
    assert expert_round.margin_pct([250, 480], [290, 540], [512, 512]) == 0.0
    # at the edge itself a pass still takes one round: 512 of 512
    assert expert_round.margin_pct([400], [512], [512]) == 0.0
    # each layer against its OWN round
    assert expert_round.margin_pct([100, 300], [128, 320], [256, 640]) == pytest.approx(50.0)
    assert expert_round.margin_pct([], [], []) is None
    assert expert_round.margin_pct([300], [300], None) is None


def test_the_fullest_expert_by_layer_is_over_steps_workers_and_experts():
    tokens = np.zeros((3, 2, 2, 4), np.int64)  # (steps, h, layers, held)
    tokens[:, :, 0] = [100, 90, 80, 70]
    tokens[2, 1, 0] = [10, 300, 20, 30]
    tokens[:, :, 1] = [5, 6, 7, 8]
    tokens[0, 0, 1] = [1, 2, 3, 4]
    assert expert_round.fullest_by_layer(tokens) == {"largest": [300, 8], "least": [100, 4]}


TEXT = """HloModule jit_train_step
  %up = f32[4,32,24]{2,1,0} parameter(0), metadata={op_name="jit(train_step)/round.fwdbwd/segment.seg01_moe/model.moe_experts/w_up"}
  %down = f32[4,24,32]{2,1,0} parameter(1), metadata={op_name="jit(train_step)/round.fwdbwd/segment.seg01_moe/model.moe_experts/w_down"}
  %rows = f32[4,16,32]{2,1,0} gather(%x), metadata={op_name="jit(train_step)/round.segment_fwd/round.fwdbwd/segment.seg01_moe/model.moe_experts/gather"}
  %hidden = bf16[4,16,24]{2,1,0} dot(%rows, %up), metadata={op_name="jit(train_step)/round.segment_fwd/round.fwdbwd/segment.seg01_moe/model.moe_experts/vmap()/dot_general"}
  %rows3 = f32[4,40,32]{2,1,0} gather(%x), metadata={op_name="jit(train_step)/round.segment_bwd/round.fwdbwd/segment.seg03_moe/model.moe_experts/gather"}
  %hidden3 = f32[4,40,24]{2,1,0} dot(%rows3, %up), metadata={op_name="jit(train_step)/round.segment_bwd/round.fwdbwd/segment.seg03_moe/model.moe_experts/vmap()/dot_general"}
  %other = f32[4,8,32]{2,1,0} add(%a, %b), metadata={op_name="jit(train_step)/round.fwdbwd/segment.seg02_attn/model.attention/add"}
  %heads = f32[4,8,64]{2,1,0} add(%a, %b), metadata={op_name="jit(train_step)/round.fwdbwd/segment.seg02_attn/model.attention/add"}
  %lone = f32[4,12,32]{2,1,0} add(%a, %b), metadata={op_name="jit(train_step)/round.fwdbwd/segment.seg05_moe/model.moe_experts/add"}
  %stack = f32[6,100,128]{2,1,0} dynamic-update-slice(%s, %g), metadata={op_name="jit(train_step)/round.segment_bwd/stream.rows/dynamic_update_slice"}
  %kept = f32[6,40,128]{2,1,0} dynamic-update-slice(%s, %g), metadata={op_name="jit(train_step)/round.segment_fwd/stream.boundary/dynamic_update_slice"}
  %experts = f32[8,2000,128]{2,1,0} parameter(2), metadata={op_name="jit(train_step)/round.fwdbwd/segment.seg01_moe/model.moe_experts/w"}
  %one = f32[1,4000,128]{2,1,0} add(%a, %b), metadata={op_name="jit(train_step)/round.segment_bwd/stream.rows/add"}
"""


def test_the_rounds_rows_are_read_off_the_compiled_text_by_label_and_segment():
    # rows: the one middle axis that stands before two widths; the matrices' own
    # (32 before 24, 24 before 32) stand before one; other labels do not count
    assert expert_round.rows_in_text(TEXT, 4) == {"seg01_moe": 16, "seg03_moe": 40}
    assert expert_round.rows_in_text(TEXT, 8) == {}
    aux = {"seg00_embed": {}, "seg01_moe": {"held_expert_tokens": np.zeros((6, 4))},
           "seg03_moe": {"held_expert_tokens": np.zeros((6, 4))}, "seg04_head": {"main_loss": 1.0}}
    assert expert_round.rows_by_layer(aux, TEXT) == [16, 40]
    # the program's own word goes before the text's
    aux["seg03_moe"]["expert_round_rows"] = np.full((6,), 48)
    assert expert_round.rows_by_layer(aux, TEXT) == [16, 48]
    # a layer with neither: no rows, and no margin
    aux["seg05_moe"] = {"held_expert_tokens": np.zeros((6, 4))}
    assert expert_round.rows_by_layer(aux, TEXT) is None
    assert expert_round.rows_by_layer({"seg00_embed": {}}, TEXT) is None
    said = expert_round.facts(
        {"seg01_moe": aux["seg01_moe"]}, np.full((2, 6, 1, 4), 12), TEXT)
    assert said == {"held_expert_tokens_by_layer": [12], "held_expert_fullest_least_by_layer": [12],
                    "expert_round_rows": [16], "expert_round_margin_pct": 25.0}


def _reader(name):
    return harness.load_by_path(
        os.path.join(ROOT, "chipbench", "layer_metrics", name + ".py"), name)


def test_the_margins_reader_reads_what_the_driver_kept_and_nothing_without_an_expert_layer():
    reader = _reader("expert_round_margin_pct.train")
    measured = {"held_expert_tokens_by_layer": [290, 762], "expert_round_rows": [512, 512],
                "held_expert_fullest_least_by_layer": [250, 600]}
    assert reader.read(SimpleNamespace(outcome={"measured": measured})) == pytest.approx(
        100 * 88 / 512)
    assert reader.read(SimpleNamespace(outcome={"measured": {"traced_steps": 4}})) is None
    entry = next(m for m in harness.load_json(ROOT, "BENCHMARK.json")["per_layer"]
                 if m["name"] == "expert_round_margin_pct.train")
    assert (entry["layer"], entry["better"], entry["moves"], entry["source"], entry["unit"]) == (
        "model", "higher", "train_samples_per_s", "program_counter", "%")
    manifest = harness.load_json(ROOT, "BENCHMARK.json")
    assert entry["workloads"] == [
        cell["name"] for cell in manifest["workloads"] if cell["config"] != "resnet18-cifar-ps"]


def test_round_rows_peak_reads_the_largest_stack_under_the_streamed_rounds_labels():
    reader = _reader("round_rows_peak_mb.train")
    config = {"n_nodes": 8, "n_byzantine": 2}
    ctx = SimpleNamespace(outcome={"compiled_text": TEXT}, config=config)
    # h = 6 rows under stream.rows; the held experts' (8, ...) matrices carry no such label
    assert reader.read(ctx) == pytest.approx(4 * 6 * 100 * 128 / 1e6)
    only_boundary = "\n".join(l for l in TEXT.splitlines() if "stream.rows" not in l)
    ctx = SimpleNamespace(outcome={"compiled_text": only_boundary}, config=config)
    assert reader.read(ctx) == pytest.approx(4 * 6 * 40 * 128 / 1e6)
    none = "\n".join(l for l in TEXT.splitlines() if "stream." not in l)
    assert reader.read(SimpleNamespace(outcome={"compiled_text": none}, config=config)) is None
    assert reader.read(SimpleNamespace(outcome={}, config=config)) is None
    manifest = harness.load_json(ROOT, "BENCHMARK.json")
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert by_name["matrix_build_device_ms.train"]["workloads"] == [
        cell["name"] for cell in manifest["workloads"] if cell["config"] == "resnet18-cifar-ps"]
