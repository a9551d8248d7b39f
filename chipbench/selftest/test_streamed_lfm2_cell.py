"""CPU rehearsal of the streamed language-model driver (``drivers/
train_round_streamed_lm.py``) with the LFM2 reference, at toy size, in a
toy directory of its own: the contract's last line on a bundle whose table
is tied, the traced run's readers (the accepted ones unchanged, the three
new ones with something to read), both lower-precision controls not
correct, the real configuration's file against the catalog's widths, the
manifest against the rules, the new operation count against a hand count,
and the tied table's reader on a hand-written text."""

from __future__ import annotations

import json
import os
import time
from types import SimpleNamespace

import pytest

from chipbench import harness, opcount_attention_qk_v, opcount_short_conv
from chipbench.selftest import manifest_rules

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "lfm2-moe-ps.trimmed-signflip-4k"
APPENDED = {
    "moe_device_ms.train", "attention_device_ms.train", "mlp_device_ms.train",
    "head_device_ms.train", "norm_device_ms.train", "recompute_device_ms.train",
    "stream_rows_device_ms.train", "segment_max_device_ms.train", "round_rows_peak_mb.train",
    "held_expert_tokens_min.train", "expert_rounds_max.train", "attention_kernel_calls.train",
    "model_unlabelled_pct.train", "attack_in_kernel_segments.train",
    "attention_qk_v_mxu_pct.train",
}
NEW = ["short_conv_device_ms.train", "short_conv_hbm_roofline_pct.train",
       "tied_table_kept_mb.train"]
ACCEPTED_CELLS = [
    "resnet18-ps.trimmed-signflip", "resnet18-ps.krum-empire",
    "resnet18-ps.trimmed-signflip.mesh4", "nemotron3-nano-ps.trimmed-signflip-4k",
    "glm47-flash-ps.trimmed-signflip-4k", "qwen3-next-ps.trimmed-signflip-4k",
    "xing4-ps.trimmed-signflip-1k"]


def _reader(name):
    return harness.load_by_path(
        os.path.join(ROOT, "chipbench", "layer_metrics", name + ".py"), name)


def _real_manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        return json.load(fh)


def _toy_manifest():
    toy = harness.load_json(HERE, "toy_streamed_lfm2", "manifest.json")
    toy["end_to_end"] = [{k: v for k, v in m.items() if k != "workloads"}
                         for m in _real_manifest()["end_to_end"]]
    readers = sorted(f[:-3] for f in os.listdir(os.path.join(ROOT, "chipbench", "layer_metrics"))
                     if f.endswith(".train.py"))
    toy["per_layer"] = [
        {"name": name, "unit": "-", "better": "lower", "source": "program_counter",
         "layer": "selftest", "moves": "train_samples_per_s", "workloads": ["toy.streamed_lfm2"]}
        for name in readers
    ]
    return toy


def _run(*, trace, control=None, seed=2**31 + 46):
    import jax

    lines = []
    line = harness.run_cell(
        _toy_manifest(), "toy.streamed_lfm2", seed=seed, seconds=0.5, trace=trace,
        devices=jax.devices()[:1], t_process=time.perf_counter(), control=control,
        emit=lines.append,
    )
    assert json.loads(lines[-1]) == json.loads(json.dumps(line, default=float))
    return line


def test_streamed_lfm2_toy_cell_prints_the_contracts_line():
    line = _run(trace=False)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 4
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}


def test_streamed_lfm2_toy_cell_traced_feeds_the_accepted_readers_and_its_own():
    line = _run(trace=True)
    assert line["correct"] is True
    got = set(line["metrics"])
    manifest = _real_manifest()
    unlisted = {m["name"] for m in manifest["per_layer"] if "workloads" not in m}
    # (matrix_build_device_ms.train has had a list since PR 46: twelve are left)
    assert len(unlisted) == 12 and unlisted - {"agg_roofline.train"} <= got
    # no peak on a CPU: the shares of one are None here, as in the other rehearsals
    on_a_cpu = {"short_conv_hbm_roofline_pct.train", "attention_qk_v_mxu_pct.train"}
    assert (APPENDED | set(NEW)) - on_a_cpu <= got
    # no state-space, delta-rule or latent mixer, no shared expert, no MTP module
    for absent in ("ssm_scan_device_ms.train", "delta_rule_device_ms.train",
                   "mla_latent_device_ms.train", "moe_shared_device_ms.train",
                   "hc_device_ms.train", "mtp_device_ms.train", "robust_overhead_pct.train",
                   "collective_device_ms.train", *on_a_cpu):
        assert absent not in got
    values = {k: v["value"] for k, v in line["metrics"].items()}
    assert values["fwdbwd_device_ms.train"] > values["recompute_device_ms.train"] > 0
    for name in ("short_conv_device_ms.train", "attention_device_ms.train",
                 "moe_device_ms.train", "mlp_device_ms.train", "head_device_ms.train",
                 "norm_device_ms.train", "stream_rows_device_ms.train"):
        assert values[name] > 0, name
    assert values["attention_kernel_calls.train"] == 0  # the lax.map route
    # the toy's table (64 x 32) is no whole tiles: its row is flat and the head's
    # gradient of it is made once before it lies in the row
    assert values["tied_table_kept_mb.train"] >= 0
    assert values["expert_rounds_max.train"] >= 1 and values["matrix_copies.train"] == 0
    assert values["model_unlabelled_pct.train"] < 5


@pytest.mark.parametrize("control", ["grad_bf16", "model_bf16"])
def test_each_lower_precision_control_of_the_streamed_lfm2_cell_comes_out_not_correct(control):
    assert _run(trace=False, control=control)["correct"] is False


def test_the_lfm2_configuration_holds_every_published_width_and_states_its_cut():
    cfg = harness.load_json(ROOT, "chipbench", "configs", "lfm2-24b-ep8-ps.json")
    period = ["conv", "conv", "full_attention", "conv"]
    published = dict(
        conv_L_cache=3, conv_bias=False, hidden_size=2048, intermediate_size=11776,
        layer_types=period * 10, max_position_embeddings=128000, model_type="lfm2_moe",
        moe_intermediate_size=1536, norm_eps=1e-05, norm_topk_prob=True, num_attention_heads=32,
        num_experts_per_tok=4, num_key_value_heads=8,
        rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
        routed_scaling_factor=1, use_expert_bias=True)
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers", "num_experts",
                              "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 40, "num_dense_layers": 2,
                                "num_experts": 64, "vocab_size": 65536}
    assert [cfg[k] for k in cfg["reduced"]] == [9, 1, 8, 8192]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["layers_held"] == [0, 2, 3, 4, 5, 6, 7, 8, 9]
    assert set(cfg["controls"]) == {"grad_bf16", "model_bf16"}
    assert cfg["stated_dtype"] == "float32" and "EIGHT" in cfg["deployment"]
    assert {"tied_table", "bcx_layout", "rotary_pairing", "expert_bias_and_router_precision",
            "router_denominator_eps", "weights", "data", "n_nodes_and_n_byzantine",
            "learning_rate", "expert_rounds"} <= set(cfg["assumed"])
    arch = cfg["reference"]["arch"]
    assert arch["held_experts"] == [0, 8]
    assert arch["rope_theta"] == cfg["rope_parameters"]["rope_theta"]
    for key, value in arch.items():  # the reference's sizes are the file's
        if key in cfg:
            assert cfg[key] == value, key
    # the head's widths once more, in the accepted reader's terms
    assert arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"] == arch["v_head_dim"] == (
        cfg["hidden_size"] // cfg["num_attention_heads"]) == 64
    # the program's factory at its defaults IS the file
    import jax

    from byzpy_tpu.models import lfm2_moe

    default = lfm2_moe.Lfm2MoeConfig()
    for key in published:
        if hasattr(default, key) and key != "layer_types":
            assert getattr(default, key) == cfg[key], key
    assert list(default.layer_types) == [cfg["layer_types"][at] for at in cfg["layers_held"]]
    assert default.rope_theta == cfg["rope_parameters"]["rope_theta"]
    assert default.num_experts == cfg["published"]["num_experts"]
    assert default.held_experts == (0, cfg["num_experts"])
    assert default.router_denominator_eps == arch["router_denominator_eps"]
    assert (default.num_hidden_layers, default.num_dense_layers, default.vocab_size) == (
        9, 1, 8192)
    bundle = jax.eval_shape(lambda: lfm2_moe.lfm2_24b_ep8(0).params)
    assert sum(leaf.size for leaf in jax.tree_util.tree_leaves(bundle)) == cfg["n_parameters"]
    keys = lfm2_moe.segment_keys(default)
    assert cfg["tied_table"] == {"leaf": "embedding", "owner": keys[0], "reader": keys[-1]}


def test_the_lfm2_cell_is_in_the_manifest_and_the_manifest_meets_the_rules():
    manifest = _real_manifest()
    assert manifest_rules.check(manifest, ROOT) == []
    cell = harness.find_cell(manifest, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2-24b-ep8-ps", "trimmed-signflip-tok4k-lm", 1)
    assert cell["config"] in [c["name"] for c in manifest["configs"]]
    mine = {m["name"] for m in harness.metrics_of_cell(manifest, CELL, "per_layer")}
    assert APPENDED | set(NEW) <= mine
    for absent in ("ssm_scan_device_ms.train", "delta_rule_device_ms.train",
                   "mtp_device_ms.train", "moe_shared_device_ms.train",
                   "matrix_build_device_ms.train", "attention_kernel_mxu_pct.train"):
        assert absent not in mine
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:  # a reader that may return None has a list from the start
        assert by_name[name]["workloads"] == [CELL]
    assert by_name["short_conv_hbm_roofline_pct.train"]["layer"] == "kernels"
    assert by_name["tied_table_kept_mb.train"]["source"] == "program_counter"
    # the metric that has had nothing to read in the language cells since PR 43
    # listed the seven cells accepted before this one, so that this cell did not
    # report it; since PR 52 it lists the cells with a matrix build, the three ResNet cells
    assert by_name["matrix_build_device_ms.train"]["workloads"] == ACCEPTED_CELLS[:3]
    # (later cells are appended behind this one)
    assert [w["name"] for w in manifest["workloads"]][:8] == ACCEPTED_CELLS + [CELL]
    assert cell["chips"] == 1  # nothing of it exists only across chips


def test_the_short_convolutions_opcount_is_the_hand_count():
    # one worker's (4096, 2048) float32 array: 33.6 MB; fifteen of them a block,
    # seven short-convolution blocks, six honest workers: 21.1 GB, 25.8 ms at 819 GB/s
    cfg = harness.load_json(ROOT, "chipbench", "configs", "lfm2-24b-ep8-ps.json")
    assert opcount_short_conv.conv_blocks(cfg) == 7
    least = opcount_short_conv.least_bytes_per_step(cfg, {"tokens_per_worker": 4096})
    assert least == 15 * 7 * 6 * 4096 * 2048 * 4 == 60 * 4096 * 2048 * 7 * 6
    assert least / 819e9 == pytest.approx(0.02581, rel=1e-3)
    # the accepted count of the kernels' products takes grouped-query widths as they
    # are: 32 query heads of 64 / 64 (the key/value heads' number does not enter)
    flops = opcount_attention_qk_v.kernel_flops("causal_attention_fwd", 32, 64, 64, 4096)
    assert flops == 32 * (4096 * 4096 / 2) * 2 * 128


def test_the_roofline_reader_is_the_floor_over_the_labels_time(monkeypatch):
    reader = _reader("short_conv_hbm_roofline_pct.train")
    cfg = harness.load_json(ROOT, "chipbench", "configs", "lfm2-24b-ep8-ps.json")
    peaks = harness.load_json(ROOT, "chipbench", "peaks.json")
    ctx = SimpleNamespace(peaks=peaks, config=cfg, mix={"tokens_per_worker": 4096},
                          devices=[SimpleNamespace(device_kind="TPU v5 lite")])
    asked = []

    def path_ms(ctx_, *labels, without=()):
        asked.append((labels, tuple(without)))
        return 60.0

    monkeypatch.setattr(reader.scope_paths, "path_ms", path_ms)
    assert reader.read(ctx) == pytest.approx(100 * 21139292160 / 819e9 / 0.060)  # 43 %
    # the projections' label begins with the operator's letters and is left out
    assert asked == [(("model.short_conv",), ("model.short_conv_proj",))]
    monkeypatch.setattr(reader.scope_paths, "path_ms", lambda ctx_, *labels, without=(): None)
    assert reader.read(ctx) is None
    other = harness.load_json(ROOT, "chipbench", "configs", "glm47-flash-ep8-ps.json")
    assert reader.read(SimpleNamespace(**{**vars(ctx), "config": other})) is None
    assert reader.read(SimpleNamespace(**{**vars(ctx), "devices": [
        SimpleNamespace(device_kind="cpu")]})) is None


_TEXT = """HloModule jit_train_step

%fused_in_place (p0: f32[6,64,128], p1: f32[1,64,128]) -> f32[6,64,128] {
  %p0 = f32[6,64,128]{2,1,0} parameter(0)
  %p1 = f32[1,64,128]{2,1,0} parameter(1)
  ROOT %dus = f32[6,64,128]{2,1,0} dynamic-update-slice(%p0, %p1), metadata={op_name="jit(train_step)/segment.seg04_head/while/body/closed_call/round.segment_bwd/round.fwdbwd/stream.shared_rows/dynamic_update_slice"}
}

%fused_grad (p0: f32[16,64], p1: f32[16,128]) -> f32[64,128] {
  %p0 = f32[16,64]{1,0} parameter(0)
  %p1 = f32[16,128]{1,0} parameter(1)
  ROOT %dot = f32[64,128]{1,0} dot(%p0, %p1), lhs_contracting_dims={0}, rhs_contracting_dims={0}, metadata={op_name="jit(train_step)/segment.seg04_head/while/body/closed_call/round.segment_bwd/round.fwdbwd/transpose(jvp(model.head))/dot_general"}
}

ENTRY %main (a: f32[64,128], b: f32[16,64], c: f32[16,128]) -> f32[6,64,128] {
  %a = f32[64,128]{1,0} parameter(0)
  %b = f32[16,64]{1,0} parameter(1)
  %c = f32[16,128]{1,0} parameter(2)
  %rows = f32[6,64,128]{2,1,0} broadcast(%a), dimensions={1,2}, metadata={op_name="jit(train_step)/segment.seg04_head/empty"}
  %grad = f32[64,128]{1,0} fusion(%b, %c), kind=kOutput, calls=%fused_grad
  %laid = f32[8,8,128]{2,1,0} copy(%grad), metadata={op_name="jit(train_step)/segment.seg04_head/while/body/closed_call/round.segment_bwd/round.fwdbwd/stream.shared_rows/transpose"}
  %view = f32[1,64,128]{2,1,0} bitcast(%laid)
  %elsewhere = f32[64,128]{1,0} add(%a, %a), metadata={op_name="jit(train_step)/segment.seg02_attn_moe/round.update/add"}
  ROOT %written = f32[6,64,128]{2,1,0} fusion(%rows, %view), kind=kLoop, calls=%fused_in_place
}
"""


def test_the_tied_tables_reader_counts_what_the_readers_turn_makes_of_the_tables_size():
    reader = _reader("tied_table_kept_mb.train")
    config = {"n_nodes": 8, "n_byzantine": 2, "vocab_size": 64, "hidden_size": 128,
              "tied_table": {"leaf": "embedding", "owner": "seg00_embed", "reader": "seg04_head"}}
    ctx = SimpleNamespace(config=config, outcome={"compiled_text": _TEXT})
    # the gradient through the head and its relaid copy: two tables; the rows, the
    # in-place write, the view and another segment's array are not counted
    assert reader.read(ctx) == pytest.approx(2 * 64 * 128 * 4 / 1e6)
    untied = SimpleNamespace(config={k: v for k, v in config.items() if k != "tied_table"},
                             outcome={"compiled_text": _TEXT})
    assert reader.read(untied) is None
    assert reader.read(SimpleNamespace(config=config, outcome={"compiled_text": ""})) is None
    parent = _TEXT.replace("stream.shared_rows", "stream.rows")  # a program without the rule
    assert reader.read(SimpleNamespace(config=config, outcome={"compiled_text": parent})) is None
