"""CPU rehearsal of the streamed language-model driver (``drivers/
train_round_streamed_lm.py``) with the Qwen3-Next reference, at toy size,
in a toy directory of its own: the contract's last line, the traced run's
readers (the accepted ones unchanged, the gated delta rule's two with
something to read), both lower-precision controls not correct, the real
configuration's file against the catalog's widths, the manifest against
the rules, and the delta rule's operation count against a hand count."""

from __future__ import annotations

import json
import os
import time
from types import SimpleNamespace

import pytest

from chipbench import harness, opcount_delta_rule
from chipbench.selftest import manifest_rules

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "qwen3-next-ps.trimmed-signflip-4k"
APPENDED = {
    "ssm_proj_device_ms.train", "ssm_gate_device_ms.train", "moe_device_ms.train",
    "moe_shared_device_ms.train", "attention_device_ms.train", "attention_kernel_calls.train",
    "attention_kernel_mxu_pct.train", "recompute_device_ms.train", "round_rows_peak_mb.train",
    "held_expert_tokens_min.train", "expert_rounds_max.train", "model_unlabelled_pct.train",
    "head_device_ms.train", "norm_device_ms.train", "stream_rows_device_ms.train",
    "segment_max_device_ms.train",
}
NEW = {"delta_rule_device_ms.train", "delta_rule_roofline_pct.train"}


def _real_manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        return json.load(fh)


def _toy_manifest():
    toy = harness.load_json(HERE, "toy_streamed_qwen3", "manifest.json")
    toy["end_to_end"] = [{k: v for k, v in m.items() if k != "workloads"}
                         for m in _real_manifest()["end_to_end"]]
    readers = sorted(f[:-3] for f in os.listdir(os.path.join(ROOT, "chipbench", "layer_metrics"))
                     if f.endswith(".train.py"))
    toy["per_layer"] = [
        {"name": name, "unit": "-", "better": "lower", "source": "program_counter",
         "layer": "selftest", "moves": "train_samples_per_s", "workloads": ["toy.streamed_qwen3"]}
        for name in readers
    ]
    return toy


def _run(*, trace, control=None, seed=2**31 + 29):
    import jax

    lines = []
    line = harness.run_cell(
        _toy_manifest(), "toy.streamed_qwen3", seed=seed, seconds=0.5, trace=trace,
        devices=jax.devices()[:1], t_process=time.perf_counter(), control=control,
        emit=lines.append,
    )
    assert json.loads(lines[-1]) == json.loads(json.dumps(line, default=float))
    return line


def test_streamed_qwen3_toy_cell_prints_the_contracts_line():
    line = _run(trace=False)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 4
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}


def test_streamed_qwen3_toy_cell_traced_feeds_the_accepted_readers_and_its_own():
    line = _run(trace=True)
    assert line["correct"] is True
    got = set(line["metrics"])
    # the thirteen readers without a list read this cell as they read the others
    manifest = _real_manifest()
    unlisted = {m["name"] for m in manifest["per_layer"] if "workloads" not in m}
    # (thirteen when the cell was added; `matrix_build_device_ms.train` got a list at PR 44)
    assert 12 <= len(unlisted) <= 13 and unlisted - {"agg_roofline.train"} <= got
    # no peak on a CPU: the shares of one are None here, as in the other rehearsals
    on_a_cpu = {"attention_kernel_mxu_pct.train", "delta_rule_roofline_pct.train"}
    assert (APPENDED | NEW) - on_a_cpu <= got
    # no Mamba-2 scan, no latent attention, no MTP module, no dense MLP; one chip
    for absent in ("ssm_scan_device_ms.train", "mla_latent_device_ms.train",
                   "mtp_device_ms.train", "mlp_device_ms.train", "robust_overhead_pct.train",
                   "collective_device_ms.train", *on_a_cpu):
        assert absent not in got
    values = {k: v["value"] for k, v in line["metrics"].items()}
    assert values["fwdbwd_device_ms.train"] > values["recompute_device_ms.train"] > 0
    for name in ("delta_rule_device_ms.train", "ssm_proj_device_ms.train",
                 "ssm_gate_device_ms.train", "attention_device_ms.train", "moe_device_ms.train",
                 "moe_shared_device_ms.train", "head_device_ms.train", "norm_device_ms.train"):
        assert values[name] > 0, name
    assert values["attention_kernel_calls.train"] == 0  # the lax.map route
    assert values["expert_rounds_max.train"] >= 1 and values["matrix_copies.train"] == 0
    assert values["model_unlabelled_pct.train"] < 5


@pytest.mark.parametrize("control", ["grad_bf16", "model_bf16"])
def test_each_lower_precision_control_of_the_streamed_qwen3_cell_comes_out_not_correct(control):
    assert _run(trace=False, control=control)["correct"] is False


def test_the_qwen3_next_configuration_holds_every_published_width_and_states_its_cut():
    cfg = harness.load_json(ROOT, "chipbench", "configs", "qwen3-next-ep16-ps.json")
    published = dict(
        hidden_size=2048, head_dim=256, num_attention_heads=16, num_key_value_heads=2,
        linear_num_key_heads=16, linear_num_value_heads=32, linear_key_head_dim=128,
        linear_value_head_dim=128, linear_conv_kernel_dim=4, full_attention_interval=4,
        partial_rotary_factor=0.25, rope_theta=10000000, rms_norm_eps=1e-06,
        moe_intermediate_size=512, shared_expert_intermediate_size=512, num_experts_per_tok=10,
        intermediate_size=5120, decoder_sparse_step=1, mlp_only_layers=[], norm_topk_prob=True,
        max_position_embeddings=262144, model_type="qwen3_next", hidden_act="silu",
        rope_scaling=None, tie_word_embeddings=False, use_sliding_window=False)
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                                "vocab_size": 151936}
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (4, 32, 18992)
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert set(cfg["controls"]) == {"grad_bf16", "model_bf16"}
    assert cfg["stated_dtype"] == "float32" and "SIXTEEN" in cfg["deployment"]
    assert {"in_proj_columns", "q_proj_columns", "chunk", "delta_rule_vectors", "weights",
            "rotary_pairing", "router_precision", "triangular_system", "data",
            "n_nodes_and_n_byzantine", "learning_rate", "expert_rounds"} <= set(cfg["assumed"])
    assert "no_mtp" in cfg["departures"]
    arch = cfg["reference"]["arch"]
    assert (arch["num_attention_heads"], arch["head_dim"]) == (16, 256)
    assert arch["held_experts"] == [0, 32]
    for key, value in arch.items():  # the reference's sizes are the file's
        if key in cfg:
            assert cfg[key] == value, key
    # the program's factory at its defaults IS the file
    import jax

    from byzpy_tpu.models import qwen3_next

    default = qwen3_next.Qwen3NextConfig()
    for key in published:
        if hasattr(default, key):
            assert getattr(default, key) == cfg[key], key
    assert default.num_experts == cfg["published"]["num_experts"]
    assert default.held_experts == (0, cfg["num_experts"])
    assert (default.num_hidden_layers, default.vocab_size) == (4, 18992)
    shapes = jax.eval_shape(lambda: qwen3_next.qwen3_next_ep16(0).params)
    assert sum(leaf.size for leaf in jax.tree_util.tree_leaves(shapes)) == cfg["n_parameters"]
    assert qwen3_next.round_rows(default, 4096) == 320


def test_the_qwen3_next_cell_is_in_the_manifest_and_the_manifest_meets_the_rules():
    manifest = _real_manifest()
    assert manifest_rules.check(manifest, ROOT) == []
    cell = harness.find_cell(manifest, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "qwen3-next-ep16-ps", "trimmed-signflip-tok4k-lm", 1)
    # (by name, not by place: later configurations are appended behind this one)
    assert cell["config"] in [c["name"] for c in manifest["configs"]]
    mine = {m["name"] for m in harness.metrics_of_cell(manifest, CELL, "per_layer")}
    assert APPENDED | NEW <= mine
    for absent in ("ssm_scan_device_ms.train", "mla_latent_device_ms.train",
                   "mtp_device_ms.train", "mlp_device_ms.train"):
        assert absent not in mine
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:  # a reader that may return None has a list from the start
        assert by_name[name]["workloads"] == [CELL]
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index(sorted(NEW)[0])  # appended together, in this order
    assert names[at:at + 2] == sorted(NEW)
    assert by_name["delta_rule_roofline_pct.train"]["layer"] == "kernels"
    # six cells then, more since; one of them on four chips
    assert len(manifest["workloads"]) >= 6
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


def test_delta_rule_opcount_is_the_hand_count():
    # two value heads on one key head, K = 4, V = 3: three products of 4 x 3
    # multiply-adds a value head; q, k of 4, v, o of 3 a value head, g, beta
    assert opcount_delta_rule.flops_per_position(2, 4, 3) == 2 * 3 * 2 * 4 * 3 == 144
    assert opcount_delta_rule.bytes_per_position(1, 2, 4, 3) == 4 * (4 + 4 + 6 + 6 + 2 + 2)
    arch = {"linear_num_key_heads": 16, "linear_num_value_heads": 32,
            "linear_key_head_dim": 128, "linear_value_head_dim": 128}
    assert opcount_delta_rule.flops_per_position(32, 128, 128) == 32 * 6 * 128 * 128
    assert opcount_delta_rule.bytes_per_position(16, 32, 128, 128) == 4 * (
        2048 + 2048 + 4096 + 4096 + 32 + 32)
    # at the v5e's peaks the bytes bind: 49,408 B / 819 GB/s against 3.1 MFLOP / 197 TFLOP/s
    least = opcount_delta_rule.least_seconds_per_position(
        arch, flops_per_s=197e12, bytes_per_s=819e9)
    assert least == pytest.approx(49408 / 819e9) and least > 3145728 / 197e12
    config = {"num_hidden_layers": 4, "full_attention_interval": 4, "n_nodes": 8,
              "n_byzantine": 2, "reference": {"arch": arch}}
    step = opcount_delta_rule.least_seconds_per_step(
        config, {"tokens_per_worker": 4096}, flops_per_s=197e12, bytes_per_s=819e9)
    assert step == pytest.approx(49408 / 819e9 * 4096 * 3 * 6 * 4)  # 17.8 ms


def test_the_roofline_reader_is_the_floor_over_the_labels_time(monkeypatch):
    reader = harness.load_by_path(
        os.path.join(ROOT, "chipbench", "layer_metrics", "delta_rule_roofline_pct.train.py"),
        "delta_rule_roofline_pct.train")
    cfg = harness.load_json(ROOT, "chipbench", "configs", "qwen3-next-ep16-ps.json")
    peaks = harness.load_json(ROOT, "chipbench", "peaks.json")
    ctx = SimpleNamespace(peaks=peaks, config=cfg, mix={"tokens_per_worker": 4096},
                          devices=[SimpleNamespace(device_kind="TPU v5 lite")])
    monkeypatch.setattr(reader.scope_paths, "path_ms", lambda ctx_, label: {
        "model.delta_rule": 178.0}[label])
    assert reader.read(ctx) == pytest.approx(100 * 49408 / 819e9 * 4096 * 72 / 0.178)  # 10 %
    # a program that never enters the label, a configuration without the
    # layer, a device without a peak: nothing to read, and no error
    monkeypatch.setattr(reader.scope_paths, "path_ms", lambda ctx_, label: None)
    assert reader.read(ctx) is None
    other = harness.load_json(ROOT, "chipbench", "configs", "glm47-flash-ep8-ps.json")
    assert reader.read(SimpleNamespace(**{**vars(ctx), "config": other})) is None
    assert reader.read(SimpleNamespace(**{**vars(ctx), "devices": [
        SimpleNamespace(device_kind="cpu")]})) is None
