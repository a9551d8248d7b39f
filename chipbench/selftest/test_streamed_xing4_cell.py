"""CPU rehearsal of the streamed language-model driver (``drivers/
train_round_streamed_lm.py``) with the Xing4.0 reference, at toy size, in a
toy directory of its own: the contract's last line, the traced run's
readers (the accepted ones unchanged, the hyper-connections' with something
to read), both lower-precision controls not correct, the real
configuration's file against the catalog's widths, the manifest against the
rules, and the two new operation counts against a hand count."""

from __future__ import annotations

import json
import os
import time
from types import SimpleNamespace

import pytest

from chipbench import harness, opcount_attention_qk_v, opcount_hyper_connections
from chipbench.selftest import manifest_rules

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "xing4-ps.trimmed-signflip-1k"
APPENDED = {
    "moe_device_ms.train", "attention_device_ms.train", "recompute_device_ms.train",
    "round_rows_peak_mb.train", "held_expert_tokens_min.train", "expert_rounds_max.train",
    "attention_kernel_calls.train", "mla_latent_device_ms.train", "model_unlabelled_pct.train",
    "mlp_device_ms.train", "head_device_ms.train", "norm_device_ms.train",
    "moe_shared_device_ms.train", "stream_rows_device_ms.train", "segment_max_device_ms.train",
}
NEW = ["hc_device_ms.train", "hc_hbm_roofline_pct.train", "attention_qk_v_mxu_pct.train",
       "hc_stream_writes.train"]


def _real_manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        return json.load(fh)


def _toy_manifest():
    toy = harness.load_json(HERE, "toy_streamed_xing4", "manifest.json")
    toy["end_to_end"] = [{k: v for k, v in m.items() if k != "workloads"}
                         for m in _real_manifest()["end_to_end"]]
    readers = sorted(f[:-3] for f in os.listdir(os.path.join(ROOT, "chipbench", "layer_metrics"))
                     if f.endswith(".train.py"))
    toy["per_layer"] = [
        {"name": name, "unit": "-", "better": "lower", "source": "program_counter",
         "layer": "selftest", "moves": "train_samples_per_s", "workloads": ["toy.streamed_xing4"]}
        for name in readers
    ]
    return toy


def _run(*, trace, control=None, seed=2**31 + 41):
    import jax

    lines = []
    line = harness.run_cell(
        _toy_manifest(), "toy.streamed_xing4", seed=seed, seconds=0.5, trace=trace,
        devices=jax.devices()[:1], t_process=time.perf_counter(), control=control,
        emit=lines.append,
    )
    assert json.loads(lines[-1]) == json.loads(json.dumps(line, default=float))
    return line


def test_streamed_xing4_toy_cell_prints_the_contracts_line():
    line = _run(trace=False)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 4
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}


def test_streamed_xing4_toy_cell_traced_feeds_the_accepted_readers_and_its_own():
    line = _run(trace=True)
    assert line["correct"] is True
    got = set(line["metrics"])
    manifest = _real_manifest()
    unlisted = {m["name"] for m in manifest["per_layer"] if "workloads" not in m}
    # (thirteen when the cell was added; `matrix_build_device_ms.train` got a list at PR 44)
    assert 12 <= len(unlisted) <= 13 and unlisted - {"agg_roofline.train"} <= got
    # no peak on a CPU: the shares of one are None here, as in the other rehearsals
    on_a_cpu = {"hc_hbm_roofline_pct.train", "attention_qk_v_mxu_pct.train"}
    assert (APPENDED | set(NEW)) - on_a_cpu <= got
    # no state-space or delta-rule mixer, no MTP module; one chip
    for absent in ("ssm_scan_device_ms.train", "delta_rule_device_ms.train",
                   "mtp_device_ms.train", "robust_overhead_pct.train",
                   "collective_device_ms.train", *on_a_cpu):
        assert absent not in got
    values = {k: v["value"] for k, v in line["metrics"].items()}
    assert values["fwdbwd_device_ms.train"] > values["recompute_device_ms.train"] > 0
    for name in ("hc_device_ms.train", "attention_device_ms.train", "mla_latent_device_ms.train",
                 "moe_device_ms.train", "moe_shared_device_ms.train", "mlp_device_ms.train",
                 "head_device_ms.train", "norm_device_ms.train"):
        assert values[name] > 0, name
    assert values["attention_kernel_calls.train"] == 0  # the lax.map route
    # three blocks, two sublayers each: at least a write-back in the first forward
    # and one in the second, and a cotangent in the backward
    assert values["hc_stream_writes.train"] >= 3 * 2 * 2
    assert values["expert_rounds_max.train"] >= 1 and values["matrix_copies.train"] == 0
    assert values["model_unlabelled_pct.train"] < 5


@pytest.mark.parametrize("control", ["grad_bf16", "model_bf16"])
def test_each_lower_precision_control_of_the_streamed_xing4_cell_comes_out_not_correct(control):
    assert _run(trace=False, control=control)["correct"] is False


def test_the_xing4_configuration_holds_every_published_width_and_states_its_cut():
    cfg = harness.load_json(ROOT, "chipbench", "configs", "xing4-29b-ep8-ps.json")
    published = dict(
        hidden_size=3584, intermediate_size=9216, moe_intermediate_size=1024,
        num_attention_heads=32, num_key_value_heads=32, q_lora_rank=768, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, rope_theta=10000,
        rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
                      "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                      "type": "yarn"},
        hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-06, mhc_h_res_clamp_min=-30,
        mhc_h_res_clamp_max=30, num_experts_per_tok=4, n_shared_experts=1,
        routed_scaling_factor=2, scoring_func="sigmoid", topk_method="noaux_tc", n_group=1,
        topk_group=1, norm_topk_prob=True, rms_norm_eps=1e-06, model_type="xing4_0",
        max_position_embeddings=262144, hidden_act="silu", tie_word_embeddings=False,
        attention_bias=False, moe_layer_freq=1, ep_size=1)
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
                              "vocab_size", "num_nextn_predict_layers"]
    assert cfg["published"] == {"num_hidden_layers": 40, "first_k_dense_replace": 2,
                                "n_routed_experts": 64, "vocab_size": 131072,
                                "num_nextn_predict_layers": 1}
    assert [cfg[k] for k in cfg["reduced"]] == [5, 1, 8, 16384, 0]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert set(cfg["controls"]) == {"grad_bf16", "model_bf16"}
    assert cfg["stated_dtype"] == "float32" and "EIGHT" in cfg["deployment"]
    assert {"stream_entry_and_exit", "sinkhorn_order_and_eps", "hc_norm_scale",
            "hc_seeded_alpha_and_b", "mscale_all_dim", "rotary_pairing",
            "router_correction_bias", "router_precision", "weights", "data",
            "n_nodes_and_n_byzantine", "learning_rate", "expert_rounds"} <= set(cfg["assumed"])
    arch = cfg["reference"]["arch"]
    assert arch["held_experts"] == [0, 8]
    for key, value in arch.items():  # the reference's sizes are the file's
        if key in cfg:
            assert cfg[key] == value, key
    # the program's factory at its defaults IS the file
    import jax

    from byzpy_tpu.models import xing4

    default = xing4.Xing4Config()
    for key in published:
        if hasattr(default, key) and key != "rope_scaling":
            assert getattr(default, key) == cfg[key], key
    scaling = default.rope_scaling
    assert {k: getattr(scaling, k) for k in cfg["rope_scaling"] if k != "type"} == {
        k: v for k, v in cfg["rope_scaling"].items() if k != "type"}
    assert default.n_routed_experts == cfg["published"]["n_routed_experts"]
    assert default.held_experts == (0, cfg["n_routed_experts"])
    assert (default.num_hidden_layers, default.first_k_dense_replace, default.vocab_size,
            default.num_nextn_predict_layers) == (5, 1, 16384, 0)
    shapes = jax.eval_shape(lambda: xing4.xing4_29b_ep8(0).params)
    assert sum(leaf.size for leaf in jax.tree_util.tree_leaves(shapes)) == cfg["n_parameters"]


def test_the_xing4_cell_is_in_the_manifest_and_the_manifest_meets_the_rules():
    manifest = _real_manifest()
    assert manifest_rules.check(manifest, ROOT) == []
    cell = harness.find_cell(manifest, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "xing4-29b-ep8-ps", "trimmed-signflip-tok1k-lm", 1)
    # (by name, not by place: the next configuration is appended behind this one)
    assert cell["config"] in [c["name"] for c in manifest["configs"]]
    mine = {m["name"] for m in harness.metrics_of_cell(manifest, CELL, "per_layer")}
    assert APPENDED | set(NEW) <= mine
    for absent in ("ssm_scan_device_ms.train", "delta_rule_device_ms.train",
                   "mtp_device_ms.train", "attention_kernel_mxu_pct.train"):
        assert absent not in mine
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:  # a reader that may return None has a list from the start;
        # a later cell with the same mechanism is appended to it (LFM2: the products' widths)
        assert by_name[name]["workloads"][0] == CELL
    assert by_name["hc_device_ms.train"]["workloads"] == [CELL]
    assert set(NEW) <= set(by_name)
    assert by_name["hc_hbm_roofline_pct.train"]["layer"] == "kernels"
    # the mix is the accepted one at a quarter of the tokens, and nothing else
    mix = harness.load_json(ROOT, "chipbench", "traffic", "trimmed-signflip-tok1k-lm.json")
    accepted = harness.load_json(ROOT, "chipbench", "traffic", "trimmed-signflip-tok4k-lm.json")
    assert mix["tokens_per_worker"] == 1024 and accepted["tokens_per_worker"] == 4096
    drop = ("tokens_per_worker", "what")
    assert {k: v for k, v in mix.items() if k not in drop} == {
        k: v for k, v in accepted.items() if k not in drop}
    assert cell["chips"] == 1  # nothing of it exists only across chips


def test_the_two_opcounts_are_the_hand_counts():
    # the streams of one worker at the cell's sizes: 58.7 MB; six of them a
    # sublayer, ten sublayers, six honest workers: 21.1 GB, 25.8 ms at 819 GB/s
    assert opcount_hyper_connections.streams_bytes(1024, 4, 3584) == 1024 * 4 * 3584 * 4
    config = {"n_nodes": 8, "n_byzantine": 2, "num_hidden_layers": 5, "hc_mult": 4,
              "hidden_size": 3584}
    least = opcount_hyper_connections.least_bytes_per_step(config, {"tokens_per_worker": 1024})
    assert least == 6 * 10 * 6 * 58720256 and least / 819e9 == pytest.approx(0.02581, rel=1e-3)
    # one head, two positions: the causal half is 2 entries; forward 192 + 128,
    # dq 192 + 128 + 192, dk/dv 192 + 128 + 128 + 192 multiply-adds an entry
    flops = {kind: opcount_attention_qk_v.kernel_flops(kind, 1, 192, 128, 2)
             for kind in opcount_attention_qk_v.PRODUCTS}
    assert flops == {"causal_attention_fwd": 2 * 2 * 320, "causal_attention_dq": 2 * 2 * 512,
                     "causal_attention_dkv": 2 * 2 * 640}


def test_the_roofline_reader_is_the_floor_over_the_labels_time(monkeypatch):
    reader = harness.load_by_path(
        os.path.join(ROOT, "chipbench", "layer_metrics", "hc_hbm_roofline_pct.train.py"),
        "hc_hbm_roofline_pct.train")
    cfg = harness.load_json(ROOT, "chipbench", "configs", "xing4-29b-ep8-ps.json")
    peaks = harness.load_json(ROOT, "chipbench", "peaks.json")
    ctx = SimpleNamespace(peaks=peaks, config=cfg, mix={"tokens_per_worker": 1024},
                          devices=[SimpleNamespace(device_kind="TPU v5 lite")])
    monkeypatch.setattr(reader.scope_paths, "path_ms", lambda ctx_, *labels: 80.0)
    assert reader.read(ctx) == pytest.approx(100 * 21139292160 / 819e9 / 0.080)  # 32 %
    # a program that never enters the labels, a configuration without
    # hyper-connections, a device without a peak: nothing to read, and no error
    monkeypatch.setattr(reader.scope_paths, "path_ms", lambda ctx_, *labels: None)
    assert reader.read(ctx) is None
    other = harness.load_json(ROOT, "chipbench", "configs", "glm47-flash-ep8-ps.json")
    assert reader.read(SimpleNamespace(**{**vars(ctx), "config": other})) is None
    assert reader.read(SimpleNamespace(**{**vars(ctx), "devices": [
        SimpleNamespace(device_kind="cpu")]})) is None
