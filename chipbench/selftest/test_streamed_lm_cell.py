"""CPU rehearsal of the second streamed driver (``drivers/
train_round_streamed_lm.py``) with the GLM-4.7-Flash reference, at toy
size, in a toy directory of its own: the contract's last line, the traced
run's readers (the accepted ones unchanged, this configuration's with
something to read), both lower-precision controls not correct, the real
configuration's file against the catalog's widths, and the manifest
against the rules."""

from __future__ import annotations

import json
import os
import time

import pytest

from chipbench import harness, opcount_attention
from chipbench.selftest import manifest_rules

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "glm47-flash-ps.trimmed-signflip-4k"
READ_HERE = {
    "moe_device_ms.train", "attention_device_ms.train", "recompute_device_ms.train",
    "round_rows_peak_mb.train", "held_expert_tokens_min.train", "expert_rounds_max.train",
    "mla_latent_device_ms.train", "mtp_device_ms.train",
}


def _real_manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        return json.load(fh)


def _toy_manifest():
    toy = harness.load_json(HERE, "toy_streamed_lm", "manifest.json")
    toy["end_to_end"] = [{k: v for k, v in m.items() if k != "workloads"}
                         for m in _real_manifest()["end_to_end"]]
    readers = sorted(f[:-3] for f in os.listdir(os.path.join(ROOT, "chipbench", "layer_metrics"))
                     if f.endswith(".train.py"))
    toy["per_layer"] = [
        {"name": name, "unit": "-", "better": "lower", "source": "program_counter",
         "layer": "selftest", "moves": "train_samples_per_s", "workloads": ["toy.streamed_lm"]}
        for name in readers
    ]
    return toy


def _run(*, trace, control=None, seed=2**31 + 29):
    import jax

    lines = []
    line = harness.run_cell(
        _toy_manifest(), "toy.streamed_lm", seed=seed, seconds=0.5, trace=trace,
        devices=jax.devices()[:1], t_process=time.perf_counter(), control=control,
        emit=lines.append,
    )
    assert json.loads(lines[-1]) == json.loads(json.dumps(line, default=float))
    return line


def test_streamed_lm_toy_cell_prints_the_contracts_line():
    line = _run(trace=False)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 4
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}


def test_streamed_lm_toy_cell_traced_feeds_the_accepted_readers_and_its_own():
    line = _run(trace=True)
    assert line["correct"] is True
    got = set(line["metrics"])
    accepted = {"step_device_ms.train", "fwdbwd_device_ms.train", "aggregate_device_ms.train",
                "update_device_ms.train", "matrix_build_device_ms.train", "kernel_route.train",
                "device_idle_pct.train", "peak_hbm_gb.train", "scope_unattributed_pct.train",
                "matrix_copies.train", "sublane_matrix_writes.train"}
    assert accepted <= got and READ_HERE <= got
    # no Mamba-2 layer; no plain block; one chip; no kernel and no peak on a CPU
    for absent in ("ssm_scan_device_ms.train", "robust_overhead_pct.train",
                   "collective_device_ms.train", "attention_kernel_mxu_pct.train"):
        assert absent not in got
    values = {k: v["value"] for k, v in line["metrics"].items()}
    assert values["fwdbwd_device_ms.train"] > values["recompute_device_ms.train"] > 0
    assert values["attention_device_ms.train"] > values["mla_latent_device_ms.train"] > 0
    assert values["mtp_device_ms.train"] > 0 and values["moe_device_ms.train"] > 0
    assert values["attention_kernel_calls.train"] == 0  # the lax.map route
    assert values["expert_rounds_max.train"] >= 1 and values["matrix_copies.train"] == 0


@pytest.mark.parametrize("control", ["grad_bf16", "model_bf16"])
def test_each_lower_precision_control_of_the_streamed_lm_cell_comes_out_not_correct(control):
    assert _run(trace=False, control=control)["correct"] is False


def test_the_glm_configuration_holds_every_published_width_and_states_its_cut():
    cfg = harness.load_json(ROOT, "chipbench", "configs", "glm47-flash-ep8-ps.json")
    published = dict(
        hidden_size=2048, num_attention_heads=20, num_key_value_heads=20, q_lora_rank=768,
        kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
        moe_intermediate_size=1536, intermediate_size=10240, num_experts_per_tok=4,
        routed_scaling_factor=1.8, n_shared_experts=1, num_nextn_predict_layers=1,
        first_k_dense_replace=1, rope_theta=1000000, rms_norm_eps=1e-05)
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 47, "n_routed_experts": 64,
                                "vocab_size": 154880}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (5, 8, 19360)
    assert set(cfg["controls"]) == {"grad_bf16", "model_bf16"}
    assert cfg["stated_dtype"] == "float32" and "EIGHT" in cfg["deployment"]
    assert {"rotary_pairing", "eh_proj_order", "mtp_loss_weight", "router_correction_bias",
            "router_precision", "weights", "data", "n_nodes_and_n_byzantine", "learning_rate",
            "expert_rounds"} <= set(cfg["assumed"])
    # the program's factory at its defaults IS the file
    import jax

    from byzpy_tpu.models import glm4_moe_lite

    default = glm4_moe_lite.Glm4MoeLiteConfig()
    for key in published:
        if hasattr(default, key):
            assert getattr(default, key) == cfg[key], key
    shapes = jax.eval_shape(lambda: glm4_moe_lite.glm47_flash_ep8(0).params)
    assert sum(leaf.size for leaf in jax.tree_util.tree_leaves(shapes)) == cfg["n_parameters"]


def test_the_new_cell_is_in_the_manifest_and_the_manifest_meets_the_rules():
    manifest = _real_manifest()
    assert manifest_rules.check(manifest, ROOT) == []
    cell = harness.find_cell(manifest, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "glm47-flash-ep8-ps", "trimmed-signflip-tok4k-lm", 1)
    mine = {m["name"] for m in harness.metrics_of_cell(manifest, CELL, "per_layer")}
    assert {"mla_latent_device_ms.train", "mtp_device_ms.train",
            "attention_kernel_mxu_pct.train", "attention_kernel_calls.train"} <= mine
    assert "ssm_scan_device_ms.train" not in mine
    mix = harness.load_json(ROOT, "chipbench", "traffic", cell["traffic"] + ".json")
    accepted = harness.load_json(ROOT, "chipbench", "traffic", "trimmed-signflip-tok4k.json")
    assert {**mix, "driver": accepted["driver"]} == accepted


def test_attention_opcount_is_the_causal_half():
    one = opcount_attention.causal_product_flops(20, 256, 4096)
    assert one == 20 * 4096 * 4096 / 2 * 256 * 2 == pytest.approx(85.9e9, rel=1e-3)
    assert opcount_attention.kernel_flops("causal_attention_dkv", 20, 256, 4096) == 4 * one
