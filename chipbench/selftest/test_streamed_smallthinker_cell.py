"""CPU rehearsal of the streamed language-model driver (``drivers/
train_round_streamed_lm.py``) with the SmallThinker reference, at toy size
(96 tokens a worker against a window of 12), in a toy directory of its own:
the contract's last line, the traced run's readers (the accepted ones
unchanged, the three new ones), both lower-precision controls and three
model faults not correct,
the real configuration's file against the catalog's widths, the manifest
against the rules, the windowed calls' operation count against a brute-force
count of ``(i, j)`` pairs, and each new reader on a synthetic compiled text
(``None`` where the names are absent)."""

from __future__ import annotations

import json
import os
import time
from types import SimpleNamespace

import pytest

from chipbench import harness, opcount_attention, opcount_window_attention
from chipbench.selftest import manifest_rules

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "smallthinker-ps.trimmed-signflip-8k"
CONFIG = "smallthinker-21b-ep8-ps"
APPENDED = {
    "attention_kernel_mxu_pct.train", "attention_device_ms.train", "attention_kernel_calls.train",
    "moe_device_ms.train", "recompute_device_ms.train", "held_expert_tokens_min.train",
    "expert_rounds_max.train", "model_unlabelled_pct.train", "head_device_ms.train",
    "norm_device_ms.train", "stream_rows_device_ms.train", "segment_max_device_ms.train",
    "attack_in_kernel_segments.train",
}
NEW = ["window_attention_kernel_ms.train", "window_attention_mxu_pct.train",
       "window_attention_kernel_calls.train"]


def _reader(name):
    return harness.load_by_path(
        os.path.join(ROOT, "chipbench", "layer_metrics", name + ".py"), name)


def _real_manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        return json.load(fh)


def _toy_manifest():
    toy = harness.load_json(HERE, "toy_streamed_smallthinker", "manifest.json")
    toy["end_to_end"] = [{k: v for k, v in m.items() if k != "workloads"}
                         for m in _real_manifest()["end_to_end"]]
    readers = sorted(f[:-3] for f in os.listdir(os.path.join(ROOT, "chipbench", "layer_metrics"))
                     if f.endswith(".train.py"))
    toy["per_layer"] = [
        {"name": name, "unit": "-", "better": "lower", "source": "program_counter",
         "layer": "selftest", "moves": "train_samples_per_s",
         "workloads": ["toy.streamed_smallthinker"]}
        for name in readers
    ]
    return toy


def _run(*, trace, control=None, seed=2**31 + 49):
    import jax

    lines = []
    line = harness.run_cell(
        _toy_manifest(), "toy.streamed_smallthinker", seed=seed, seconds=0.5, trace=trace,
        devices=jax.devices()[:1], t_process=time.perf_counter(), control=control,
        emit=lines.append,
    )
    assert json.loads(lines[-1]) == json.loads(json.dumps(line, default=float))
    return line


def test_streamed_smallthinker_toy_cell_prints_the_contracts_line():
    line = _run(trace=False)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 4
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}


def test_streamed_smallthinker_toy_cell_traced_feeds_the_accepted_readers_and_its_own():
    line = _run(trace=True)
    assert line["correct"] is True
    got = set(line["metrics"])
    manifest = _real_manifest()
    unlisted = {m["name"] for m in manifest["per_layer"] if "workloads" not in m}
    assert len(unlisted) == 12 and unlisted - {"agg_roofline.train"} <= got
    # no peak on a CPU and no kernel on the lax.map route: the shares of a peak and
    # the kernels' time are None here, as in the other rehearsals
    on_a_chip = {"attention_kernel_mxu_pct.train", "window_attention_mxu_pct.train",
                 "window_attention_kernel_ms.train"}
    assert (APPENDED | set(NEW)) - on_a_chip <= got
    for absent in ("ssm_scan_device_ms.train", "delta_rule_device_ms.train",
                   "mla_latent_device_ms.train", "moe_shared_device_ms.train",
                   "hc_device_ms.train", "mtp_device_ms.train", "mlp_device_ms.train",
                   "short_conv_device_ms.train", "tied_table_kept_mb.train", *on_a_chip):
        assert absent not in got
    values = {k: v["value"] for k, v in line["metrics"].items()}
    assert values["fwdbwd_device_ms.train"] > values["recompute_device_ms.train"] > 0
    for name in ("attention_device_ms.train", "moe_device_ms.train", "head_device_ms.train",
                 "norm_device_ms.train", "stream_rows_device_ms.train"):
        assert values[name] > 0, name
    # the lax.map route: no kernel of either name, and the counter says 0, not None
    assert values["attention_kernel_calls.train"] == 0
    assert values["window_attention_kernel_calls.train"] == 0
    assert values["expert_rounds_max.train"] >= 1 and values["matrix_copies.train"] == 0
    assert values["model_unlabelled_pct.train"] < 5


@pytest.mark.parametrize("control", ["grad_bf16", "model_bf16"])
def test_each_lower_precision_control_of_the_smallthinker_cell_comes_out_not_correct(control):
    assert _run(trace=False, control=control)["correct"] is False


FAULTS = ["fault_window_ignored", "fault_window_off_by_one", "fault_global_blocks_turned"]


@pytest.mark.parametrize("fault", FAULTS)
def test_each_model_fault_of_the_smallthinker_cell_comes_out_not_correct_at_toy_size(fault):
    """The model faults a change of the factory's sizes can make, through the
    harness: the program faulty, the reference as the file has it. (At the
    cell's own size the comparison sees a window ignored and a global block
    turned, and NOT a window one key too long: PERF.md section 2.)"""
    assert _run(trace=False, control=fault)["correct"] is False


def test_the_configuration_holds_every_published_width_and_states_its_cut():
    cfg = harness.load_json(ROOT, "chipbench", "configs", CONFIG + ".json")
    period = [0, 1, 1, 1]
    published = dict(
        head_dim=128, hidden_size=2560, max_position_embeddings=16384,
        model_name="smallthinker_21b_instruct", moe_ffn_hidden_size=768,
        moe_num_active_primary_experts=6, moe_primary_router_apply_softmax=True,
        norm_topk_prob=True, num_attention_heads=28, num_key_value_heads=4, rms_norm_eps=1e-06,
        rope_layout=period * 13, rope_scaling=None, rope_theta=1500000,
        sliding_window_layout=period * 13, sliding_window_size=4096, tie_word_embeddings=False)
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["num_hidden_layers", "moe_num_primary_experts", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 52, "moe_num_primary_experts": 64,
                                "vocab_size": 151936}
    assert [cfg[k] for k in cfg["reduced"]] == [8, 8, 18992]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["layers_held"] == list(range(8))
    assert set(cfg["controls"]) == {"grad_bf16", "model_bf16", *FAULTS}
    toy = harness.load_json(HERE, "toy_streamed_smallthinker", "configs", "smallthinker-toy.json")
    assert set(toy["controls"]) == set(cfg["controls"])
    assert cfg["stated_dtype"] == "float32" and "EIGHT" in cfg["deployment"]
    assert {"router_input", "window", "router_scores", "no_qk_norm_no_bias", "rotary_pairing",
            "router_precision", "secondary_experts", "weights", "data",
            "n_nodes_and_n_byzantine", "learning_rate", "expert_rounds"} <= set(cfg["assumed"])
    arch = cfg["reference"]["arch"]
    assert arch["held_experts"] == [0, 8] and arch["layers_held"] == cfg["layers_held"]
    for key, value in arch.items():  # the reference's sizes are the file's
        if key in cfg:
            assert cfg[key] == value, key
    # what the accepted attention_kernel_mxu_pct.train reads
    assert (arch["num_attention_heads"], arch["num_key_value_heads"], arch["head_dim"]) == (
        28, 4, 128)
    # the program's factory at its defaults IS the file
    import jax

    from byzpy_tpu.models import smallthinker

    default = smallthinker.SmallThinkerConfig()
    for key in published:
        if hasattr(default, key) and not key.endswith("_layout"):
            assert getattr(default, key) == cfg[key], key
    for key in ("sliding_window_layout", "rope_layout"):
        assert list(getattr(default, key)) == [cfg[key][at] for at in cfg["layers_held"]]
    assert default.moe_num_primary_experts == cfg["published"]["moe_num_primary_experts"]
    assert default.held_experts == (0, cfg["moe_num_primary_experts"])
    assert (default.num_hidden_layers, default.vocab_size) == (8, 18992)
    bundle = jax.eval_shape(lambda: smallthinker.smallthinker_21b_ep8(0).params)
    assert sum(leaf.size for leaf in jax.tree_util.tree_leaves(bundle)) == cfg[
        "n_parameters"] == 643_852_800
    mix = harness.load_json(ROOT, "chipbench", "traffic", "trimmed-signflip-tok8k-lm.json")
    old = harness.load_json(ROOT, "chipbench", "traffic", "trimmed-signflip-tok4k-lm.json")
    assert {k for k in mix if mix[k] != old[k]} == {"tokens_per_worker", "traced_steps", "what"}
    assert (mix["tokens_per_worker"], mix["traced_steps"]) == (8192, 2)


def test_the_cell_is_in_the_manifest_and_the_manifest_meets_the_rules():
    manifest = _real_manifest()
    assert manifest_rules.check(manifest, ROOT) == []
    cell = harness.find_cell(manifest, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "trimmed-signflip-tok8k-lm", 1)  # nothing of it exists only across chips
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "moe_num_primary_experts", "vocab_size"]
    mine = {m["name"] for m in harness.metrics_of_cell(manifest, CELL, "per_layer")}
    assert APPENDED | set(NEW) <= mine
    for absent in ("matrix_build_device_ms.train", "mlp_device_ms.train",
                   "moe_shared_device_ms.train", "attention_qk_v_mxu_pct.train",
                   "ssm_scan_device_ms.train", "short_conv_device_ms.train"):
        assert absent not in mine
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:  # a reader that may return None has a list from the start
        assert CELL in by_name[name]["workloads"]
        assert by_name[name]["moves"] == "train_samples_per_s"
    assert by_name["window_attention_mxu_pct.train"]["layer"] == "kernels"
    assert by_name["window_attention_kernel_calls.train"]["source"] == "program_counter"
    # by name, not by place: the next cell and the next metric are appended after these
    names = [m["name"] for m in manifest["per_layer"]]
    assert [name for name in names if name in NEW] == NEW


@pytest.mark.parametrize("tokens, window", [
    (1, 1), (5, 1), (5, 3), (7, 7), (7, 8), (40, 12), (96, 12), (300, 130), (130, 300)])
def test_the_windowed_count_is_the_brute_force_count_of_pairs(tokens, window):
    brute = sum(1 for i in range(tokens) for j in range(tokens) if 0 <= i - j < window)
    assert opcount_window_attention.window_entries(tokens, window) == brute


def test_the_windowed_kernels_operations_at_the_published_sizes():
    entries = opcount_window_attention.window_entries(8192, 4096)
    assert entries == 4096 * 4097 // 2 + 4096 * 4096 == 25_167_872
    assert opcount_window_attention.KINDS == {
        "window_attention_fwd": "causal_attention_fwd", "window_attention_dq":
        "causal_attention_dq", "window_attention_dkv": "causal_attention_dkv"}
    for kind, products in (("fwd", 2), ("dq", 3), ("dkv", 4)):
        flops = opcount_window_attention.kernel_flops(
            "window_attention_" + kind, 28, 128, 8192, 4096)
        assert flops == 28 * entries * 2 * 128 * products
        # three quarters of the causal call's count, to the diagonal's half-entries
        causal = opcount_attention.kernel_flops("causal_attention_" + kind, 28, 128, 8192)
        assert flops / causal == pytest.approx(0.75, abs=2e-4)
    # a window no shorter than the sequence counts the causal half (with its diagonal)
    assert opcount_window_attention.window_entries(4096, 4096) == 4096 * 4097 // 2


def _text(names):
    calls = "\n".join(
        f'  %{name}.{i} = f32[8192,3584]{{1,0}} custom-call(%a), '
        f'custom_call_target="tpu_custom_call", metadata={{op_name="jit(train_step)/'
        f'segment.seg02_window/while/body/round.fwdbwd/model.attention/{name}"}}'
        for i, name in enumerate(names))
    return ("HloModule jit_train_step\n\nENTRY %main (a: f32[8192,3584]) -> f32[8192,3584] {\n"
            "  %a = f32[8192,3584]{1,0} parameter(0)\n" + calls +
            "\n  ROOT %out = f32[8192,3584]{1,0} add(%a, %a), "
            'metadata={op_name="jit(train_step)/round.update/add"}\n}\n')


def _ctx(text, kernel_ms, **over):
    cfg = harness.load_json(ROOT, "chipbench", "configs", CONFIG + ".json")
    ctx = SimpleNamespace(
        peaks=harness.load_json(ROOT, "chipbench", "peaks.json"), config=cfg,
        mix={"tokens_per_worker": 8192}, devices=[SimpleNamespace(device_kind="TPU v5 lite")],
        outcome={"compiled_text": text,
                 "measured": {"scope_join": None if kernel_ms is None else
                              {"kernel_ms": kernel_ms}}})
    for key, value in over.items():
        setattr(ctx, key, value)
    return ctx


def test_the_three_new_readers_on_a_synthetic_text():
    windowed = ["window_attention_fwd"] * 12 + ["window_attention_dq"] * 6 + [
        "window_attention_dkv"] * 6
    causal = ["causal_attention_fwd"] * 4 + ["causal_attention_dq"] * 2 + [
        "causal_attention_dkv"] * 2
    times = {"window_attention_fwd": 300.0, "window_attention_dq": 250.0,
             "window_attention_dkv": 350.0, "causal_attention_fwd": 111.0}
    calls, ms, share = (_reader(name) for name in (
        "window_attention_kernel_calls.train", "window_attention_kernel_ms.train",
        "window_attention_mxu_pct.train"))
    ctx = _ctx(_text(windowed + causal), times)
    assert calls.read(ctx) == 24
    assert ms.read(ctx) == 900.0  # the causal calls' time is not in it
    entries = 25_167_872
    flops = 6 * 28 * entries * 2 * 128 * (12 * 2 + 6 * 3 + 6 * 4)
    assert share.read(ctx) == pytest.approx(100 * flops / 0.9 / 197e12)
    assert 0 < share.read(ctx) < 100
    # a step whose windowed blocks fell to another route: the counter says 0, the
    # two that need the kernels' time say nothing
    elsewhere = _ctx(_text(causal), {"causal_attention_fwd": 111.0})
    assert calls.read(elsewhere) == 0
    assert ms.read(elsewhere) is None and share.read(elsewhere) is None
    # a configuration with no window, a program without scopes to join (the parent
    # of the PR that added the kernels), a run with no compiled text, a device
    # with no peak
    other = harness.load_json(ROOT, "chipbench", "configs", "lfm2-24b-ep8-ps.json")
    for reader in (calls, share):
        assert reader.read(_ctx(_text(causal), times, config=other)) is None
    for reader in (ms, share):
        assert reader.read(_ctx(_text(windowed), None)) is None
    for reader in (calls, ms, share):
        assert reader.read(_ctx("", times)) is None
    assert share.read(_ctx(_text(windowed), times,
                           devices=[SimpleNamespace(device_kind="cpu")])) is None
