"""CPU rehearsal of the streamed-round driver (``drivers/
train_round_streamed.py``) with the Nemotron-H reference, at toy size, in
a toy directory of its own: the contract's last line, the traced run's
readers (the accepted ones unchanged, the new ones with something to
read), both lower-precision controls not correct, and the readers that
``scope_paths`` serves on a text written by hand."""

from __future__ import annotations

import json
import os
import time

import pytest

from chipbench import harness, scope_paths

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NEW_READERS = {
    "ssm_scan_device_ms.train", "moe_device_ms.train", "attention_device_ms.train",
    "recompute_device_ms.train", "round_rows_peak_mb.train", "held_expert_tokens_min.train",
    "expert_rounds_max.train",
}


def _toy_manifest():
    toy = harness.load_json(HERE, "toy_streamed", "manifest.json")
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        real = json.load(fh)
    toy["end_to_end"] = [{k: v for k, v in m.items() if k != "workloads"}
                         for m in real["end_to_end"]]
    readers = sorted(f[:-3] for f in os.listdir(os.path.join(ROOT, "chipbench", "layer_metrics"))
                     if f.endswith(".train.py"))
    toy["per_layer"] = [
        {"name": name, "unit": "-", "better": "lower", "source": "program_counter",
         "layer": "selftest", "moves": "train_samples_per_s", "workloads": ["toy.streamed"]}
        for name in readers
    ]
    return toy


def _run(*, trace, control=None, seed=2**31 + 17):
    import jax

    lines = []
    line = harness.run_cell(
        _toy_manifest(), "toy.streamed", seed=seed, seconds=0.5, trace=trace,
        devices=jax.devices()[:1], t_process=time.perf_counter(), control=control,
        emit=lines.append,
    )
    assert json.loads(lines[-1]) == json.loads(json.dumps(line, default=float))
    return line


def test_streamed_toy_cell_prints_the_contracts_line():
    line = _run(trace=False)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 4
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}


def test_streamed_toy_cell_traced_feeds_the_accepted_readers_and_the_new_ones():
    line = _run(trace=True)
    assert line["correct"] is True
    got = set(line["metrics"])
    accepted = {"step_device_ms.train", "fwdbwd_device_ms.train", "aggregate_device_ms.train",
                "update_device_ms.train", "matrix_build_device_ms.train", "kernel_route.train",
                "device_idle_pct.train", "peak_hbm_gb.train", "scope_unattributed_pct.train",
                "matrix_copies.train", "sublane_matrix_writes.train"}
    assert accepted <= got and NEW_READERS <= got
    assert "robust_overhead_pct.train" not in got  # no plain block: the reader's None
    assert "collective_device_ms.train" not in got  # one chip
    values = {k: v["value"] for k, v in line["metrics"].items()}
    # the model's passes lie in round.fwdbwd, the innermost round.* of them all
    assert values["fwdbwd_device_ms.train"] > values["recompute_device_ms.train"] > 0
    assert values["ssm_scan_device_ms.train"] > 0 and values["moe_device_ms.train"] > 0
    assert values["attention_device_ms.train"] > 0
    assert values["held_expert_tokens_min.train"] >= 0
    assert values["expert_rounds_max.train"] >= 1
    assert values["matrix_copies.train"] == 0
    # n rows of the largest segment (toy: no kernel serves, so no padding), never of d
    import jax

    from chipbench.harness import resolve
    cfg = harness.load_json(HERE, "toy_streamed", "configs", "nemotron-toy.json")
    kwargs = dict(cfg["model"]["kwargs"], held_experts=tuple(cfg["model"]["kwargs"]["held_experts"]))
    shapes = jax.eval_shape(lambda: resolve(cfg["model"]["factory"])(0, **kwargs).params)
    largest = max(sum(leaf.size for leaf in jax.tree_util.tree_leaves(sub))
                  for sub in shapes.values())
    assert values["round_rows_peak_mb.train"] == pytest.approx(4 * 8 * largest / 1e6)


@pytest.mark.parametrize("control", ["grad_bf16", "model_bf16"])
def test_each_lower_precision_control_of_the_streamed_cell_comes_out_not_correct(control):
    assert _run(trace=False, control=control)["correct"] is False


def test_the_real_streamed_configuration_states_its_cut_and_lists_both_controls():
    cfg = harness.load_json(ROOT, "chipbench", "configs", "nemotron3-nano-ep16-ps.json")
    assert set(cfg["controls"]) == {"grad_bf16", "model_bf16"}
    assert cfg["stated_dtype"] == "float32"
    assert set(cfg["reduced"]) >= {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert cfg["published"]["n_routed_experts"] == 128 and cfg["n_routed_experts"] == 8
    assert cfg["hidden_size"] == 2688 and cfg["moe_intermediate_size"] == 1856


TEXT = """HloModule jit_train_step

%fused_a (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %m = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(train_step)/round.segment_recompute/round.fwdbwd/model.ssm_scan/mul"}
  ROOT %a = f32[4]{0} add(%m, %p), metadata={op_name="jit(train_step)/round.segment_recompute/round.fwdbwd/add"}
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %f = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_a
  %g = f32[4]{0} all-gather-start(%f), metadata={op_name="jit(train_step)/round.update/round.param_gather/x"}
  ROOT %b = f32[4]{0} negate(%g), metadata={op_name="jit(train_step)/round.segment_bwd/round.fwdbwd/transpose(jvp(model.ssm_scan))/neg"}
}
"""


def test_scope_paths_shares_a_fusion_out_and_reads_opcodes():
    found = scope_paths.read_text(TEXT)
    assert found["f"]["opcode"] == "fusion" and len(found["f"]["paths"]) == 2
    assert found["g"]["opcode"] == "all-gather-start"
    table = {"instructions": found, "owned": [[{"f": 2e6, "g": 1e6, "b": 4e6}]]}

    class Ctx:
        outcome = {"measured": {"scope_paths": table, "scope_paths_text": found}}

    assert scope_paths.path_ms(Ctx, "model.ssm_scan") == pytest.approx(1.0 + 4.0)
    assert scope_paths.path_ms(Ctx, "round.segment_recompute",
                               without=("round.segment_bwd",)) == pytest.approx(2.0)
    assert scope_paths.path_ms(Ctx, "model.attention") is None
    assert scope_paths.opcode_ms(Ctx, "all-gather", "all-to-all") == pytest.approx(1.0)
    assert scope_paths.opcode_ms(Ctx, "reduce-scatter") is None
