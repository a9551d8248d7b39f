"""The join of a trace with the compiled text (``chipbench/scope_join.py``):
hand-written texts and event lists, and a pair recorded on the chip whose
reduction must give what is written beside it. No number here is a device
metric of the benchmark."""

from __future__ import annotations

import os

import pytest

from chipbench import harness, scope_join as sj
from chipbench.trace_reduce import Event

HERE = os.path.dirname(os.path.abspath(__file__))

# A compiled text in the form the TPU's compiler prints, cut to what the
# join reads: two fused computations, the entry, one loop body.
TEXT = '''HloModule jit_train_step, is_scheduled=true, entry_computation_layout={(f32[8,64]{1,0})->f32[64]{0}}

%fused_computation (param_0.1: f32[8,64]) -> f32[8,64] {
  %param_0.1 = f32[8,64]{1,0:T(8,128)} parameter(0)
  %constant.3 = f32[]{:T(128)} constant(2)
  %mul.1 = f32[8,64]{1,0:T(8,128)} broadcast(%constant.3), dimensions={}, metadata={op_name="jit(train_step)/round.fwdbwd/vmap(transpose(jvp()))/mul" stack_frame_id=3}
  ROOT %mul.0 = f32[8,64]{1,0:T(8,128)} multiply(%param_0.1, %mul.1), metadata={op_name="jit(train_step)/round.fwdbwd/vmap(transpose(jvp()))/mul" stack_frame_id=3}
}

%fused_computation.1 (param_0.4: f32[1,1,128]) -> f32[1,1,64] {
  %param_0.4 = f32[1,1,128]{2,1,0:T(1,128)S(1)} parameter(0), metadata={op_name="jit(train_step)/round.aggregate/ignored_on_a_parameter"}
  %slice.27 = f32[1,1,64]{2,1,0:T(1,128)} slice(%param_0.4), slice={[0:1], [0:1], [0:64]}, metadata={op_name="jit(train_step)/round.aggregate/jit(_sorted_reduce_stream_call)/slice" stack_frame_id=8}
  %constant.32 = f32[]{:T(128)} constant(1)
  ROOT %add.5 = f32[1,1,64]{2,1,0:T(1,128)} add(%slice.27, %constant.32), metadata={op_name="jit(train_step)/round.update/add" stack_frame_id=2}
}

%fused_computation.2 (param_0.9: f32[64]) -> f32[64] {
  %param_0.9 = f32[64]{0:T(128)} parameter(0)
  ROOT %copy.9 = f32[64]{0:T(128)} copy(%param_0.9)
}

%body.7 (arg.1: (s32[], f32[64])) -> (s32[], f32[64]) {
  %arg.1 = (s32[]{:T(128)}, f32[64]{0:T(128)}) parameter(0)
  %step.2 = f32[64]{0:T(128)} negate(%arg.1), metadata={op_name="jit(train_step)/round.update/round.param_gather/while/body/neg"}
  ROOT %tuple.3 = (s32[]{:T(128)}, f32[64]{0:T(128)}) tuple(%arg.1, %step.2)
}

ENTRY %main.3 (x.1: f32[8,64]) -> f32[64] {
  %x.1 = f32[8,64]{1,0:T(8,128)} parameter(0), sharding={replicated}, metadata={op_name="x"}
  %multiply_fusion = f32[8,64]{1,0:T(8,128)} fusion(%x.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(train_step)/round.fwdbwd/vmap(transpose(jvp()))/mul"}
  %copy.1 = f32[8,64]{0,1:T(8,128)} copy(%x.1)
  %dynamic-update-slice.5 = f32[8,64]{1,0:T(8,128)} dynamic-update-slice(%multiply_fusion, %copy.1, %constant.0, %constant.0)
  %pad.2 = f32[1,8,128]{2,1,0:T(8,128)S(1)} pad(%dynamic-update-slice.5, %constant.0), padding=0_0x0_0x0_64, metadata={op_name="jit(train_step)/round.aggregate/jit(_sorted_reduce_stream_call)/scatter" stack_frame_id=6}
  %sorted_reduce_stream.1 = f32[1,1,128]{2,1,0:T(1,128)S(1)} custom-call(%pad.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/round.aggregate/jit(_sorted_reduce_stream_call)/sorted_reduce_stream/pallas_call" stack_frame_id=7}, backend_config={"custom_call_config":{"body":"selection_mean_streamTUzvUgFNTElS","needs_layout_passes":true}}
  %other.1 = f32[1,1,128]{2,1,0:T(1,128)S(1)} custom-call(%pad.2), custom_call_target="Sharding", metadata={op_name="jit(train_step)/round.aggregate/sharding_constraint"}
  %slice_add_fusion = f32[1,1,64]{2,1,0:T(1,128)} fusion(%sorted_reduce_stream.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/round.update/add" stack_frame_id=2}
  %copy_fusion = f32[64]{0:T(128)} fusion(%slice_add_fusion), kind=kLoop, calls=%fused_computation.2
  %while.4 = (s32[]{:T(128)}, f32[64]{0:T(128)}) while(%copy_fusion), condition=%cond.6, body=%body.7, metadata={op_name="jit(train_step)/round.update/while"}
  ROOT %bitcast.1 = f32[64]{0:T(1024)} bitcast(%while.4), metadata={op_name="jit(train_step)/round.update/add" stack_frame_id=2}
}
'''
KERNELS = ("selection_mean_stream", "sorted_reduce_stream", "clip_selection_mean_stream")


def test_the_innermost_scope_is_found_anywhere_in_the_path():
    assert sj.scope_of("jit(train_step)/round.fwdbwd/vmap(transpose(jvp()))/add_any") == "round.fwdbwd"
    assert sj.scope_of("jit(train_step)/round.update/round.param_gather/all_gather") == "round.param_gather"
    assert sj.scope_of("jit(f)/transpose(jvp(round.fwdbwd))/mul") == "round.fwdbwd"
    assert sj.scope_of("jit(train_step)/serving.opt_update/add") is None
    assert sj.scope_of("ys") is None


def test_labels_by_fused_computation_mixed_unscoped_and_kernel_by_name():
    labels = sj.read_labels(TEXT, KERNELS)
    assert labels.label["multiply_fusion"] == "round.fwdbwd"  # every instruction agrees
    assert labels.label["slice_add_fusion"] == sj.MIXED  # its own op_name says round.update
    assert labels.straddles["slice_add_fusion"] == ("round.aggregate", "round.update")
    assert labels.label["copy_fusion"] == sj.UNSCOPED  # no metadata, and a mixed operand
    assert labels.label["x.1"] == sj.UNSCOPED  # a parameter, whatever it carries
    assert labels.label["copy.1"] == sj.UNSCOPED  # a parameter's copy: no operand has a scope
    # compiler-made, no metadata: the one scope its labelled operands share
    assert labels.label["dynamic-update-slice.5"] == "round.fwdbwd"
    assert labels.inherited == {"dynamic-update-slice.5", "tuple.3"}  # the loop body's tuple too
    assert labels.label["pad.2"] == "round.aggregate"
    assert labels.label["while.4"] == "round.update"
    assert labels.label["step.2"] == "round.param_gather"  # an op of the loop's body
    # the kernel is found by the program's name on its line, not in the
    # serialized body and not on another custom call
    assert labels.kernel == {"sorted_reduce_stream.1": "sorted_reduce_stream"}
    assert labels.scopes == ("round.aggregate", "round.fwdbwd", "round.param_gather", "round.update")
    assert sj.read_labels(TEXT).kernel == {}


def test_an_op_owns_the_instants_no_inner_op_runs_so_a_scope_is_a_union():
    # a while of 100 ns with two body ops of 30 ns each: 100 ns, not 160
    ops = [Event("while.4", 0, 100), Event("step.2", 10, 40), Event("step.2", 50, 80),
           Event("pad.2", 100, 130)]
    owned = sj.owned_ns(ops)
    assert owned == {"while.4": pytest.approx(40), "step.2": pytest.approx(60),
                     "pad.2": pytest.approx(30)}
    assert sum(owned.values()) == pytest.approx(130)  # the busy time, exactly
    # an inner op that starts with its outer op still owns its time
    assert sj.owned_ns([Event("inner", 0, 5), Event("outer", 0, 20)]) == {
        "inner": pytest.approx(5), "outer": pytest.approx(15)}


def _joined():
    def step(run_id, at):
        ops = [Event("multiply_fusion", at, at + 600), Event("dynamic-update-slice.5", at + 600, at + 700),
               Event("pad.2", at + 700, at + 750),
               Event("sorted_reduce_stream.1", at + 750, at + 850),
               Event("slice_add_fusion", at + 850, at + 870), Event("copy_fusion", at + 870, at + 880),
               Event("while.4", at + 880, at + 980), Event("step.2", at + 890, at + 920),
               Event("step.2", at + 930, at + 960), Event("not_in_the_text", at + 980, at + 1000)]
        return sj.Run(run_id, at, at + 1000, ops)

    runs = [step("7", 10_000), step("8", 11_050), step("9", 14_000)]
    other = sj.Run("20", 16_000, 16_500)
    dev = sj.DeviceRuns("/device:TPU:0", 0, runs, runs + [other])
    enqueued = {(0, "7"): 10_900.0, (0, "8"): 12_350.0, (0, "9"): 14_100.0, (0, "20"): 15_000.0}
    spans = [Event("chipbench.window", 9_000, 17_000), Event("chipbench.read_loss", 13_300, 15_200),
             Event("chipbench.agg_alone", 17_000, 19_000)]
    return sj.Joined(devices=[dev], enqueued=enqueued, spans=spans)


def test_reduction_per_scope_kernel_unattributed_and_gaps():
    out = sj.reduce_runs(_joined(), sj.read_labels(TEXT, KERNELS))
    ms = out["label_ms"]
    assert ms["round.fwdbwd"] == pytest.approx(700e-6)
    assert out["inherited_ms"] == {"round.fwdbwd": pytest.approx(100e-6)}
    assert ms["round.aggregate"] == pytest.approx(150e-6)  # the pad and the kernel
    assert ms["round.update"] == pytest.approx(40e-6)  # the while without its body's ops
    assert ms["round.param_gather"] == pytest.approx(60e-6)
    assert ms[sj.MIXED] == pytest.approx(20e-6)
    assert ms[sj.UNSCOPED] == pytest.approx(30e-6)  # no metadata; not in the text
    assert sum(ms.values()) == pytest.approx(out["busy_ms"]) == pytest.approx(1000e-6)
    assert out["kernel_ms"] == {"sorted_reduce_stream": pytest.approx(100e-6)}
    assert out["unattributed_pct"] == pytest.approx(5.0)
    assert out["host_gap_us_per_step"] == pytest.approx((50 + 1950) / 2 * 1e-3)
    assert out["executions"] == [3]
    named = {h[0]: h[1:] for h in out["heaviest_unattributed"]}
    assert named["slice_add_fusion"] == [
        sj.MIXED, ["round.aggregate", "round.update"], pytest.approx(20e-6)]
    assert set(named) == {"slice_add_fusion", "copy_fusion", "not_in_the_text"}


def test_skew_from_run_id_pairs_and_gaps_between_programs_under_shifted_spans():
    joined = _joined()
    # run 8 started on the device 1300 ns before the host enqueued it
    assert sj.clock_skew_ns(joined) == pytest.approx(1300.0)
    gaps = sj.between_program_gaps(joined, 1300.0)
    # 12,050 -> 14,000 is 13,350 -> 15,300 on the host's clock: its middle
    # lies in read_loss; without the shift it would lie before it
    assert gaps[0] == ["read_loss", pytest.approx(1950e-9)]
    assert gaps[1] == ["window", pytest.approx(1000e-9)]
    assert gaps[2] == ["window", pytest.approx(50e-9)]
    assert sj.between_program_gaps(joined, 0.0)[0][0] == "window"
    assert sj.clock_skew_ns(sj.Joined(devices=joined.devices, enqueued={}, spans=[])) is None


def test_a_program_that_declares_no_scopes_gives_the_readers_nothing(monkeypatch):
    from byzpy_tpu.observability import catalog

    ctx = harness.Ctx(manifest={}, cell={"name": "c"}, config={}, mix={}, seed=0, seconds=0,
                      trace=True, devices=[], t_process=0.0)
    ctx.outcome = {"compiled_text": TEXT, "measured": {"step_module": "train_step"}}
    monkeypatch.delattr(catalog, "SCOPES")  # the parent of the PR that added them
    assert sj.of(ctx) is None and sj.scope_ms(ctx, "round.fwdbwd") is None
    for name in os.listdir(os.path.join(harness.HERE, "layer_metrics")):
        if "device_ms" in name or "scope_" in name or "host_gap" in name:
            reader = harness.load_by_path(os.path.join(harness.HERE, "layer_metrics", name), name[:-3])
            if name != "step_device_ms.train.py":
                assert reader.read(ctx) is None


def test_reduction_of_the_recorded_scoped_chip_trace_gives_what_is_written_beside_it():
    want = harness.load_json(HERE, "recorded", "toy_step_scoped.expected.json")
    with open(os.path.join(HERE, "recorded", "toy_step_scoped.hlo.txt"), encoding="utf-8") as fh:
        labels = sj.read_labels(fh.read(), want["kernels"])
    joined = sj.read_runs(os.path.join(HERE, "recorded", "toy_step_scoped.xplane.pb"), want["module"])
    assert [d.name for d in joined.devices] == want["devices"]
    assert list(labels.scopes) == want["scopes_in_text"]
    assert labels.kernel == want["kernel_instructions"]
    out = sj.reduce_runs(joined, labels)
    assert out["executions"] == want["executions"]
    assert out["label_ms"] == {k: pytest.approx(v, rel=1e-9) for k, v in want["label_ms"].items()}
    assert out["kernel_ms"] == {k: pytest.approx(v, rel=1e-9) for k, v in want["kernel_ms"].items()}
    assert out["busy_ms"] == pytest.approx(want["busy_ms"], rel=1e-9)
    assert sum(out["label_ms"].values()) == pytest.approx(out["busy_ms"], rel=1e-3)
    assert out["unattributed_pct"] == pytest.approx(want["unattributed_pct"], rel=1e-9)
    assert out["host_gap_us_per_step"] == pytest.approx(want["host_gap_us_per_step"], rel=1e-9)
    assert sj.clock_skew_ns(joined) == pytest.approx(want["clock_skew_ns"], rel=1e-9)
    # every traced op of the step is an instruction of the recorded text
    traced = {op.name for dev in joined.devices for run in dev.runs for op in run.ops}
    assert traced and traced <= set(labels.label)
