"""`attack_in_kernel_segments.train` counts the segments whose byzantine rows
the sort kernel forms itself, on a short compiled text written after the
lines of a streamed step compiled for a described v5e (PR 43;
`backend_config` cut off): 0 where every aggregate is the kernel without a
prologue, k with k calls of the kernel that has one, nothing for a step that
declares no segments."""

import os

import pytest

from chipbench import harness

READER = harness.load_by_path(
    os.path.join(harness.HERE, "layer_metrics", "attack_in_kernel_segments.train.py"),
    "attack_in_kernel_segments.train")

LOOP = ('%fusion.7 = f32[6,128,128]{2,1,0:T(8,128)} fusion(%while.3), kind=kLoop, '
        'calls=%fused_computation.7, metadata={op_name="jit(train_step)/segment.s1_mid/while/body/'
        'round.segment_bwd/round.fwdbwd/stream.rows/dynamic_update_slice"}')
WRITTEN = ('%sorted_reduce_stream.{i} = f32[1,128,128]{{2,1,0:T(8,128)}} custom-call(%bitcast.{i}), '
           'custom_call_target="tpu_custom_call", operand_layout_constraints='
           '{{f32[1,8,128,128]{{3,2,1,0}}}}, metadata={{op_name="jit(train_step)/segment.s{i}/'
           'round.aggregate/jit(_sorted_reduce_stream_call)/sorted_reduce_stream/pallas_call"}}')
FORMED = ('%sorted_reduce_stream_attacked.{i} = f32[1,128,128]{{2,1,0:T(8,128)}} '
          'custom-call(%bitcast.{i}), custom_call_target="tpu_custom_call", '
          'operand_layout_constraints={{f32[1,6,128,128]{{3,2,1,0}}}}, metadata={{op_name='
          '"jit(train_step)/segment.s{i}/round.aggregate/jit(_sorted_reduce_stream_attacked_call)/'
          'sorted_reduce_stream_attacked/pallas_call"}}')
OTHER = ('%rows_to_tokens.3 = f32[4096,2048]{1,0:T(8,128)} custom-call(%a, %b), '
         'custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/segment.s1_mid/'
         'while/body/round.segment_bwd/round.fwdbwd/model.moe_experts/rows_to_tokens/pallas_call"}')


def _ctx(text):
    ctx = harness.Ctx(manifest={}, cell={"name": "c"}, config={}, mix={}, seed=0, seconds=0,
                      trace=True, devices=[], t_process=0.0)
    ctx.outcome = {"compiled_text": text}
    return ctx


@pytest.mark.parametrize("formed, written", [(0, 4), (4, 0), (3, 1), (11, 0)])
def test_the_segments_whose_rows_the_kernel_forms_are_counted_by_its_name(formed, written):
    lines = [LOOP, OTHER]
    lines += [WRITTEN.format(i=i) for i in range(written)]
    lines += [FORMED.format(i=written + i) for i in range(formed)]
    assert READER.read(_ctx("\n".join(lines))) == formed


def test_the_name_counts_only_on_a_mosaic_call():
    # the jitted call's name in another op's path, and a host-side custom call
    path_only = LOOP.replace("stream.rows", "jit(_sorted_reduce_stream_attacked_call)")
    host = FORMED.format(i=0).replace("tpu_custom_call", "Sharding")
    assert READER.read(_ctx("\n".join([LOOP, path_only, host]))) == 0
    assert READER.read(_ctx("\n".join([LOOP, path_only, host, FORMED.format(i=1)]))) == 1


@pytest.mark.parametrize("text", ["", None, FORMED.format(i=0) + "\n" + OTHER.replace(
    "round.segment_bwd/", "")])
def test_a_step_without_segments_gives_nothing(text):
    assert READER.read(_ctx(text)) is None
