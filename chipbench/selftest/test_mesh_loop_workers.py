"""`mesh_loop_workers.train` reads how many of its own workers a chip of the
mesh round runs one after another, on short compiled texts written after the
lines of the ResNet-18 round compiled for four described v5e chips (PR 47;
shapes cut, `backend_config` cut off): the loop's count from its condition
where the backend writes none on the line, from `known_trip_count` where it
does (the CPU rehearsal), 0 for the partitioned `vmap` program, nothing for a
step on one chip."""

import os

import pytest

from chipbench import harness

READER = harness.load_by_path(
    os.path.join(harness.HERE, "layer_metrics", "mesh_loop_workers.train.py"),
    "mesh_loop_workers.train")

HEADER = "HloModule jit_train_step, is_scheduled=true, num_partitions={k}\n"
ONE_CHIP = "HloModule jit_train_step, is_scheduled=true\n"

# one worker's convolution, in a fusion the loop's body calls
FUSED = """
%fused_computation.7 (param_0.1: bf16[512,32,32,64], param_1.1: bf16[3,3,64,64]) -> f32[512,32,32,64] {
  %param_0.1 = bf16[512,32,32,64]{0,3,2,1} parameter(0)
  %param_1.1 = bf16[3,3,64,64]{3,2,1,0} parameter(1)
  ROOT %convolution.5 = f32[512,32,32,64]{0,3,2,1} convolution(%param_0.1, %param_1.1), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f, metadata={op_name="jit(train_step)/round.fwdbwd/shard_map/while/body/closed_call/jvp(ResNet)/Conv_0/conv_general_dilated"}
}
"""
BODY = """
%wide.region_0.203_spmd.sunk.clone (wide.param.4: (s32[], f32[2], f32[2,11173962])) -> (s32[], f32[2], f32[2,11173962]) {
  %wide.param.4 = (s32[], f32[2], f32[2,11173962]) parameter(0)
  %fusion.7 = f32[512,32,32,64]{0,3,2,1} fusion(%a, %b), kind=kOutput, calls=%fused_computation.7, metadata={op_name="jit(train_step)/round.fwdbwd/shard_map/while/body/closed_call/jvp(ResNet)/Conv_0/conv_general_dilated"}
  ROOT %tuple.1 = (s32[], f32[2], f32[2,11173962]) tuple(%i, %losses, %rows)
}
"""
CONDITION = """
%wide.region_203.204_spmd.clone (wide.param.5: (s32[], f32[2], f32[2,11173962])) -> pred[] {{
  %constant.3379 = s32[]{{:T(128)}} constant({trips}), metadata={{op_name="jit(train_step)/round.fwdbwd/shard_map"}}
  %wide.param.5 = (s32[], f32[2], f32[2,11173962]) parameter(0)
  %get-tuple-element.3570 = s32[]{{:T(128)}} get-tuple-element(%wide.param.5), index=0
  ROOT %lt.143 = pred[]{{:T(512)}} compare(%get-tuple-element.3570, %constant.3379), direction=LT, metadata={{op_name="jit(train_step)/round.fwdbwd/shard_map/while/cond/lt"}}
}}
"""
# the sort's own loops, and a loop the compiler made of a row's copy inside the body
OTHER_LOOPS = """
%cond (param.2: (u32[], f32[11173962])) -> pred[] {
  %constant.2356 = u32[] constant(1)
  %param.2 = (u32[], f32[11173962]) parameter(0)
  %get-tuple-element.2035 = u32[] get-tuple-element(%param.2), index=0
  ROOT %compare.37 = pred[] compare(%get-tuple-element.2035, %constant.2356), direction=LT
}

%body (param.3: (u32[], f32[11173962])) -> (u32[], f32[11173962]) {
  %param.3 = (u32[], f32[11173962]) parameter(0)
  ROOT %tuple.2 = (u32[], f32[11173962]) tuple(%n, %row)
}
"""
ENTRY = """
ENTRY %main.226 (xs: f32[2,512,32,32,3]) -> f32[2793491] {{
  %while.15 = (u32[], f32[11173962]) while(%tuple.575), condition=%cond, body=%body
  %while.14 = (s32[], f32[2], f32[2,11173962]) while(%tuple.9), condition=%wide.region_203.204_spmd.clone, body=%wide.region_0.203_spmd.sunk.clone, metadata={{op_name="jit(train_step)/round.fwdbwd/shard_map/while"}}{known}
  %all-to-all.1 = f32[8,2793491] all-to-all(%rows), metadata={{op_name="jit(train_step)/round.transpose/sharding_constraint"}}
  ROOT %sort.1 = f32[2793491] fusion(%all-to-all.1), kind=kLoop, calls=%fused_sort, metadata={{op_name="jit(train_step)/round.aggregate/sort"}}
}}
"""
# the parent's program: both of a chip's workers in one convolution, no loop
VMAPPED = """
ENTRY %main.9 (xs: f32[2,512,32,32,3]) -> f32[2793491] {
  %convolution-base-dilated.75 = f32[2,3,3,64,64]{4,3,0,2,1} convolution(%fusion.94, %fusion.4), window={size=32x32x2 pad=1_1x1_1x0_0 lhs_dilate=1x1x2}, dim_labels=2f01b_2i01o->201bf, metadata={op_name="jit(train_step)/round.fwdbwd/vmap()/transpose"}
  %while.3 = (s32[], f32[8,2793491]) while(%tuple.4), condition=%cond, body=%body, metadata={op_name="jit(train_step)/round.aggregate/while"}
  ROOT %all-to-all.1 = f32[8,2793491] all-to-all(%rows), metadata={op_name="jit(train_step)/round.transpose/sharding_constraint"}
}
"""


def _ctx(text):
    ctx = harness.Ctx(manifest={}, cell={"name": "c"}, config={}, mix={}, seed=0, seconds=0,
                      trace=True, devices=[], t_process=0.0)
    ctx.outcome = {"compiled_text": text}
    return ctx


def _looped(trips, *, chips=4, known=""):
    return (HEADER.format(k=chips) + FUSED + BODY + CONDITION.format(trips=trips) + OTHER_LOOPS
            + ENTRY.format(known=known))


@pytest.mark.parametrize("trips", [2, 4, 16])
def test_the_loops_count_is_the_constant_its_condition_compares_with(trips):
    assert READER.read(_ctx(_looped(trips))) == trips


def test_a_count_the_backend_wrote_on_the_line_is_taken_first():
    known = ', backend_config={"known_trip_count":{"n":"2"},"known_init_step":{"init":"0","step":"1"}}'
    assert READER.read(_ctx(_looped(7, known=known))) == 2


def test_the_partitioned_vmap_program_reads_zero():
    assert READER.read(_ctx(HEADER.format(k=4) + OTHER_LOOPS + VMAPPED)) == 0


def test_a_loop_under_the_scope_that_holds_no_contraction_does_not_count():
    # the row's copy loop alone, carrying the scope, and no model loop
    text = _looped(2).replace("calls=%fused_computation.7", "calls=%fused_computation.8")
    assert READER.read(_ctx(text)) == 0
    # a contraction of another scope in the body does not make it the model's loop
    assert READER.read(_ctx(_looped(2).replace(
        "round.fwdbwd/shard_map/while/body/closed_call/jvp(ResNet)/Conv_0",
        "round.aggregate/gram"))) == 0


def test_a_loop_whose_count_cannot_be_read_gives_nothing():
    text = _looped(2).replace("direction=LT", "direction=NE")
    assert READER.read(_ctx(text)) is None


@pytest.mark.parametrize("text", ["", None, ONE_CHIP + FUSED + BODY + CONDITION.format(trips=2)
                                  + ENTRY.format(known=""),
                                  _looped(2, chips=1)])
def test_a_step_that_was_not_partitioned_gives_nothing(text):
    assert READER.read(_ctx(text)) is None
