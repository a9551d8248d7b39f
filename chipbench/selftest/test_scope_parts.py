"""The parts, segments and leading ops of ``round.fwdbwd``
(``chipbench/scope_parts.py``) on a compiled text and an ``owned`` table
written by hand: no chip, no trace. No number here is a device metric of the
benchmark."""

from __future__ import annotations

import json
import os

import pytest

from chipbench import harness, scope_parts as sp, scope_paths

HERE = os.path.dirname(os.path.abspath(__file__))
BWD = "jit(train_step)/segment.seg01_m/while/body/closed_call/round.segment_bwd/round.fwdbwd/"
RE = "jit(train_step)/segment.seg01_m/while/body/closed_call/round.segment_recompute/round.fwdbwd/"
FWD = "jit(train_step)/round.segment_fwd/round.fwdbwd/while/body/closed_call/"

# A compiled text in the form the TPU's compiler prints, cut to what the
# readers read: the tables a stack_frame_id points into, one fused
# computation of three instructions under two labels, one loop body, the entry.
TEXT = f'''HloModule jit_train_step, is_scheduled=true

FileNames
1 "/x/byzpy_tpu/models/layers.py"
2 "/x/byzpy_tpu/parallel/ps.py"
FunctionNames
1 "rms_norm"
2 "train_step"
FileLocations
1 {{file_name_id=1 function_name_id=1 line=19 end_line=19 column=8 end_column=40}}
2 {{file_name_id=2 function_name_id=2 line=372 end_line=374 column=4 end_column=9}}
StackFrames
1 {{file_location_id=1 parent_frame_id=1}}
2 {{file_location_id=2 parent_frame_id=2}}

%fused_computation.1 (param_0.1: f32[64,32]) -> f32[64,32] {{
  %param_0.1 = f32[64,32]{{1,0:T(8,128)}} parameter(0), metadata={{op_name="{RE}jvp(model.norm)/ignored_on_a_parameter"}}
  %mul.1 = f32[64,32]{{1,0:T(8,128)}} multiply(%param_0.1, %param_0.1), metadata={{op_name="{RE}jvp(model.norm)/mul" stack_frame_id=1}}
  %rsqrt.1 = f32[64,32]{{1,0:T(8,128)}} rsqrt(%mul.1), metadata={{op_name="{RE}jvp(model.norm)/rsqrt" stack_frame_id=1}}
  ROOT %dot.1 = f32[64,32]{{1,0:T(8,128)}} multiply(%rsqrt.1, %param_0.1), metadata={{op_name="{RE}jvp(vmap(model.ssm_proj))/dot_general" stack_frame_id=2}}
}}

%body.7 (arg.1: (s32[], f32[64,32])) -> (s32[], f32[64,32]) {{
  %arg.1 = (s32[]{{:T(128)}}, f32[64,32]{{1,0:T(8,128)}}) parameter(0)
  %norm_proj_fusion = f32[64,32]{{1,0:T(8,128)}} fusion(%arg.1), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{RE}jvp(vmap(model.ssm_proj))/dot_general" stack_frame_id=2}}
  %head_dot.3 = f32[64,16]{{1,0:T(8,128)}} convolution(%norm_proj_fusion, %arg.1), metadata={{op_name="{BWD}transpose(jvp(model.head))/dot_general" source_file="/x/byzpy_tpu/models/nemotron_h.py" source_line=251}}
  %mtp_attn.2 = f32[64,32]{{1,0:T(8,128)}} convolution(%head_dot.3, %arg.1), metadata={{op_name="{RE}jvp(model.mtp)/vmap(model.attention)/dot_general" stack_frame_id=2}}
  %mtp_add.4 = f32[64,32]{{1,0:T(8,128)}} add(%mtp_attn.2, %arg.1), metadata={{op_name="{RE}jvp(model.mtp)/add" stack_frame_id=2}}
  %latent_norm.5 = f32[64,32]{{1,0:T(8,128)}} multiply(%mtp_add.4, %arg.1), metadata={{op_name="{RE}jvp(vmap(model.attention))/model.mla_latent/model.norm/mul" stack_frame_id=1}}
  %rows_dus.6 = f32[8,64,128]{{2,1,0:T(8,128)}} dynamic-update-slice(%arg.1, %latent_norm.5), metadata={{op_name="{BWD}stream.rows/dynamic_update_slice" stack_frame_id=2}}
  %copy.8 = f32[64,32]{{0,1:T(8,128)}} copy(%latent_norm.5)
  ROOT %tuple.3 = (s32[]{{:T(128)}}, f32[64,32]{{1,0:T(8,128)}}) tuple(%arg.1, %copy.8)
}}

ENTRY %main.3 (x.1: f32[64,32]) -> f32[64,32] {{
  %x.1 = f32[64,32]{{1,0:T(8,128)}} parameter(0), metadata={{op_name="x"}}
  %embed_gather.1 = f32[64,32]{{1,0:T(8,128)}} gather(%x.1), metadata={{op_name="{FWD}segment.seg00_embed/model.embed/gather" stack_frame_id=2}}
  %while.4 = (s32[]{{:T(128)}}, f32[64,32]{{1,0:T(8,128)}}) while(%embed_gather.1), condition=%cond.6, body=%body.7, metadata={{op_name="jit(train_step)/segment.seg01_m/while" stack_frame_id=2}}
  %agg.1 = f32[64,32]{{1,0:T(8,128)}} custom-call(%while.4), custom_call_target="tpu_custom_call", metadata={{op_name="jit(train_step)/segment.seg01_m/round.aggregate/sorted_reduce_stream/pallas_call" stack_frame_id=2}}
  %upd.1 = f32[64,32]{{1,0:T(8,128)}} subtract(%agg.1, %x.1), metadata={{op_name="jit(train_step)/segment.seg01_m/round.update/sub" stack_frame_id=2}}
  ROOT %norm.9 = f32[]{{:T(128)}} sqrt(%upd.1), metadata={{op_name="jit(train_step)/round.update/sqrt" stack_frame_id=2}}
}}
'''

# nanoseconds each instruction owns in four executions of the step; the
# second and third have the median round.fwdbwd time between them
RUN = {"norm_proj_fusion": 3000.0, "head_dot.3": 5000.0, "mtp_attn.2": 2000.0, "mtp_add.4": 100.0,
       "latent_norm.5": 400.0, "rows_dus.6": 700.0, "copy.8": 50.0, "embed_gather.1": 300.0,
       "while.4": 10.0, "agg.1": 900.0, "upd.1": 600.0, "norm.9": 20.0, "not_in_the_text": 5.0}
RUNS = [{**RUN, "head_dot.3": 9000.0}, {**RUN, "head_dot.3": 5200.0}, RUN,
        {**RUN, "head_dot.3": 1000.0, "agg.1": 100.0}]


class _Ctx:
    """What the readers touch of a ``harness.Ctx``."""

    def __init__(self, text, runs=RUNS):
        instructions = scope_paths.read_text(text)
        self.said = []
        self.outcome = {"compiled_text": text, "measured": {
            "step_module": "train_step", "scope_paths_text": instructions,
            "scope_paths": {"instructions": instructions, "owned": [list(runs)]},
            "scope_parts_executions": {"head_dot.3": 6, "norm_proj_fusion": 6}}}

    def say(self, **facts):
        self.said.append(json.loads(json.dumps(facts, default=float)))


@pytest.mark.parametrize("path, part", [
    (BWD + "transpose(jvp(model.head))/dot_general", "model.head"),
    (RE + "jvp(model.mtp)/vmap(model.attention)/dot_general", "model.attention"),
    (RE + "jvp(model.mtp)/add", sp.UNLABELLED),
    (RE + "jvp(vmap(model.attention))/model.mla_latent/model.norm/mul", "model.norm"),
    (RE + "jvp(model.head)/model.mtp/reduce_sum", "model.head"),
    (BWD + "stream.rows/dynamic_update_slice", "stream.rows"),
    (RE + "jvp(model.moe_experts)/model.moe_shared/dot_general", "model.moe_shared"),
    ("jit(train_step)/round.update/sqrt", sp.UNLABELLED),
])
def test_an_op_belongs_to_the_last_label_of_its_path_the_envelope_left_out(path, part):
    assert sp.part_of(path) == part


def test_a_path_is_placed_by_part_pass_segment_and_stage():
    assert sp.place_of(BWD + "transpose(jvp(model.head))/dot_general") == (
        "model.head", "round.segment_bwd", "seg01_m", "round.fwdbwd")
    # a backward op of a custom_vjp holds the second forward's label too: it is backward
    assert sp.place_of(BWD + "transpose(round.segment_recompute)/round.fwdbwd/jvp(model.moe_experts)/lt"
                       )[1] == "round.segment_bwd"
    assert sp.place_of(FWD + "segment.seg00_embed/model.embed/gather") == (
        "model.embed", "round.segment_fwd", "seg00_embed", "round.fwdbwd")
    assert sp.place_of("jit(train_step)/round.update/sqrt") == (
        sp.UNLABELLED, None, sp.NO_SEGMENT, "round.update")


def test_parts_add_up_to_round_fwdbwd_exactly_and_a_fusion_is_shared_two_to_one():
    ctx = _Ctx(TEXT)
    parts = sp.parts(ctx)
    # what path_ms finds under round.fwdbwd, and the copy the compiler made of
    # the norm's result (no op_name: path_ms leaves it out, 50 ns)
    assert sum(parts.values()) == pytest.approx(
        scope_paths.path_ms(ctx, "round.fwdbwd") + 50e-6, rel=1e-12)
    # the median step: the mean of the two middle executions (head_dot 5200 and 5000 ns)
    assert parts["model.head"] == pytest.approx(5100e-6)
    # 2/3 of the fusion, the latent's norm, and that norm's copy
    assert parts["model.norm"] == pytest.approx((2000 + 400 + 50) * 1e-6)
    assert parts["model.ssm_proj"] == pytest.approx(1000e-6)  # 1/3 of the fusion
    assert parts["model.attention"] == pytest.approx(2000e-6)  # not model.mtp's
    assert parts[sp.UNLABELLED] == pytest.approx(100e-6)  # model.mtp alone is no part
    assert parts["stream.rows"] == pytest.approx(700e-6)
    assert parts["model.embed"] == pytest.approx(300e-6)
    assert set(parts) == {"model.head", "model.norm", "model.ssm_proj", "model.attention",
                          sp.UNLABELLED, "stream.rows", "model.embed"}
    # an odd number of executions: the middle one
    odd = _Ctx(TEXT, RUNS[:3])
    assert sp.parts(odd)["model.head"] == pytest.approx(5200e-6)
    assert sum(sp.parts(odd).values()) == pytest.approx(
        scope_paths.path_ms(odd, "round.fwdbwd") + 50e-6, rel=1e-12)


def test_what_the_compiler_put_around_a_loop_takes_no_share_from_a_primitive():
    loop = FWD[:-1]  # ".../while/body/closed_call": a constant's, a broadcast's op_name
    norm, rows = RE + "jvp(model.norm)/mul", BWD + "stream.rows/dynamic_update_slice"
    update = "jit(train_step)/segment.seg01_m/round.update/sub"
    shares = sp.shares_of({"fused": [norm, loop, loop], "alone": [loop, loop],
                           "two_stages": [norm, loop, rows, update], "nothing": []})
    assert shares["fused"] == {sp.place_of(norm): pytest.approx(1.0)}
    assert shares["alone"] == {sp.place_of(loop): pytest.approx(1.0)}  # nothing else to take it
    assert shares["two_stages"] == {place: pytest.approx(1 / 3) for place in (
        sp.place_of(norm), sp.place_of(rows), sp.place_of(update))}
    assert "nothing" not in shares


def test_a_fusion_that_holds_a_product_is_the_products():
    # the norm's ops fused onto the product they feed: ten small instructions and one product
    product = TEXT.replace(
        'ROOT %dot.1 = f32[64,32]{1,0:T(8,128)} multiply(%rsqrt.1, %param_0.1)',
        'ROOT %dot.1 = f32[64,32]{1,0:T(8,128)} convolution(%rsqrt.1, %param_0.1)')
    assert product != TEXT
    paths = sp.paths_of(sp.read_details(product))
    assert paths["norm_proj_fusion"] == [RE + "jvp(vmap(model.ssm_proj))/dot_general"]
    ctx = _Ctx(product)
    assert sp.parts(ctx)["model.ssm_proj"] == pytest.approx(3000e-6)
    assert sp.parts(ctx)["model.norm"] == pytest.approx(450e-6)  # the latent's, and its copy
    # without a product in it, a fusion is shared over its instructions: TEXT's own
    assert sp.parts(_Ctx(TEXT))["model.ssm_proj"] == pytest.approx(1000e-6)


def test_what_the_compiler_made_without_a_name_goes_with_the_neighbour_it_was_made_for():
    details = sp.read_details(TEXT)
    assert details["copy.8"]["opcode"] == "copy" and details["copy.8"]["operands"] == ["latent_norm.5"]
    paths = sp.paths_of(details)
    # a fusion stands for its fused instructions, a parameter for nothing: scope_paths' reading
    assert paths == {name: ins["paths"] for name, ins in scope_paths.read_text(TEXT).items()}
    shares = sp.shares_of(paths)
    assert "copy.8" not in shares and "tuple.3" not in shares
    through = sp.through_neighbours(shares, details)
    # its user (the loop's tuple) has no place: its operand's, as scope_join inherits
    assert through["copy.8"] == "latent_norm.5" and shares["copy.8"] == shares["latent_norm.5"]
    assert through["tuple.3"] == "latent_norm.5"  # through the copy, to where that took them
    # a weight's cast, a prefetch: nothing feeds them that has a place, what uses them has
    # (what through_neighbours reads of the details: opcode and operands)
    cast = {"w": {"opcode": "get-tuple-element", "operands": ["arg"]},
            "start": {"opcode": "copy-start", "operands": ["w"]},
            "done": {"opcode": "copy-done", "operands": ["start"]},
            "cast": {"opcode": "convert", "operands": ["done"]},
            "dot": {"opcode": "fusion", "operands": ["x", "cast"]},
            "arg": {"opcode": "parameter", "operands": []}}
    places = {"dot": {("model.ssm_proj", "round.segment_bwd", "seg01_m", sp.FWDBWD): 1.0}}
    assert sp.through_neighbours(places, cast) == dict.fromkeys(("cast", "done", "start", "w"), "dot")
    assert places["start"] is places["dot"] and "arg" not in places


def test_segments_hold_every_column_and_add_up_to_what_carries_an_op_name():
    ctx = _Ctx(TEXT)
    found = sp.segments(ctx)
    assert set(found) == {"seg00_embed", "seg01_m", sp.NO_SEGMENT}
    assert found["seg00_embed"]["round.segment_fwd"] == pytest.approx(300e-6)
    turn = found["seg01_m"]
    assert turn["round.segment_recompute"] == pytest.approx((3000 + 2000 + 100 + 400 + 50) * 1e-6)
    assert turn["round.segment_bwd"] == pytest.approx((5100 + 700) * 1e-6)
    assert turn["round.aggregate"] == pytest.approx(900e-6)
    assert turn["round.update"] == pytest.approx(600e-6)
    assert turn["round.build_matrix"] == 0.0
    assert found[sp.NO_SEGMENT]["round.update"] == pytest.approx(20e-6)  # the closing norm
    step = sum(ms for row in found.values() for ms in row.values())
    # all but the while's own 10 ns (no stage) and the op the text lacks
    assert step == pytest.approx((sum(RUN.values()) + 100 - 10 - 5) * 1e-6)


def test_leading_ops_order_by_time_and_carry_opcode_shape_executions_and_source():
    ctx = _Ctx(TEXT)
    norm = sp.leading_ops(ctx, "model.norm")
    assert [op["name"] for op in norm] == ["norm_proj_fusion", "latent_norm.5", "copy.8"]
    assert norm[2]["through"] == "latent_norm.5" and norm[2]["op_name"] == ""
    assert "through" not in norm[0]
    assert norm[0] == {
        "name": "norm_proj_fusion", "opcode": "fusion", "shape": "f32[64,32]",
        "ms": pytest.approx(2000e-6), "executions": 6, "instructions": 1,
        "op_name": "round.fwdbwd/jvp(vmap(model.ssm_proj))/dot_general",
        "source": "/x/byzpy_tpu/parallel/ps.py:372"}
    assert norm[1]["source"] == "/x/byzpy_tpu/models/layers.py:19"  # through the tables
    head = sp.leading_ops(ctx, "model.head", k=1)
    assert head == [{
        "name": "head_dot.3", "opcode": "convolution", "shape": "f32[64,16]",
        "ms": pytest.approx(5100e-6), "executions": 6, "instructions": 1,
        "op_name": "round.fwdbwd/transpose(jvp(model.head))/dot_general",
        "source": "/x/byzpy_tpu/models/nemotron_h.py:251"}]  # named on the line itself
    assert [op["name"] for op in sp.leading_ops(ctx, sp.UNLABELLED)] == ["mtp_add.4"]
    assert sp.leading_ops(ctx, "model.mlp") == []


def test_the_same_op_of_another_segment_is_one_row():
    # a second block's copy of the head's product: another name, another segment
    twin = TEXT.replace(
        "  %mtp_attn.2 =", "  %head_dot.9 = f32[64,16]{1,0:T(8,128)} convolution(%head_dot.3, %arg.1), "
        "metadata={op_name=\"" + BWD.replace("seg01_m", "seg02_m") + "transpose(jvp(model.head))/dot_general\""
        " source_file=\"/x/byzpy_tpu/models/nemotron_h.py\" source_line=251}\n  %mtp_attn.2 =", 1)
    ctx = _Ctx(twin, [{**RUN, "head_dot.9": 1000.0}])
    ctx.outcome["measured"]["scope_parts_executions"]["head_dot.9"] = 6
    (row,) = sp.leading_ops(ctx, "model.head")
    assert row["name"] == "head_dot.3" and row["instructions"] == 2 and row["executions"] == 12
    assert row["ms"] == pytest.approx(6000e-6)
    assert set(sp.segments(ctx)) >= {"seg01_m", "seg02_m"}


def test_the_first_reader_to_ask_prints_one_line():
    ctx = _Ctx(TEXT)
    sp.part_ms(ctx, "model.head")
    sp.segments(ctx)
    sp.parts(ctx)
    assert len(ctx.said) == 1
    line = ctx.said[0]
    assert sum(line["model_parts_ms"].values()) == pytest.approx(
        scope_paths.path_ms(ctx, "round.fwdbwd") + 50e-6)
    assert line["of_it_through_neighbours_ms"] == {"model.norm": pytest.approx(50e-6)}
    assert line["model_parts_by_pass_ms"]["model.head"] == {
        "round.segment_bwd": pytest.approx(5100e-6)}
    assert set(line["segment_ms"]["seg01_m"]) == set(sp.COLUMNS)
    # every part over 2 % of the step has its leading ops listed
    assert set(line["leading_ops"]) == {"model.head", "model.norm", "model.ssm_proj",
                                        "model.attention", "stream.rows", "model.embed"}


NEW_READERS = ["model_unlabelled_pct", "ssm_proj_device_ms", "ssm_gate_device_ms", "mlp_device_ms",
               "head_device_ms", "norm_device_ms", "moe_shared_device_ms",
               "stream_rows_device_ms", "segment_max_device_ms"]


def _reader(name):
    return harness.load_by_path(
        os.path.join(harness.HERE, "layer_metrics", name + ".train.py"), name + ".train")


def test_the_new_readers_read_the_table():
    ctx = _Ctx(TEXT)
    read = {name: _reader(name).read(ctx) for name in NEW_READERS}
    total = scope_paths.path_ms(ctx, "round.fwdbwd") + 50e-6
    assert read["model_unlabelled_pct"] == pytest.approx(100.0 * 100e-6 / total)
    assert read["head_device_ms"] == pytest.approx(5100e-6)
    assert read["norm_device_ms"] == pytest.approx(2450e-6)
    assert read["ssm_proj_device_ms"] == pytest.approx(1000e-6)
    assert read["stream_rows_device_ms"] == pytest.approx(700e-6)
    assert read["segment_max_device_ms"] == pytest.approx((5550 + 5800 + 900 + 600) * 1e-6)
    # a label the compiled step never enters: nothing to read
    assert read["ssm_gate_device_ms"] is read["mlp_device_ms"] is read["moe_shared_device_ms"] is None


def test_every_reader_is_none_on_a_text_without_the_labels():
    # the parent's kind of text: round.* scopes and no model.*, stream.* or segment.* label
    from chipbench.selftest.test_scope_join import TEXT as unlabelled

    ctx = _Ctx(unlabelled, [{"multiply_fusion": 600.0, "pad.2": 50.0}])
    ctx.outcome["measured"].pop("scope_paths")  # and no trace is opened to find that out
    assert sp.parts(ctx) is None and sp.segments(ctx) is None
    assert sp.part_ms(ctx, "model.head") is None
    for name in NEW_READERS:
        assert _reader(name).read(ctx) is None
    assert ctx.said == [] and "scope_paths" not in ctx.outcome["measured"]


def test_a_step_with_the_old_labels_alone_reads_its_unlabelled_share_and_no_new_part():
    # the parent of the PR that added the parts: model.attention is there, the
    # partition is not, and no segment label
    old = TEXT.replace("segment.seg01_m/", "").replace("segment.seg00_embed/", "")
    for label in ("model.head", "model.norm", "model.ssm_proj", "model.embed", "stream.rows"):
        old = old.replace(label, "")
    ctx = _Ctx(old)
    read = {name: _reader(name).read(ctx) for name in NEW_READERS}
    total = scope_paths.path_ms(ctx, "round.fwdbwd") + 50e-6
    # all but the attention product (2000 ns), the latent's op (400, now
    # model.mla_latent's) and its copy (50)
    assert read.pop("model_unlabelled_pct") == pytest.approx(100.0 * (total - 2450e-6) / total)
    assert set(read.values()) == {None}


def test_the_manifest_keeps_the_contracts_rules_and_lists_the_new_readers_cells():
    from chipbench.selftest import manifest_rules

    path = os.path.join(harness.ROOT, "BENCHMARK.json")
    manifest = harness.load_json(path)
    assert manifest_rules.check(manifest, harness.ROOT, os.path.getsize(path)) == []
    # (the language cells by their configuration, not by a list of two: every
    # model_config PR since has appended one, and a reader's list grew with it)
    lm = [cell["name"] for cell in manifest["workloads"] if cell["config"] != "resnet18-cifar-ps"]
    assert lm[:2] == ["nemotron3-nano-ps.trimmed-signflip-4k",
                      "glm47-flash-ps.trimmed-signflip-4k"]
    listed = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_READERS:
        entry = listed[name + ".train"]
        assert set(entry["workloads"]) <= set(lm) and entry["source"] == "device_trace"
        assert set(entry["workloads"]) & set(lm[:2])  # still read where it was first listed
    counts = {cell["name"]: len(harness.metrics_of_cell(manifest, cell["name"], "per_layer"))
              for cell in manifest["workloads"]}
    # 29 and 30 when the parts were added; since then one reader left the language
    # cells (`matrix_build_device_ms.train`, nothing to read since PR 43) and others came
    assert all(counts[name] >= 29 for name in lm)
    assert "matrix_build_device_ms.train" not in {
        m["name"] for name in lm for m in harness.metrics_of_cell(manifest, name, "per_layer")}
    # none of the parts in a ResNet cell
    resnet = {m["name"] for cell in manifest["workloads"] if cell["name"] not in lm
              for m in harness.metrics_of_cell(manifest, cell["name"], "per_layer")}
    assert not resnet & {name + ".train" for name in NEW_READERS}
    assert all(n <= 17 for name, n in counts.items() if name not in lm)


# -- a pair recorded on the chip -----------------------------------------------


def _recorded(tmp_path):
    """The toy Nemotron-H streamed step (one Mamba-2 block, one expert block)
    traced for three executions on a v5e: its compiled text and its trace."""
    import gzip

    from chipbench import scope_join

    with gzip.open(os.path.join(HERE, "recorded", "toy_streamed_parts.hlo.txt.gz"), "rt",
                   encoding="utf-8") as fh:
        text = fh.read()
    details = sp.read_details(text)
    shares = sp.shares_of(sp.paths_of(details))
    through = sp.through_neighbours(shares, details)
    with gzip.open(os.path.join(HERE, "recorded", "toy_streamed_parts.xplane.pb.gz")) as fh:
        with open(os.path.join(tmp_path, "toy.xplane.pb"), "wb") as plain:
            plain.write(fh.read())
    joined = scope_join.read_runs(os.path.join(tmp_path, "toy.xplane.pb"), "train_step")
    owned = [[scope_join.owned_ns(run.ops) for run in dev.runs] for dev in joined.devices]
    return text, details, shares, through, joined, owned


def test_the_recorded_toy_streamed_step_reduces_to_what_is_written_beside_it(tmp_path):
    from byzpy_tpu.observability import catalog
    from chipbench import scope_join

    want = harness.load_json(HERE, "recorded", "toy_streamed_parts.expected.json")
    text, details, shares, through, joined, owned = _recorded(tmp_path)
    assert [len(runs) for runs in owned] == want["executions"]
    step = sp.one_step({"owned": owned}, shares)
    cells = sp.table_of(step, shares)
    parts, segments = {}, {}
    for (part, _, segment, stage), ms in cells.items():
        if stage == sp.FWDBWD:
            parts[part] = parts.get(part, 0.0) + ms
        segments[segment] = segments.get(segment, 0.0) + ms
    assert parts == {k: pytest.approx(v, rel=1e-9) for k, v in want["parts_ms"].items()}
    assert segments == {k: pytest.approx(v, rel=1e-9) for k, v in want["segment_ms"].items()}
    assert set(segments) == {"seg00_embed", "seg01_mamba", "seg02_moe", "seg03_head", sp.NO_SEGMENT}
    # every traced op of the step is an instruction of the text, and all but
    # a sliver of the traced time has a place
    traced = {op.name for dev in joined.devices for run in dev.runs for op in run.ops}
    assert traced and traced <= set(details)
    busy = sum(step.values()) * 1e-6
    assert sum(cells.values()) == pytest.approx(want["placed_ms"], rel=1e-9)
    assert want["placed_ms"] > 0.95 * busy
    # the parts are scope_join's round.fwdbwd, compiler-made ops and all
    label_ms = scope_join.reduce_runs(joined, scope_join.read_labels(text, catalog.KERNELS))["label_ms"]
    assert sum(parts.values()) == pytest.approx(label_ms["round.fwdbwd"], rel=0.05)
    assert parts[sp.UNLABELLED] < 0.08 * sum(parts.values())
    assert {"model.ssm_proj", "model.ssm_gate", "model.ssm_scan", "model.moe_experts",
            "model.moe_shared", "model.norm", "model.head", "stream.rows"} <= set(parts)
    assert any(details[name]["opcode"] in ("copy", "copy-done", "bitcast", "fusion") for name in through)
