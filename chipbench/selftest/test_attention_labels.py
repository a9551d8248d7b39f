"""The six readers of what attention does outside and inside its kernels
(`attention_proj_device_ms.train`, `rotary_device_ms.train`,
`attention_core_device_ms.train`, `attention_wrap_device_ms.train`,
`attention_rest_device_ms.train`, `attention_moved_mb.train`) on a compiled
text and an ``owned`` table written by hand: no chip, no trace. No number
here is a device metric of the benchmark. Their manifest entries are found
by NAME, never by place or count."""

from __future__ import annotations

import os

import pytest

from chipbench import harness
from chipbench.selftest.test_scope_parts import BWD, RE, _Ctx

INSIDE = ("model.attention_proj", "model.rotary", "model.attention_core")
LANGUAGE_CELLS = [
    "nemotron3-nano-ps.trimmed-signflip-4k", "glm47-flash-ps.trimmed-signflip-4k",
    "qwen3-next-ps.trimmed-signflip-4k", "xing4-ps.trimmed-signflip-1k",
    "lfm2-moe-ps.trimmed-signflip-4k", "smallthinker-ps.trimmed-signflip-8k"]
READERS = {  # name -> (unit, source, its cells)
    "attention_proj_device_ms.train": ("ms", "device_trace", LANGUAGE_CELLS),
    "rotary_device_ms.train": ("ms", "device_trace", LANGUAGE_CELLS[1:]),  # Nemotron-H turns nothing
    "attention_core_device_ms.train": ("ms", "device_trace", LANGUAGE_CELLS),
    "attention_wrap_device_ms.train": ("ms", "device_trace", LANGUAGE_CELLS),
    "attention_rest_device_ms.train": ("ms", "device_trace", LANGUAGE_CELLS),
    "attention_moved_mb.train": ("MB", "program_counter", LANGUAGE_CELLS),
}
ATT = RE + "jvp(vmap(model.attention))/"
ATT_BWD = BWD + "transpose(jvp(vmap(model.attention)))/"

# One block's attention as the TPU's compiler prints it, cut to what the
# readers read: a projection, a turn, a reshape left standing, the kernel
# between its pad and its slice, a transpose fused into a consumer, a layout
# copy without an op_name, a bitcast, the backward rule's row-sum (which
# enters both labels itself), a turn inside the latents, a copy of another part.
TEXT = f'''HloModule jit_train_step, is_scheduled=true

%fused_computation.2 (param_0.2: bf16[64,32]) -> bf16[32,64] {{
  %param_0.2 = bf16[64,32]{{1,0:T(8,128)(2,1)}} parameter(0)
  ROOT %transpose.9 = bf16[32,64]{{1,0:T(8,128)(2,1)}} transpose(%param_0.2), dimensions={{1,0}}, metadata={{op_name="{ATT}model.attention_core/transpose"}}
}}

%body.7 (arg.1: (s32[], bf16[64,32])) -> (s32[], bf16[64,32]) {{
  %arg.1 = (s32[]{{:T(128)}}, bf16[64,32]{{1,0:T(8,128)(2,1)}}) parameter(0)
  %q_dot.1 = bf16[64,32]{{1,0:T(8,128)(2,1)}} convolution(%arg.1, %arg.1), metadata={{op_name="{ATT}model.attention_proj/dot_general"}}
  %turn.2 = bf16[64,32]{{1,0:T(8,128)(2,1)}} multiply(%q_dot.1, %q_dot.1), metadata={{op_name="{ATT}model.rotary/mul"}}
  %heads.3 = bf16[64,4,8]{{2,1,0:T(8,128)(2,1)}} reshape(%turn.2), metadata={{op_name="{ATT}reshape"}}
  %pad.4 = bf16[128,32]{{1,0:T(8,128)(2,1)}} pad(%turn.2, %arg.1), padding=0_64x0_0, metadata={{op_name="{ATT}model.attention_core/jit(_pad)/pad"}}
  %causal_attention_fwd.5 = bf16[128,32]{{1,0:T(8,128)(2,1)}} custom-call(%pad.4), custom_call_target="tpu_custom_call", metadata={{op_name="{ATT}model.attention_core/causal_attention_fwd/pallas_call"}}
  %slice.6 = bf16[64,32]{{1,0:T(8,128)(2,1)}} slice(%causal_attention_fwd.5), slice={{[0:64], [0:32]}}, metadata={{op_name="{ATT}model.attention_core/slice"}}
  %moved_fusion.7 = bf16[32,64]{{1,0:T(8,128)(2,1)}} fusion(%slice.6), kind=kLoop, calls=%fused_computation.2, metadata={{op_name="{ATT}model.attention_core/transpose"}}
  %copy.8 = f32[64,32]{{0,1:T(8,128)}} copy(%slice.6)
  %bitcast.9 = bf16[2048]{{0:T(1024)(128)(2,1)}} bitcast(%slice.6), metadata={{op_name="{ATT}reshape"}}
  %o_dot.10 = bf16[64,32]{{1,0:T(8,128)(2,1)}} convolution(%copy.8, %arg.1), metadata={{op_name="{ATT_BWD}model.attention_proj/dot_general"}}
  %delta.11 = f32[64]{{0:T(128)}} reduce(%o_dot.10, %arg.1), dimensions={{1}}, to_apply=%region_0.1, metadata={{op_name="{BWD}model.attention/model.attention_core/reduce_sum"}}
  %latent_turn.12 = bf16[64,32]{{1,0:T(8,128)(2,1)}} multiply(%q_dot.1, %q_dot.1), metadata={{op_name="{ATT}model.mla_latent/model.rotary/mul"}}
  %other_copy.13 = f32[64,32]{{0,1:T(8,128)}} copy(%o_dot.10), metadata={{op_name="{RE}jvp(model.moe_experts)/copy"}}
  %concat.14 = bf16[64,64]{{1,0:T(8,128)(2,1)}} concatenate(%turn.2, %turn.2), dimensions={{1}}, metadata={{op_name="{ATT}concatenate"}}
  %transpose.15 = f32[32,64]{{1,0:T(8,128)}} transpose(%copy.8), dimensions={{1,0}}, metadata={{op_name="{BWD}model.attention/model.attention_core/transpose"}}
  %copy.16 = bf16[64,32]{{0,1:T(8,128)(2,1)}} copy(%o_dot.10), metadata={{op_name="{ATT_BWD}model.attention_proj/transpose"}}
  ROOT %tuple.3 = (s32[]{{:T(128)}}, bf16[64,32]{{1,0:T(8,128)(2,1)}}) tuple(%arg.1, %copy.16)
}}

ENTRY %main.3 (x.1: bf16[64,32]) -> bf16[64,32] {{
  %x.1 = bf16[64,32]{{1,0:T(8,128)(2,1)}} parameter(0), metadata={{op_name="x"}}
  ROOT %while.4 = (s32[]{{:T(128)}}, bf16[64,32]{{1,0:T(8,128)(2,1)}}) while(%x.1), condition=%cond.6, body=%body.7, metadata={{op_name="jit(train_step)/segment.seg01_m/while"}}
}}
'''
PARENT = TEXT
for _label in INSIDE:  # the parent of the PR that added the labels: the same ops without them
    PARENT = PARENT.replace(_label + "/", "")

RUN = {"q_dot.1": 4000.0, "turn.2": 300.0, "heads.3": 70.0, "pad.4": 50.0,
       "causal_attention_fwd.5": 9000.0, "slice.6": 40.0, "moved_fusion.7": 600.0, "copy.8": 30.0,
       "o_dot.10": 5000.0, "delta.11": 200.0, "latent_turn.12": 100.0, "other_copy.13": 7.0,
       "concat.14": 80.0, "transpose.15": 90.0, "copy.16": 20.0, "while.4": 10.0}
PROJ = (4000 + 5000 + 20 + 30) * 1e-6  # the nameless copy with the product it was made for
TURN = (300 + 100) * 1e-6
CORE = (50 + 9000 + 40 + 600 + 200 + 90) * 1e-6
REST = (70 + 80) * 1e-6
KERNEL = 9000e-6
# bytes of the top-level moves under model.attention; the nameless layout copy counts
# through the product it was made for
COUNTED = {"heads.3": 4096, "pad.4": 8192, "slice.6": 4096, "copy.8": 8192, "concat.14": 8192,
           "transpose.15": 8192, "copy.16": 4096}
MOVED = sum(COUNTED.values())


def _read(name, ctx):
    return harness.load_by_path(
        os.path.join(harness.HERE, "layer_metrics", name + ".py"), name).read(ctx)


def _ctx(text, kernel_ms=None):
    ctx = _Ctx(text, [RUN])
    ctx.outcome["measured"]["scope_join"] = {
        "kernel_ms": {"causal_attention_fwd": KERNEL} if kernel_ms is None else kernel_ms}
    return ctx


@pytest.mark.parametrize("name, want, on_the_parent", [
    ("attention_proj_device_ms.train", PROJ, None),
    ("rotary_device_ms.train", TURN, None),
    ("attention_core_device_ms.train", CORE, None),
    ("attention_wrap_device_ms.train", CORE - KERNEL, None),
    ("attention_rest_device_ms.train", REST, PROJ + TURN + CORE + REST - 100e-6),
    ("attention_moved_mb.train", MOVED / 1e6, MOVED / 1e6),
])
def test_each_reader_reads_its_part_and_the_parent_reads_what_it_had(name, want, on_the_parent):
    assert _read(name, _ctx(TEXT)) == pytest.approx(want, rel=1e-12)
    found = _read(name, _ctx(PARENT))
    assert found is None if on_the_parent is None else found == pytest.approx(on_the_parent,
                                                                              rel=1e-12)


def test_the_parts_take_the_parents_part_apart_without_a_remainder():
    change, parent = _ctx(TEXT), _ctx(PARENT)
    inside = sum(_read(name, change) for name in (
        "attention_proj_device_ms.train", "rotary_device_ms.train",
        "attention_core_device_ms.train", "attention_rest_device_ms.train"))
    # all but the turn inside the latents, which the parent's latents held
    assert inside - 100e-6 == pytest.approx(_read("attention_rest_device_ms.train", parent))
    from chipbench import scope_parts

    assert scope_parts.part_ms(parent, "model.mla_latent") == pytest.approx(100e-6)
    assert scope_parts.part_ms(change, "model.mla_latent") is None


def test_the_wrap_is_nothing_where_the_step_holds_no_attention_kernel():
    assert _read("attention_wrap_device_ms.train", _ctx(TEXT, kernel_ms={})) is None
    assert _read("attention_wrap_device_ms.train", _ctx(
        TEXT, kernel_ms={"sorted_reduce_stream": 1.0})) is None
    windowed = {"window_attention_fwd": 4000e-6, "causal_attention_dq": 1000e-6}
    assert _read("attention_wrap_device_ms.train", _ctx(TEXT, kernel_ms=windowed)) == (
        pytest.approx(CORE - 5000e-6))


def test_the_rest_is_zero_where_the_labels_name_all_and_none_without_attention():
    named = TEXT.replace(ATT + "reshape", ATT + "model.attention_core/reshape").replace(
        ATT + "concatenate", ATT + "model.attention_core/concatenate")
    assert _read("attention_rest_device_ms.train", _ctx(named)) == 0.0
    assert _read("attention_core_device_ms.train", _ctx(named)) == pytest.approx(CORE + REST)
    other = PARENT.replace("model.attention", "model.mlp")
    for name in READERS:
        assert _read(name, _ctx(other)) is None, name


def test_moved_counts_an_instruction_by_its_own_opcode_place_and_op_name():
    assert _read("attention_moved_mb.train", _ctx(TEXT)) * 1e6 == MOVED
    # not the transpose fused into a consumer, the bitcast, another part's copy; each of
    # those that count, by itself (the nameless copy: by the product it was made for)
    for name, size in COUNTED.items():
        asked = "o_dot.10" if name == "copy.8" else name
        line = next(line for line in TEXT.splitlines() if f"%{asked} = " in line)
        without = TEXT.replace(line, line.replace("model.attention", "model.mlp"))
        assert _read("attention_moved_mb.train", _ctx(without)) * 1e6 == MOVED - size, name
    # a nameless copy made for another part's instruction counts nowhere
    line = next(line for line in TEXT.splitlines() if "%copy.8 = " in line)
    elsewhere = TEXT.replace(line, line.replace("copy(%slice.6)", "copy(%other_copy.13)")).replace(
        "convolution(%copy.8, %arg.1)", "convolution(%slice.6, %arg.1)").replace(
        "transpose(%copy.8)", "transpose(%slice.6)").replace(
        "tuple(%arg.1, %copy.16)", "tuple(%copy.8, %copy.16)")
    assert _read("attention_moved_mb.train", _ctx(elsewhere)) * 1e6 == MOVED - COUNTED["copy.8"]
    # no trace, no table: the text alone
    bare = harness.Ctx(manifest={}, cell={"name": "c"}, config={}, mix={}, seed=0, seconds=0,
                       trace=True, devices=[], t_process=0.0)
    bare.outcome = {"compiled_text": TEXT}
    assert _read("attention_moved_mb.train", bare) * 1e6 == MOVED
    bare.outcome = {"compiled_text": ""}
    assert _read("attention_moved_mb.train", bare) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_the_manifest_lists_each_reader_by_name_with_its_cells(name):
    from chipbench.selftest import manifest_rules

    path = os.path.join(harness.ROOT, "BENCHMARK.json")
    manifest = harness.load_json(path)
    assert manifest_rules.check(manifest, harness.ROOT, os.path.getsize(path)) == []
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    unit, source, cells = READERS[name]
    assert entry == {"name": name, "unit": unit, "better": "lower", "source": source,
                     "layer": "model", "moves": "train_samples_per_s", "workloads": cells}
    assert os.path.isfile(os.path.join(harness.HERE, "layer_metrics", name + ".py"))
    for cell in manifest["workloads"]:
        listed = {m["name"] for m in harness.metrics_of_cell(manifest, cell["name"], "per_layer")}
        assert (name in listed) == (cell["name"] in cells)
