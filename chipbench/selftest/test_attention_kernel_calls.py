"""`attention_kernel_calls.train` counts the Mosaic kernels inside
`model.attention`, on lines of two compiled steps of
`nemotron3-nano-ps.trimmed-signflip-4k` (compiled for a described v5e at
the real size, PR 33; `backend_config` cut off): the parent's (attention
is XLA's convolutions: 0, beside two `sorted_reduce_stream` calls of the
aggregate, which are in no `model.attention`) and the change's (forward,
the segment's second forward, dq, dk/dv: 4, beside the same two)."""

import os

import pytest

from chipbench import harness

HERE = os.path.dirname(os.path.abspath(__file__))
READER = harness.load_by_path(
    os.path.join(harness.HERE, "layer_metrics", "attention_kernel_calls.train.py"),
    "attention_kernel_calls.train")


def _ctx(text):
    ctx = harness.Ctx(manifest={}, cell={"name": "c"}, config={}, mix={}, seed=0, seconds=0,
                      trace=True, devices=[], t_process=0.0)
    ctx.outcome = {"compiled_text": text}
    return ctx


def _recorded(name):
    with open(os.path.join(HERE, "recorded", name), encoding="utf-8") as fh:
        return fh.read()


@pytest.mark.parametrize("name, calls", [("attention_calls_before.hlo.txt", 0),
                                         ("attention_calls_after.hlo.txt", 4)])
def test_attention_kernel_calls_of_a_recorded_text(name, calls):
    text = _recorded(name)
    assert text.count('custom_call_target="tpu_custom_call"') == calls + 2
    assert "model.attention" in text
    assert READER.read(_ctx(text)) == calls


def test_a_custom_call_counts_only_by_its_own_op_name():
    scoped = ('%k.1 = f32[8,128]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", '
              'metadata={op_name="jit(step)/round.fwdbwd/vmap(model.attention)/k/pallas_call"}')
    other = scoped.replace("vmap(model.attention)", "round.aggregate").replace("%k.1", "%k.2")
    xla = ('%d.1 = f32[8,128]{1,0} convolution(%a, %b), '
           'metadata={op_name="jit(step)/round.fwdbwd/model.attention/dot_general"}')
    host = scoped.replace("tpu_custom_call", "Sharding").replace("%k.1", "%k.3")
    assert READER.read(_ctx("\n".join([xla, other]))) == 0
    assert READER.read(_ctx("\n".join([xla, other, host, scoped]))) == 1
    assert READER.read(_ctx("\n".join([scoped, scoped.replace("%k.1", "%k.4")]))) == 2


@pytest.mark.parametrize("text", ["", None, '%k.2 = f32[8] custom-call(%a), '
                                  'custom_call_target="tpu_custom_call", '
                                  'metadata={op_name="jit(step)/round.aggregate/k/pallas_call"}'])
def test_no_attention_in_the_text_gives_nothing(text):
    assert READER.read(_ctx(text)) is None
