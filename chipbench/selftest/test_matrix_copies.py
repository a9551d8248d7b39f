"""`matrix_copies.train` counts the instructions that rewrite the whole
gradient matrix, on lines of two compiled steps of
`resnet18-ps.trimmed-signflip` read on the TPU v5e (PR 27's traced runs,
`backend_config` cut off): the parent's (the concatenate as a `maximum`
of two pads, and the route's padded copy: 2) and the change's (one
buffer, the ravel's `dynamic-update-slice`s, one select over it, the
kernel reading that: 0). A `pad`, a `maximum` and a `concatenate` of
forward/backward are in both and count in neither."""

import os

import pytest

from chipbench import harness

HERE = os.path.dirname(os.path.abspath(__file__))
READER = harness.load_by_path(
    os.path.join(harness.HERE, "layer_metrics", "matrix_copies.train.py"), "matrix_copies.train")


def _ctx(text, config):
    ctx = harness.Ctx(manifest={}, cell={"name": "c"}, config=config, mix={}, seed=0, seconds=0,
                      trace=True, devices=[], t_process=0.0)
    ctx.outcome = {"compiled_text": text}
    return ctx


def _recorded(name):
    with open(os.path.join(HERE, "recorded", name), encoding="utf-8") as fh:
        return fh.read()


@pytest.mark.parametrize("name, copies", [("matrix_copies_before.hlo.txt", 2),
                                          ("matrix_copies_after.hlo.txt", 0)])
def test_matrix_copies_of_a_recorded_text(name, copies):
    config = harness.load_json(harness.HERE, "configs", "resnet18-cifar-ps.json")
    text = _recorded(name)
    assert " pad(" in text and " maximum(" in text and " concatenate(" in text
    assert READER.read(_ctx(text, config)) == copies


def test_rows_and_columns_decide_what_a_whole_matrix_is():
    text = _recorded("matrix_copies_before.hlo.txt")
    config = {"n_nodes": 8, "n_parameters": 11_173_962}
    assert READER.read(_ctx(text, config)) == 2
    # another count of rows, or more columns than the result has: only the pad is left
    assert READER.read(_ctx(text, dict(config, n_nodes=6))) == 1
    assert READER.read(_ctx(text, dict(config, n_parameters=11_173_963))) == 1
    assert READER.read(_ctx(text, {"n_nodes": 8})) == 2  # a configuration that states no d


@pytest.mark.parametrize("text", ["", None])
def test_no_compiled_text_gives_nothing(text):
    assert READER.read(_ctx(text, {"n_nodes": 8})) is None
