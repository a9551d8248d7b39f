"""`sublane_matrix_writes.train` counts the entry computation's
instructions that write the whole gradient matrix with workers as
sublanes, on the compiled text of three timed steps of the
`resnet18-cifar-ps` configuration as the TPU v5e's own runs of the
benchmark wrote them (PR 29's chip runs: `ctx.outcome["compiled_text"]`
of a `--trace 1` run; of each computation only the lines that name the
matrix's width, 11190272, or a folded row, 87424,128, are kept, with
`backend_config` and the computations' parameter lists cut off): the
parent's step with the trimmed mean (the relayout of the loop's stack, and
the select that puts the byzantine rows in: 2), the folded round with the
trimmed mean (the kernel reads the loop's stack: 0) and with Multi-Krum
(one relayout for its Gram: 1). The loop's body and the fused computations
hold such arrays too and count in none."""

import os

import pytest

from chipbench import harness

HERE = os.path.dirname(os.path.abspath(__file__))
READER = harness.load_by_path(
    os.path.join(harness.HERE, "layer_metrics", "sublane_matrix_writes.train.py"),
    "sublane_matrix_writes.train")
CONFIG = {"n_nodes": 8, "n_byzantine": 2, "n_parameters": 11_173_962}


def _ctx(text, config):
    ctx = harness.Ctx(manifest={}, cell={"name": "c"}, config=config, mix={}, seed=0, seconds=0,
                      trace=True, devices=[], t_process=0.0)
    ctx.outcome = {"compiled_text": text}
    return ctx


def _recorded(name):
    with open(os.path.join(HERE, "recorded", name), encoding="utf-8") as fh:
        return fh.read()


@pytest.mark.parametrize("name, writes", [("sublane_writes_parent_trimmed.hlo.txt", 2),
                                          ("sublane_writes_folded_trimmed.hlo.txt", 0),
                                          ("sublane_writes_folded_krum.hlo.txt", 1)])
def test_sublane_matrix_writes_of_a_recorded_text(name, writes):
    config = harness.load_json(harness.HERE, "configs", "resnet18-cifar-ps.json")
    text = _recorded(name)
    # the loop's body, a computation of its own, writes rows of the stack
    assert "dynamic-update-slice(" in text.partition("\nENTRY ")[0]
    assert READER.read(_ctx(text, config)) == writes


def test_rows_columns_and_the_entry_computation_decide():
    text = _recorded("sublane_writes_parent_trimmed.hlo.txt")
    assert READER.read(_ctx(text, CONFIG)) == 2
    # seven honest workers: the relayout of the six-row stack is too short to count
    assert READER.read(_ctx(text, dict(CONFIG, n_byzantine=1))) == 1
    # more columns than the matrix has: nothing is a whole matrix
    assert READER.read(_ctx(text, dict(CONFIG, n_parameters=11_190_273))) == 0
    # the same instructions inside a computation that is not the entry: not counted
    moved = text.replace("\nENTRY ", "\n")
    assert READER.read(_ctx(moved, CONFIG)) is None
    # a folded stack, a flat vector and an instruction that writes nothing
    lines = "\nENTRY %main (cut) -> cut {\n" + "\n".join([
        "  %a = f32[8,87424,128]{2,1,0:T(8,128)} fusion(%x), kind=kLoop",
        "  %b = f32[1,1,11190272]{2,1,0:T(1,128)} custom-call(%a)",
        "  %c = f32[1,8,11190272]{2,1,0:T(8,128)} bitcast(%a)",
        "  %d = f32[1,8,11190272]{2,1,0:T(8,128)} reshape(%a)",
        "  ROOT %e = f32[6,11190272]{1,0:T(8,128)} copy(%d)"]) + "\n}\n"
    assert READER.read(_ctx(lines, CONFIG)) == 2


@pytest.mark.parametrize("text", ["", None])
def test_no_compiled_text_gives_nothing(text):
    assert READER.read(_ctx(text, CONFIG)) is None
