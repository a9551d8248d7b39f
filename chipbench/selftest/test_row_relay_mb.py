"""`row_relay_mb.train` reads, from the compiled step's text, the megabytes
of leaf-sized f32 results that `copy`, `concatenate`, `transpose` and
`reshape` instructions write at the top level of the forward/backward
loop's body. Recorded: that body of the `resnet18-cifar-ps` step with the
trimmed mean as the TPU v5e's own runs wrote it (`ctx.outcome
["compiled_text"]`), before (PR 30's chip run: nine relayouts of the
weight gradients whose minor dimension is 256 or 512, 41.81 MB, and
`ravel`'s concatenate of the row, 44.76 MB) and after each leaf's gradient
is placed in the folded stack as it lies (PR 31's chip run: none). Of the
body only the top-level `copy`, `concatenate`, `transpose` and `reshape`
instructions, those that name the stack and the root are kept, and of the
entry computation the loop; `backend_config`, stack frames and parameter
lists are cut off."""

import os

import pytest

from chipbench import harness

HERE = os.path.dirname(os.path.abspath(__file__))
READER = harness.load_by_path(
    os.path.join(harness.HERE, "layer_metrics", "row_relay_mb.train.py"), "row_relay_mb.train")
CONFIG = {"n_nodes": 8, "n_byzantine": 2, "n_parameters": 11_173_962}


def _ctx(text, config):
    ctx = harness.Ctx(manifest={}, cell={"name": "c"}, config=config, mix={}, seed=0, seconds=0,
                      trace=True, devices=[], t_process=0.0)
    ctx.outcome = {"compiled_text": text}
    return ctx


def _recorded(name):
    with open(os.path.join(HERE, "recorded", name), encoding="utf-8") as fh:
        return fh.read()


@pytest.mark.parametrize("name, megabytes", [("row_relay_before.hlo.txt", 86.573056),
                                             ("row_relay_after.hlo.txt", 0.0)])
def test_row_relay_mb_of_a_recorded_loop_body(name, megabytes):
    config = harness.load_json(harness.HERE, "configs", "resnet18-cifar-ps.json")
    text = _recorded(name)
    # both bodies write the stack in place, and both hold small copies that do not count
    assert "dynamic-update-slice_fusion" in text.partition("\nENTRY ")[0]
    assert " copy(" in text.partition("\nENTRY ")[0]
    assert READER.read(_ctx(text, config)) == megabytes


def test_the_recorded_parent_holds_the_nine_relayouts_and_the_concatenate():
    text = _recorded("row_relay_before.hlo.txt")
    assert "concatenate.26 = f32[11190272]" in text
    assert text.count("f32[576,4,8,128]{3,1,2,0:T(4,128)} copy(") == 3
    after = _recorded("row_relay_after.hlo.txt")
    assert "f32[11190272]" not in after and "f32[576,4,8,128]" not in after


def _text(body_lines, carry="f32[8,87424,128]{2,1,0:T(8,128)}", body="%body.1"):
    return ("%fused.1 (cut) -> cut {\n"
            "  ROOT %inner = f32[576,4,8,128]{3,1,2,0:T(4,128)} copy(%p)\n}\n\n"
            + "%body.1 (cut) -> cut {\n" + "\n".join(body_lines) + "\n}\n\n"
            + "ENTRY %main (cut) -> cut {\n"
            + "  %early = (s32[], f32[64]{0}) while(%t0), condition=%c0, body=%other\n"
            + f"  %loop = (s32[], f32[6]{{0}}, {carry}) while(%t), condition=%c, body={body}\n"
            + "  ROOT %entry_copy = f32[576,4,8,128]{3,1,2,0:T(4,128)} copy(%q)\n}\n")


def test_what_counts_and_what_does_not():
    lines = [
        "  %a = f32[576,4,8,128]{3,1,2,0:T(4,128)} copy(%x)",              # 2359296 elements
        "  %b = f32[131072]{0:T(1024)} concatenate(%x, %y), dimensions={0}",  # 2^17 exactly
        "  %c = f32[256,512]{0,1:T(8,128)} transpose(%x), dimensions={1,0}",
        "  %d = f32[3,3,256,256]{3,2,1,0:T(8,128)} reshape(%x)",
        "  %small = f32[128,512]{0,1:T(8,128)} copy(%x)",                   # 2^16: a GroupNorm transpose
        "  %narrow = bf16[576,4,8,128]{3,1,2,0} copy(%x)",                  # not the stated f32
        "  %free = f32[576,4,8,128]{3,1,2,0:T(4,128)} bitcast(%x)",
        "  %fused = f32[576,4,8,128]{3,1,2,0:T(4,128)} fusion(%x), kind=kLoop, calls=%fused.1",
        "  ROOT %put = f32[8,87424,128]{2,1,0:T(8,128)} fusion(%s, %a), kind=kLoop, calls=%fused.1",
    ]
    want = 4 * (576 * 4 * 8 * 128 + 131072 + 256 * 512 + 3 * 3 * 256 * 256) / 1e6
    assert READER.read(_ctx(_text(lines), CONFIG)) == want
    assert READER.read(_ctx(_text(lines[4:]), CONFIG)) == 0.0
    # the first loop whose carry holds the (n, ., 128) stack decides: another n, none
    assert READER.read(_ctx(_text(lines), dict(CONFIG, n_nodes=16))) is None
    # a mesh's step carries flat (n, d) rows through no such loop
    assert READER.read(_ctx(_text(lines, carry="f32[8,11173962]{1,0:T(8,128)}"), CONFIG)) is None
    # a body the text does not hold
    assert READER.read(_ctx(_text(lines, body="%gone"), CONFIG)) is None


@pytest.mark.parametrize("text", ["", None])
def test_no_compiled_text_gives_nothing(text):
    assert READER.read(_ctx(text, CONFIG)) is None
