"""Every op of a language model's streamed step says which part, which
segment and which pass it belongs to, and saying so changes nothing else.

``model.*`` is a partition of what the model computes (an op belongs to the
LAST ``model.*`` label of its ``op_name`` path, ``model.mtp`` left out: an
envelope around a whole block), ``stream.*`` names the round's own work
inside ``round.fwdbwd``, and ``segment.<key>`` is entered by the round
itself for any bundle. The labels are read off the compiled steps of toy
sizes of both bundles, where ``chipbench/scope_parts.py`` reads them; the
steps' text, names and locations apart, is the parent's.
"""

from __future__ import annotations

import contextlib
import hashlib
import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from byzpy_tpu.analysis import scan_paths
from byzpy_tpu.analysis.rules import METRIC_CONTRACT
from byzpy_tpu.observability import catalog
from byzpy_tpu.ops import attack_ops, coordinatewise, robust
from byzpy_tpu.parallel.ps import PSStepConfig, build_ps_train_step
from chipbench import harness, scope_parts, scope_paths
from chipbench.scope_parts import UNLABELLED, part_of  # the readers' own rule

N, B = 8, 2
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(ROOT, "tests", "fixtures", "analysis")
SIGN_FLIP = coordinatewise.RoundAttack(attack_ops.sign_flip, of="honest_mean")
SEGMENT = re.compile(r"segment\.([A-Za-z0-9_]+)")
PARTS = {"nemotron": ["model.norm", "model.embed", "model.head", "model.ssm_proj",
                      "model.ssm_gate", "model.ssm_scan", "model.attention", "model.moe_route",
                      "model.moe_experts", "model.moe_shared", "stream.rows", "stream.boundary"],
         "glm": ["model.norm", "model.embed", "model.head", "model.mlp", "model.attention",
                 "model.mla_latent", "model.moe_route", "model.moe_experts", "model.moe_shared",
                 "model.mtp_join", "stream.rows", "stream.boundary"],
         "qwen": ["model.norm", "model.embed", "model.head", "model.ssm_proj", "model.ssm_gate",
                  "model.delta_rule", "model.attention", "model.moe_route", "model.moe_experts",
                  "model.moe_shared", "stream.rows", "stream.boundary"],
         "xing": ["model.norm", "model.embed", "model.head", "model.mlp", "model.attention",
                  "model.mla_latent", "model.moe_route", "model.moe_experts", "model.moe_shared",
                  "model.hc_maps", "model.hc_mix", "stream.rows", "stream.boundary"],
         "lfm": ["model.norm", "model.embed", "model.head", "model.mlp", "model.attention",
                 "model.short_conv", "model.short_conv_proj", "model.moe_route",
                 "model.moe_experts", "stream.rows", "stream.shared_rows", "stream.boundary"],
         # SmallThinker (PR 49): both kinds of attention under ONE label, the router
         # (which reads the block's normed input) under the expert layers' own
         "small": ["model.norm", "model.embed", "model.head", "model.attention",
                   "model.moe_route", "model.moe_experts", "stream.rows", "stream.boundary"]}
# PR 53: what attention does is three parts more (no turn by position in Nemotron-H)
INSIDE_ATTENTION = ("model.attention_proj", "model.rotary", "model.attention_core")
WHOLE_ATTENTION = re.compile(r"model\.attention(?!_)")  # the label itself, not its prefix
for _model, _parts in PARTS.items():
    _parts.extend(label for label in INSIDE_ATTENTION
                  if (_model, label) != ("nemotron", "model.rotary"))


def _toy(model):
    if model == "nemotron":
        from byzpy_tpu.models import nemotron_h as nh

        return nh.nemotron_h_bundle(nh.NemotronHConfig(
            hidden_size=32, pattern="M*E", vocab_size=64, mamba_num_heads=4, mamba_head_dim=8,
            ssm_state_size=16, n_groups=2, chunk_size=8, num_attention_heads=4,
            num_key_value_heads=2, head_dim=8, query_block=8, n_routed_experts=16,
            num_experts_per_tok=3, moe_intermediate_size=24,
            moe_shared_expert_intermediate_size=40, held_experts=(4, 4)), seed=0)
    if model == "qwen":
        from byzpy_tpu.models import qwen3_next as qn

        return qn.qwen3_next_ep16(
            0, hidden_size=32, num_hidden_layers=2, full_attention_interval=2, vocab_size=64,
            linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=8,
            linear_value_head_dim=8, chunk_size=8, num_attention_heads=4, num_key_value_heads=2,
            head_dim=8, query_block=8, num_experts=16, num_experts_per_tok=3,
            moe_intermediate_size=24, shared_expert_intermediate_size=24, held_experts=(4, 4))
    if model == "xing":
        from byzpy_tpu.models import xing4

        return xing4.xing4_29b_ep8(
            0, hidden_size=32, num_hidden_layers=2, vocab_size=64, num_attention_heads=2,
            q_lora_rank=16, kv_lora_rank=12, qk_nope_head_dim=6, qk_rope_head_dim=2, v_head_dim=8,
            query_block=8, intermediate_size=48, n_routed_experts=16, num_experts_per_tok=3,
            moe_intermediate_size=24, held_experts=(4, 4))
    if model == "lfm":
        from byzpy_tpu.models import lfm2_moe

        return lfm2_moe.lfm2_24b_ep8(
            0, hidden_size=32, layer_types=["conv", "full_attention", "conv"], vocab_size=64,
            num_attention_heads=4, num_key_value_heads=2, query_block=8, intermediate_size=48,
            num_experts=16, num_experts_per_tok=3, moe_intermediate_size=24, held_experts=(4, 4))
    if model == "small":
        from byzpy_tpu.models import smallthinker

        return smallthinker.smallthinker_21b_ep8(
            0, hidden_size=32, vocab_size=64, num_attention_heads=4, num_key_value_heads=2,
            head_dim=8, sliding_window_layout=[0, 1], rope_layout=[0, 1], sliding_window_size=6,
            query_block=8, moe_num_primary_experts=16, moe_num_active_primary_experts=3,
            moe_ffn_hidden_size=24, held_experts=(4, 4))
    from byzpy_tpu.models import glm4_moe_lite as glm

    return glm.glm47_flash_ep8(
        0, hidden_size=32, num_hidden_layers=2, vocab_size=64, num_attention_heads=2,
        q_lora_rank=16, kv_lora_rank=12, qk_nope_head_dim=6, qk_rope_head_dim=2, v_head_dim=8,
        query_block=8, intermediate_size=48, n_routed_experts=16, num_experts_per_tok=3,
        moe_intermediate_size=24, held_experts=(4, 4))


def _lowered(model):
    bundle = _toy(model)
    step, opt = build_ps_train_step(bundle, partial(robust.trimmed_mean, f=2),
                                    PSStepConfig(n_nodes=N, n_byzantine=B), attack=SIGN_FLIP)
    tokens = jnp.zeros((N, 1, 19), jnp.int32)
    return [seg.key for seg in bundle.segments], jax.jit(step).lower(
        bundle.params, opt, tokens, tokens, jax.random.PRNGKey(1))


@contextlib.contextmanager
def _no_compile_cache():
    """The persistent compile cache leaves metadata out of its key: a step
    that differs from a cached one by its scopes alone comes back with the
    cached one's ``op_name``s (PERF.md section 7). These compiles go by it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


_META = re.compile(r",?\s*metadata=\{[^}]*\}")
_FRAMES = re.compile(r"^(?:\d+ (?:\"|\{).*|FileNames|FunctionNames|FileLocations|StackFrames)$")
_LOC = re.compile(r"\s*loc\([^\n]*\)|#loc[^\n]*\n")


def _bare(compiled_text):
    """A compiled text without what a scope can reach: ``metadata={...}`` and
    the tables of file names and stack frames it points into."""
    return "\n".join(line for line in _META.sub("", compiled_text).splitlines()
                     if not _FRAMES.match(line))


def _renamed(text):
    """Every ``%name`` replaced by its order of appearance."""
    names = {}
    return re.sub(r"%[\w.\-]+", lambda m: names.setdefault(m.group(0), f"%i{len(names)}"), text)


@pytest.fixture(scope="module", params=["nemotron", "glm", "qwen", "xing", "lfm", "small"])
def compiled_text(request):
    """``(model, segment keys, the compiled step's text)``."""
    with _no_compile_cache():
        keys, lowered = _lowered(request.param)
        return request.param, keys, lowered.compile().as_text()


@pytest.fixture(scope="module")
def step_text(compiled_text):
    """``(model, segment keys, [(opcode, op_name)] of the compiled step, its
    bare text)``."""
    model, keys, text = compiled_text
    ops = []
    for line in text.splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        called = re.search(r"\s([a-z][a-z0-9\-]*)\(", " " + line.split("=", 1)[-1])
        # the program's own ops: their op_name starts at the jitted function
        # (a reducer's region is named from inside its loop, and never runs
        # as an op of its own)
        if (m and called and called.group(1) != "parameter"
                and m.group(1).startswith("jit(train_step)/")):
            ops.append((called.group(1), m.group(1)))
    return model, keys, ops, _bare(text)


# What may stand under round.fwdbwd with no part: the blocks' residual adds
# (and the sum of the two cotangents that meet there), the worker loops'
# counters, the expert layer's count of dropped tokens (a difference of two
# sums), a cast. Nothing that multiplies.
ALLOWED = re.compile(
    r"(?:segment\.\w+|jvp\(\)|transpose\(jvp\(\)\)|model\.mtp|jvp\(model\.mtp\)"
    r"|transpose\(jvp\(model\.mtp\)\))/(?:add|add_any|sub|reduce_sum|convert_element_type)$"
    r"|/while/body/add$|/while/cond/lt$")
# an op_name that ends at a control-flow node names what the compiler put
# around it (constants, tuples), not a primitive of the program
PLUMBING = re.compile(r"/(?:while|closed_call|cond|body)$")


def test_every_op_of_round_fwdbwd_holds_a_part_but_for_a_short_allow_list(step_text):
    model, _, ops, _ = step_text
    inside = [name for _, name in ops if "round.fwdbwd" in name and not PLUMBING.search(name)]
    assert len(inside) > 1000
    bare = [name for name in inside if part_of(name) == UNLABELLED]
    assert [name for name in bare if not ALLOWED.search(name)] == []
    assert len(bare) < 0.05 * len(inside), (len(bare), len(inside))


def test_every_part_appears_and_the_catalog_lists_it(step_text):
    model, _, ops, _ = step_text
    seen = {part_of(name) for _, name in ops if "round.fwdbwd" in name}
    # (the toy GLM's attention is its latents, its turn, its core and w_o's
    # product: with the three labels inside it nothing ends at model.attention)
    assert set(PARTS[model]) - ({"model.attention"} if model == "glm" else set()) <= seen
    assert seen - {UNLABELLED} <= set(catalog.SCOPES)
    # nothing of round.fwdbwd's parts leaks out of it
    assert {part_of(name) for _, name in ops
            if "round." in name and "round.fwdbwd" not in name} == {UNLABELLED}


def test_every_op_of_a_round_scope_holds_one_segment_and_every_segment_appears(step_text):
    model, keys, ops, _ = step_text
    scoped = [name for _, name in ops if re.search(r"round\.[a-z_]+", name)]
    held = [set(SEGMENT.findall(name)) for name in scoped]
    assert max(len(found) for found in held) == 1
    # without one: the first forward's loop over the workers, which runs every
    # segment (its counter, the reads and writes of a worker's turn, and the
    # kept stacks it starts from, where the backend makes an op of them), and
    # the closing metrics (two means and a root); nothing of the model
    outside = [name for name, found in zip(scoped, held) if not found]
    first_forward = re.compile(
        r"round\.segment_fwd/round\.fwdbwd/(?:while(?:/body/closed_call)?|while/body/add"
        r"|while/cond/lt|while/body/closed_call/stream\.boundary/\w+|stream\.boundary/empty)$")
    assert outside and all(
        first_forward.search(name) or "jit(train_step)/round.update/" in name for name in outside)
    assert not any("model." in name for name in outside)
    # (LFM2's and SmallThinker's blocks are a third of the others' ops against
    # the same loop plumbing a segment: 6.2 % there and, with two blocks in the
    # toy where LFM2's has three, 7.1 %)
    assert len(outside) < {"lfm": 0.07, "small": 0.08}.get(model, 0.06) * len(scoped)
    assert {key for found in held for key in found} == set(keys)
    # a segment's whole turn: its three passes and the round's three stages
    for key in keys[1:-1]:
        mine = [name for name, found in zip(scoped, held) if key in found]
        for scope in ("round.segment_fwd", "round.segment_recompute", "round.segment_bwd",
                      "round.build_matrix", "round.aggregate", "round.update"):
            assert any(scope in name for name in mine), (key, scope)
    # the label is the outermost of the backward sweep
    assert any(name.startswith(f"jit(train_step)/segment.{keys[-1]}/") for name in scoped)


def test_the_norm_is_a_part_of_its_own_inside_the_latents_and_the_gate(step_text):
    model, _, ops, _ = step_text
    outer = {"nemotron": "model.ssm_gate", "glm": "model.mla_latent",
             "qwen": "model.attention", "xing": "model.mla_latent",
             "lfm": "model.attention", "small": None}[model]
    if outer is None:  # no norm inside a mixer: every norm stands between the parts
        assert not [name for _, name in ops if "model.norm" in name
                    and re.search(r"model\.(?:attention|moe_)", name)]
        return
    nested = [name for _, name in ops if outer in name and "model.norm" in name]
    assert nested and all(part_of(name) == "model.norm" for name in nested)
    for a_pass in ("round.segment_fwd", "round.segment_recompute", "round.segment_bwd"):
        assert any(a_pass in name for name in nested), a_pass


def test_the_convolutions_own_backward_stays_in_the_gate(step_text):
    """``layers.conv_silu``'s backward is traced outside the mixer's
    scope (a ``custom_vjp``), enters ``model.ssm_gate`` itself, and leaves
    nothing of the gate that writes into a padded copy."""
    model, _, ops, _ = step_text
    own = [(opcode, name) for opcode, name in ops
           if re.search(r"model\.ssm_gate\)+/model\.ssm_gate/\w+$", name)]
    if model in ("glm", "xing", "lfm", "small"):
        assert not own
        return
    assert {name.rsplit("/", 1)[-1] for _, name in own} >= {"pad", "mul", "add", "reduce_sum"}
    assert all("round.segment_bwd" in name and part_of(name) == "model.ssm_gate"
               for _, name in own)
    assert not [opcode for opcode, name in ops if part_of(name) == "model.ssm_gate"
                and opcode in ("scatter", "dynamic-update-slice")]


def test_the_head_holds_its_terms_and_glms_second_stays_in_the_envelope(step_text):
    model, _, ops, _ = step_text
    head = [name for _, name in ops if "model.head" in name]
    # the head has no first forward: it runs inside its segment's turn
    assert head and not any("round.segment_fwd" in name for name in head)
    assert all(any(a_pass in name for name in head)
               for a_pass in ("round.segment_recompute", "round.segment_bwd"))
    if model != "glm":
        assert not any("model.mtp" in name for _, name in ops)
        return
    second = [name for _, name in ops if "model.head" in name and "model.mtp" in name]
    assert second and all(part_of(name) in ("model.head", "model.norm") for name in second)
    join = [name for _, name in ops if "model.mtp_join" in name]
    assert join and all("model.mtp" in name.replace("model.mtp_join", "") for name in join)
    shared = [name for _, name in ops if "model.moe_shared" in name]
    assert shared and all("model.moe_experts" in name for name in shared)


def test_the_hyper_connections_two_labels_never_nest_and_hold_all_three_passes(step_text):
    """``model.hc_maps`` and ``model.hc_mix`` (Xing4.0 alone): neither inside
    the other, no sublayer's work under either, each in every pass (the two
    mixes' own backward rules enter ``model.hc_mix`` themselves), and with
    them no residual add is left without a part in a block."""
    model, keys, ops, _ = step_text
    held = [name for _, name in ops if "model.hc_" in name]
    if model != "xing":
        assert not held
        return
    assert not [name for name in held if "model.hc_maps" in name and "model.hc_mix" in name]
    for label in ("model.hc_maps", "model.hc_mix"):
        mine = [name for name in held if label in name]
        assert all(part_of(name) == label for name in mine)
        for a_pass in ("round.segment_fwd", "round.segment_recompute", "round.segment_bwd"):
            assert any(a_pass in name for name in mine), (label, a_pass)
    own = [name for name in held
           if re.search(r"model\.hc_mix\)+/model\.hc_mix/\w+$", name)]
    assert own and all("round.segment_bwd" in name for name in own)
    # the blocks' residual path is labelled: what is left bare under a block's
    # segment is the cotangents' meeting, never the forward's add
    blocks = [name for _, name in ops if "round.fwdbwd" in name
              and any(f"segment.{key}" in name for key in keys[1:-1])
              and part_of(name) == UNLABELLED and not PLUMBING.search(name)]
    assert not [name for name in blocks if "round.segment_fwd" in name
                and re.search(r"segment\.\w+/add$", name)]


def test_the_short_convolution_is_two_parts_and_its_own_backward_stays_in_the_first(step_text):
    """``model.short_conv`` (the gates and the convolution between them) and
    ``model.short_conv_proj`` (the products with ``w_in`` and ``w_out``),
    LFM2 alone: the second label begins with the first's letters and is a
    part of its own all the same; neither holds the other; each in every
    pass; the backward rule of ``layers.gated_short_conv`` (a
    ``custom_vjp``, traced outside the operator's scope) enters
    ``model.short_conv`` itself and multiplies no matrix."""
    model, _, ops, _ = step_text
    held = [(opcode, name) for opcode, name in ops if "model.short_conv" in name]
    if model != "lfm":
        assert not held
        return
    parts = {name: part_of(name) for _, name in held}
    assert set(parts.values()) == {"model.short_conv", "model.short_conv_proj"}
    assert not [name for name in parts if "model.short_conv_proj" in name
                and re.search(r"model\.short_conv(?!_proj)", name)]
    for label in ("model.short_conv", "model.short_conv_proj"):
        mine = [name for name, part in parts.items() if part == label]
        for a_pass in ("round.segment_fwd", "round.segment_recompute", "round.segment_bwd"):
            assert any(a_pass in name for name in mine), (label, a_pass)
    own = [(opcode, name) for opcode, name in held
           if re.search(r"model\.short_conv\)+/model\.short_conv/\w+$", name)]
    assert {name.rsplit("/", 1)[-1] for _, name in own} >= {"pad", "mul", "concatenate",
                                                            "reduce_sum"}
    assert all("round.segment_bwd" in name for _, name in own)
    assert not [opcode for opcode, name in held if parts[name] == "model.short_conv"
                and opcode in ("dot", "convolution", "scatter", "dynamic-update-slice")]
    assert [opcode for opcode, name in held if parts[name] == "model.short_conv_proj"
            and opcode in ("dot", "convolution")]


def test_a_tied_tables_way_to_its_owners_row_has_a_label_of_its_own(step_text):
    """``stream.shared_rows``: in the turn of the segment that READS another's
    parameters (LFM2's head) and nowhere else, in the backward pass, writing
    rows; the owner's own gradient joins the row under ``stream.rows``."""
    model, keys, ops, _ = step_text
    shared = [(opcode, name) for opcode, name in ops if "stream.shared_rows" in name]
    if model != "lfm":
        assert not shared
        return
    assert shared and all(f"segment.{keys[-1]}/" in name and "round.segment_bwd" in name
                          and part_of(name) == "stream.shared_rows" for _, name in shared)
    assert any(opcode == "dynamic-update-slice" or name.endswith("dynamic_update_slice")
               for opcode, name in shared)
    # the owner's turn ADDS to what the reader left: it reads the row it writes
    first = [name for _, name in ops if f"segment.{keys[0]}/" in name and "stream.rows" in name]
    assert any(name.endswith("/dynamic_slice") for name in first)
    assert any(name.endswith("/dynamic_update_slice") for name in first)
    # the table's second use is the head's
    assert any("model.head" in name and name.endswith("dot_general") for _, name in ops)


def test_both_kinds_of_attention_hold_one_label_and_the_router_the_expert_layers(step_text):
    """SmallThinker alone: a global block and a windowed one stand under
    ``model.attention`` in every pass (the rotary turn of the windowed block
    with them), and the router, which reads the block's normed input, under
    ``model.moe_route`` in every pass of both blocks."""
    model, keys, ops, _ = step_text
    if model != "small":
        return
    assert keys == ["seg00_embed", "seg01_global", "seg02_window", "seg03_head"]
    for key in keys[1:-1]:
        for label in ("model.attention", "model.moe_route", "model.moe_experts"):
            mine = [name for _, name in ops if f"segment.{key}/" in name and label in name]
            # (of model.attention's ops: those that hold none of the labels inside it)
            assert mine and all(part_of(name) == label for name in mine if not any(
                inside in name for inside in INSIDE_ATTENTION)), (key, label)
            for a_pass in ("round.segment_fwd", "round.segment_recompute", "round.segment_bwd"):
                assert any(a_pass in name for name in mine), (key, label, a_pass)
    turned = [name for _, name in ops if "model.attention" in name
              and re.search(r"/(?:cos|sin)$", name)]
    assert turned and all("segment.seg02_window/" in name and part_of(name) == "model.rotary"
                          for name in turned)
    assert not [name for _, name in ops if "model.rotary" in name
                and "segment.seg02_window/" not in name]


def test_what_attention_does_is_three_labels_inside_it_in_every_pass(step_text):
    """``model.attention_proj``, ``model.rotary`` (not Nemotron-H's: no
    positional term) and ``model.attention_core``: each in all three passes
    of a block with attention, each an op's part where it is the last label
    (a head's norm in front of a turn stays ``model.norm``), and nothing of
    them outside ``model.attention``."""
    model, keys, ops, _ = step_text
    for label in (label for label in INSIDE_ATTENTION if label in PARTS[model]):
        mine = [name for _, name in ops if label in name]
        assert mine and all(part_of(name) in (label, "model.norm") for name in mine), label
        assert any(part_of(name) == label for name in mine)
        for a_pass in ("round.segment_fwd", "round.segment_recompute", "round.segment_bwd"):
            assert any(a_pass in name for name in mine), (label, a_pass)
        # a block's: never the embedding's segment, never the head's
        assert all(any(f"segment.{key}/" in name for key in keys[1:-1])
                   or "round.segment_fwd" in name for name in mine)
    if model == "nemotron":
        assert not [name for _, name in ops if "model.rotary" in name]


def test_nothing_of_the_three_labels_leaks_out_of_model_attention(step_text):
    """Every caller of ``rotary``, ``attention_proj``, ``causal_attention``
    and ``blocked_causal_attention`` stands inside ``model.attention``: an op
    whose path holds one of the three holds ``model.attention`` itself too
    (the label is a prefix of two of them: asked by ``WHOLE_ATTENTION``)."""
    _, _, ops, _ = step_text
    held = [name for _, name in ops if any(label in name for label in INSIDE_ATTENTION)]
    assert held and all(WHOLE_ATTENTION.search(name) for name in held)


def test_the_three_labels_never_nest_in_one_another_and_the_products_are_projs(step_text):
    model, _, ops, _ = step_text
    for name in {name for _, name in ops}:
        assert sum(label in name for label in INSIDE_ATTENTION) <= 1, name
    # every product of model.attention's own is a projection or the core's
    # (latent attention: or the latents')
    products = [name for opcode, name in ops if "model.attention" in name
                and name.endswith("dot_general")]
    assert products and {part_of(name) for name in products} <= {
        "model.attention_proj", "model.attention_core", "model.mla_latent"}
    assert not [name for _, name in ops if part_of(name) == "model.rotary"
                and name.endswith("dot_general")]


def test_the_cores_backward_stays_in_the_core(step_text):
    """The core's backward ops hold ``model.attention_core`` (the CPU's route:
    the transpose of ``blocked_causal_attention``'s own ops; the kernels'
    ``custom_vjp`` rule, traced outside the forward's scopes, enters both
    labels itself: ``tests/test_round_matrix_once.py`` reads it off the
    texts compiled for the TPU)."""
    _, _, ops, _ = step_text
    backward = [name for _, name in ops if "round.segment_bwd" in name
                and "model.attention_core" in name]
    assert backward and all(part_of(name) == "model.attention_core" for name in backward)
    assert any("transpose(" in name and name.endswith("dot_general") for name in backward)


# -- the benchmark's readers of the three labels, on a toy step's text ---------------

ATTENTION_READERS = ["attention_proj_device_ms", "rotary_device_ms", "attention_core_device_ms",
                     "attention_wrap_device_ms", "attention_rest_device_ms", "attention_moved_mb"]


class _Traced:
    """What a reader touches of a ``harness.Ctx``, for a text with no trace:
    every instruction owns a microsecond of one execution, and the step holds
    no kernel (the CPU's route)."""

    def __init__(self, text):
        instructions = scope_paths.read_text(text)
        owned = {name: 1000.0 for name, ins in instructions.items() if ins["paths"]}
        self.outcome = {"compiled_text": text, "measured": {
            "step_module": "train_step", "scope_paths_text": instructions,
            "scope_paths": {"instructions": instructions, "owned": [[owned]]},
            "scope_join": {"kernel_ms": {}}, "scope_parts_executions": {}}}

    def say(self, **facts):
        pass


def _read(name, ctx):
    return harness.load_by_path(
        os.path.join(harness.HERE, "layer_metrics", name + ".train.py"), name + ".train").read(ctx)


@pytest.fixture(scope="module")
def read_by_the_readers(compiled_text):
    """``(model, reader -> its value on the step's text, reader -> its value
    on the text with the three labels taken out: the parent's)``, and the part
    ``model.mla_latent`` of both."""
    model, _, text = compiled_text
    parent = text
    for label in INSIDE_ATTENTION:
        parent = parent.replace(label, "")
    sides = []
    for side in (text, parent):
        ctx = _Traced(side)
        values = {name: _read(name, ctx) for name in ATTENTION_READERS}
        values["model.mla_latent"] = scope_parts.part_ms(ctx, "model.mla_latent")
        sides.append(values)
    return (model, *sides)


@pytest.mark.parametrize("reader", ATTENTION_READERS)
def test_each_reader_reads_its_label_off_a_toy_steps_text(read_by_the_readers, reader):
    model, change, parent = read_by_the_readers
    value = change[reader]
    if reader == "attention_wrap_device_ms":  # no kernel in the CPU's step
        assert value is None and parent[reader] is None
    elif reader == "attention_moved_mb":
        # what a path HOLDS: the same number with and without the labels inside
        # (0 where the CPU's compiler fused every move into its consumer; the
        # texts compiled for the TPU, tests/test_round_matrix_once.py, hold some)
        assert value is not None and value >= 0 and value == parent[reader]
    elif reader == "attention_rest_device_ms":
        # (0 where the three labels name all there is: the toy GLM's attention)
        assert value is not None and 0 <= value < parent[reader]
        assert value > 0 or model == "glm"
    elif reader == "rotary_device_ms" and model == "nemotron":
        assert value is None and parent[reader] is None
    else:
        assert value is not None and value > 0 and parent[reader] is None


def test_the_new_parts_and_the_remainder_add_up_to_the_parents_part(read_by_the_readers):
    """With a turn inside the latents (GLM, Xing) the projections, the core
    and the remainder are the parent's ``model.attention`` and the turn is
    what ``model.mla_latent`` lost; elsewhere all four are the parent's."""
    model, change, parent = read_by_the_readers
    inside = sum(change[name] or 0.0 for name in (
        "attention_proj_device_ms", "attention_core_device_ms", "attention_rest_device_ms"))
    turn = change["rotary_device_ms"] or 0.0
    if model in ("glm", "xing"):
        assert inside == pytest.approx(parent["attention_rest_device_ms"], rel=1e-9)
        assert change["model.mla_latent"] + turn == pytest.approx(
            parent["model.mla_latent"], rel=1e-9)
    else:
        assert inside + turn == pytest.approx(parent["attention_rest_device_ms"], rel=1e-9)


def test_round_fwdbwd_is_still_the_innermost_round_scope(step_text):
    _, _, ops, _ = step_text
    passes = [name for _, name in ops if "round.segment_" in name]
    assert {re.findall(r"round\.[A-Za-z0-9_]+", name)[-1] for name in passes} == {"round.fwdbwd"}


def test_a_scope_is_metadata_and_nothing_else(step_text, monkeypatch):
    """The same step with every ``jax.named_scope`` a no-op compiles to the
    same text, ``metadata={...}`` and the frame tables apart."""
    model, _, _, bare = step_text
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    with _no_compile_cache():
        text = _lowered(model)[1].compile().as_text()
    assert "model.norm" not in text and "segment." not in text
    if model == "xing":
        # 114,000 lines (the Sinkhorn iterations unrolled, three passes): the
        # numeric suffix of XLA's instruction names moves with the metadata in a
        # module of this size, and nothing else does
        assert _renamed(_bare(text)) == _renamed(bare)
        return
    assert _bare(text) == bare


# sha256 of the lowered text without its locations, taken on the parent commit
# (9588a48) with this file's own functions before the program was touched;
# "nemotron" is tests/test_streamed_round.py's "toy-nemotron" too, and was
# taken again at PR 37 (191ff157... before it), which changed one thing in the
# Mamba-2 mixer's text and nothing else: the convolution and its SiLU are one
# function with a backward of its own that hands out its three column blocks
# (``nemotron_h.conv_silu``); tests/test_nemotron_h.py holds it to the plain
# formula's value and gradient. Both were taken again at PR 39 (38fcba49...
# and 49bf5224... before it, which still hold with the parent's
# ``parallel/moe.py`` under that PR's tree: moving ``conv_silu`` and ``rotary``
# to ``models/layers.py`` changed nothing), which changed the expert layer's
# text alone: a round places its readers by flat index and, where that at least
# halves the columns, reads back by a token's picks;
# tests/test_held_experts_combine.py holds the layer to the one it was.
# "qwen" is PR 39's own. All three were taken again at PR 40 (0914aa04...,
# a7a4f567... and 04808da9... before it), which changed the expert layer's
# text alone: a round's rows go back to the tokens by one read a column,
# multiplied and added in float32 (``moe._rows_to_tokens``: the combine's
# forward and the dispatch gather's own backward; no ``(T, k, D)`` array, no
# scatter-add of rows), and every model takes a column a pick;
# tests/test_held_experts_combine.py holds the layer to automatic
# differentiation of the one it was and to the dense reference. PR 41 left
# all three as they were (``mla_attention`` moved to ``models/layers.py`` with
# YaRN's frequencies and two head widths behind it, the attention kernels
# take a key width and a value width, the round labels its kept stacks
# ``stream.boundary``); "xing" is PR 41's own.
PARENT_LOWERED = {
    "nemotron": "096a8c43ea20e23734bdcfdae98cae66626b4f3cb089f09496dc149ac04cd2cc",
    "glm": "f400e96fe68b6a88edff8bd5148c5bc19d8071fd12e1c1da7a3df5c4f2fb2cca",
    "qwen": "85d70f5d84607f6a37ed0e8a5f606c8e17dbd3f3b2878e2b0db80f9971ea188e",
    "xing": "8cd6c9fc95e8f8e393494ba64ed11c538aa959d426e56d72bf7a9d57744e97c2",
}


# PR 42 changed ``round.update`` alone, and only where the optimizer's update
# is elementwise (``coordinatewise.is_elementwise``, the round's default is):
# a segment whose row is whole tiles (on the CPU: whose d divides by 1024)
# takes its leaves' stretches as tile views and sums the norm leaf by leaf.
# With every optimizer sent down the other path (whole leaves, as before) the
# four texts are still the parent's, digest for digest; down the new path:
ROW_ORDER_LOWERED = {
    "nemotron": "fa69c81d10845f6b28e2fbed62ef6ac4ba7a7e5bd1dc82dd983340b6205322bb",
    "glm": "f4a024fa13517ed45c19d781bf6021787d550efd03a9bcf7c1f93f85cd3752f7",
    "qwen": "82ac41af5e5099b1341ad04880b300bda8b84f0cfde5c8d93741ec60489eb811",
    "xing": "7eb5d95cc99d4987ffbbca2c464f5b518826806d0a53ff95e42b6804a2fafbcb",
}


@pytest.mark.parametrize("model", sorted(PARENT_LOWERED))
def test_the_toy_streamed_steps_lower_to_the_parents_text(monkeypatch, model):
    from byzpy_tpu.ops import coordinatewise

    monkeypatch.setattr(coordinatewise, "is_elementwise", lambda opt, params, state: False)
    text = _LOC.sub("", _lowered(model)[1].as_text())
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_LOWERED[model]


@pytest.mark.parametrize("model", sorted(ROW_ORDER_LOWERED))
def test_the_toy_streamed_steps_lower_to_the_text_they_had_in_the_rows_order(model):
    text = _LOC.sub("", _lowered(model)[1].as_text())
    assert hashlib.sha256(text.encode()).hexdigest() == ROW_ORDER_LOWERED[model]


# -- the catalog and byzlint hold the labels ----------------------------------

NEW_SCOPES = ["model.norm", "model.embed", "model.head", "model.ssm_proj", "model.ssm_gate",
              "model.mlp", "model.moe_shared", "model.mtp_join", "stream.rows", "stream.boundary",
              "model.delta_rule", "model.hc_maps", "model.hc_mix", "model.short_conv",
              "model.short_conv_proj", "stream.shared_rows", *INSIDE_ATTENTION]


@pytest.mark.parametrize("scope", NEW_SCOPES)
def test_the_catalog_lists_each_new_scope(scope):
    assert scope in catalog.SCOPES


def test_the_segment_family_is_a_catalogued_prefix():
    assert catalog.SCOPE_PREFIXES == ("segment.",)
    assert not any(scope.startswith(catalog.SCOPE_PREFIXES) for scope in catalog.SCOPES)


def _contract_findings(name):
    result = scan_paths([os.path.join(FIXTURES, name)], select=[METRIC_CONTRACT])
    return [f.message for f in result.findings if f.rule == METRIC_CONTRACT]


def test_byzlint_accepts_a_computed_scope_under_a_catalogued_prefix():
    assert _contract_findings("metric_contract_scopes_fp.py") == []


def test_byzlint_flags_a_computed_scope_outside_every_catalogued_prefix():
    found = [m for m in _contract_findings("metric_contract_scopes_tp.py")
             if "computed named_scope label" in m]
    assert len(found) == 2
    assert any("'round.'" in m for m in found)  # a literal head that is no family
    assert any("starts with ''" in m for m in found)  # no literal head at all


def test_byzlint_is_clean_on_the_modules_that_enter_the_labels():
    paths = [os.path.join(ROOT, "byzpy_tpu", *parts) for parts in (
        ("parallel", "ps.py"), ("parallel", "moe.py"), ("models", "nemotron_h.py"),
        ("models", "glm4_moe_lite.py"), ("models", "layers.py"), ("models", "qwen3_next.py"),
        ("models", "xing4.py"), ("models", "lfm2_moe.py"), ("models", "smallthinker.py"),
        ("models", "bundle.py"))]
    result = scan_paths(paths, select=[METRIC_CONTRACT])
    assert [f.message for f in result.findings if f.rule == METRIC_CONTRACT] == []
