"""How far the expert layers' loads stand from the edge of a round, as a
percentage of the round: over the expert layers, the least distance of a
layer's fullest held expert (the largest count over the run's steps,
honest workers and held experts, `held_expert_tokens_by_layer`, and the
least of its passes' fullest experts) from a whole multiple `k x rows`,
`k >= 1`, of that layer's round (`expert_round_rows`: the program's own
word in `segment_aux` where it gives one, else read off the compiled
step's text; `chipbench/expert_round.py`). The held experts multiply
their tokens a round of `rows` each, as many rounds as the pass's
fullest expert needs, so a layer pass's cost steps at every multiple: a
cell whose margin is small changes its number of rounds, and its rate by
a round's cost, with the draw of the data or with a later change to the
round's size. 50 is the middle of a round, 0 a layer whose own passes
lie on both sides of a multiple. It is the cell's guard: it falls before
a spread shows. `None` for a model with no expert layer. Source:
program_counter."""

from chipbench import expert_round


def read(ctx):
    measured = ctx.outcome["measured"]
    if "held_expert_tokens_by_layer" not in measured:
        return None
    return expert_round.margin_pct(
        measured["held_expert_fullest_least_by_layer"], measured["held_expert_tokens_by_layer"],
        measured["expert_round_rows"])
