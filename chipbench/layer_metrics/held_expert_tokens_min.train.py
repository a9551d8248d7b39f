"""The least tokens any held expert got from one honest worker in one
step, over every step of the run: from the step's own metrics
(`segment_aux`, `held_expert_tokens`). A deployment's expert sees its
share of sixteen chips' batches; here it sees one worker's sequence, so
this says how thin the thinnest expert batch was (0 would mean an expert
with no gradient from a worker). `None` for a model with no expert layer.
Source: program_counter."""


def read(ctx):
    return ctx.outcome["measured"].get("held_expert_tokens_min")
