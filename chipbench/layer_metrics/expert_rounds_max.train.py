"""The most rounds an expert layer took for one worker's tokens in one
step, over every step of the run: from the step's own metrics
(`segment_aux`, `expert_rounds`). The held experts multiply their tokens
a round of `round_rows` each, as many rounds as the fullest expert needs
(`byzpy_tpu.parallel.moe.held_experts_ffn`); 1 says every expert's
tokens fitted one round in every step, so the window's rate does not
hang on which seed drew a popular expert. `None` for a model with no
expert layer, and for a program whose expert layer does not count rounds.
Source: program_counter."""


def read(ctx):
    return ctx.outcome["measured"].get("expert_rounds_max")
