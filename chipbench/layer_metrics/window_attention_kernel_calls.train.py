"""How many sliding-window attention kernels the compiled step runs: its
`tpu_custom_call` instructions named `window_attention_fwd`,
`window_attention_dq` or `window_attention_dkv`. One for each pass's kernel
a windowed block (forward, the segment's second forward, dq, dk / dv: 4 a
block, 24 for six windowed blocks); 0 where a model with a
`sliding_window_size` runs its windowed blocks some other way (the `lax.map`
route, or the causal kernels: a window no shorter than the sequence). `None`
for a configuration whose reference names no window, or a run with no
compiled text. Read from the compiled program's text; a count, repeats
exactly. Source: program_counter."""

from chipbench import opcount_window_attention, scope_join


def read(ctx):
    text = ctx.outcome.get("compiled_text")
    arch = ctx.config.get("reference", {}).get("arch", {})
    if not text or "sliding_window_size" not in arch:
        return None
    return len(scope_join.read_labels(text, list(opcount_window_attention.KINDS)).kernel)
