"""1 if the compiled step holds a Mosaic custom call (the aggregate took
the Pallas route), else 0. Read from the compiled program's text; a
count, repeats exactly. Source: program_counter."""


def read(ctx):
    text = ctx.outcome.get("compiled_text")
    if not text:
        return None
    return 1 if "tpu_custom_call" in text else 0
