"""Model step, prefill: device time of the chunked-prefill program ("XLA
Modules" line) per engine step that ran a prefill chunk, in ms."""
from perfbench import engine_spans as ES


def read(ctx):
    red = ES.load(ctx)
    if not red:
        return None
    return ES.per_step_ms(red["program_s"]["chunk"],
                          ES.steps_with(red, "chunk"))
