"""Decode attention kernels: device time of the split-KV combine kernels
(LSE or AMLA) per engine step that decoded, in ms."""
from perfbench import engine_spans as ES


def read(ctx):
    red = ES.load(ctx)
    if not red:
        return None
    return ES.per_step_ms(red["kernel_s"]["decode_combine"],
                          ES.steps_with(red, "decode"))
