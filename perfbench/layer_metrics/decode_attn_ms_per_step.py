"""Decode attention: device time of the split-KV decode and combine kernels
in the traced slice, per engine step that decoded, in ms."""
from perfbench.layer_metrics import _common as C


def read(ctx):
    k = C.decode_attn_s(ctx)
    return None if k is None else k / len(C.decode_steps(ctx)) * 1e3
