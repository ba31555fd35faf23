"""Decode attention: least time (bytes at HBM bandwidth or FLOPs at the bf16
peak, whichever is larger) over the kernels' device time, %."""
from perfbench.layer_metrics import _common as C


def read(ctx):
    k = C.decode_attn_s(ctx)
    return None if k is None else 100.0 * C.decode_attn_least_s(ctx) / k
