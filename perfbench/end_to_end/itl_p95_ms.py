"""95th percentile (nearest rank) of every gap between consecutive output
tokens of every request, both tokens inside the window, in ms; +inf when
no request emitted two tokens in it."""
import math

from perfbench import stats
from perfbench.end_to_end._common import window_tokens


def read(ctx):
    gaps = []
    for lv in ctx["requests"]:
        ts = window_tokens(ctx, lv)
        gaps += [(b - a) * 1e3 for a, b in zip(ts, ts[1:])]
    return stats.percentile(gaps, 95) if gaps else math.inf
