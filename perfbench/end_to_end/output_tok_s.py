"""Output tokens emitted inside the window over the window's seconds."""
from perfbench.end_to_end._common import window_tokens


def read(ctx):
    n = sum(len(window_tokens(ctx, lv)) for lv in ctx["requests"])
    return n / ctx["window_s"]
