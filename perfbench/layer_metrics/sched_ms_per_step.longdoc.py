"""Scheduler, allocator and prefix cache: host self time of the engine's
admit, tier_drain, grow and retire spans, per engine step, in ms."""
from perfbench import engine_spans as ES

PHASES = {"engine.admit", "engine.tier_drain", "engine.grow",
          "engine.retire"}


def read(ctx):
    red = ES.load(ctx)
    if not red:
        return None
    steps = red["steps"]
    return sum(sp["self_s"] for st in steps for sp in st["spans"]
               if sp["name"] in PHASES) / len(steps) * 1e3
