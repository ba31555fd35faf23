"""Engine host loop: device idle time under the engine's steps that is not
under an ``engine.sync`` span (a wait on the device), per engine step, in
ms: the time the chip waits for the host's own work."""
from perfbench import engine_spans as ES


def read(ctx):
    red = ES.load(ctx)
    if not red or not red["devices"]:
        return None
    steps = red["steps"]
    idle = sum(st["idle_s"] for st in steps) - sum(
        sp["idle_s"] for st in steps for sp in st["spans"]
        if sp["name"] == "engine.sync")
    return idle / len(steps) * 1e3
