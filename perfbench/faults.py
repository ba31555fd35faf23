"""Faults planted in the program's engine, to show that the check of
``correct`` catches them (``tests/test_faults.py`` at small widths,
``calibrate.py --fault`` at a cell's own size). Each takes the engine once
it is built (``harness.run_cell``'s ``engine_hook``).

The same faults planted in the reference put in the program's place are
read by ``harness.check_sample``.
"""
from __future__ import annotations

import jax

from perfbench import harness


def token_altered(engine):
    """The third token of every request altered where it is produced."""
    vocab = engine.cfg.vocab_size
    emit = engine._emit

    def altered(req, tok):
        if len(req.out_tokens) == harness.ALTERED_INDEX:
            tok = harness.altered(tok, vocab)
        emit(req, tok)

    engine._emit = altered


def state_unchanged(engine):
    """A decode step that returns the cache state it was given: the token it
    appends is lost."""
    from repro.launch import steps as ST
    step = jax.jit(ST.make_decode_step(engine.cfg))

    def stale(params, tok, state, pos):
        logits, _ = step(params, tok, state, pos)
        return logits, state

    engine._decode_fn = stale


FAULTS = {"token_altered": token_altered, "state_unchanged": state_unchanged}
