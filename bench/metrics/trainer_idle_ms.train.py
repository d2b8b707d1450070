"""Idle ms of the chip per traced step while the trainer loop was in one
of its own spans other than the step (train cells)."""
from benchlib import scopes


def read(ctx):
    return scopes.trainer_idle_ms(ctx)
