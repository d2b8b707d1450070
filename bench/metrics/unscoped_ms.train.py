"""Device ms per train step (`train_step` execution) in ops under no
program scope (train cells)."""
from benchlib import scopes


def read(ctx):
    return scopes.unscoped_ms(ctx, "train", "train_step")
