"""Device ms per train step (`train_step` execution) in ops under a
facility dispatch scope, ``contract.*`` (train cells)."""
from benchlib import scopes


def read(ctx):
    return scopes.contract_ms(ctx, "train", "train_step")
