"""Device ms per prompt (`prefill_step` execution) in ops under a
facility dispatch scope, ``contract.*`` (score cells)."""
from benchlib import scopes


def read(ctx):
    return scopes.contract_ms(ctx, "score", "prefill_step")
