"""Device ms per decode step (`serve_step` execution) in ops under a
facility dispatch scope, ``contract.*`` (generate cells)."""
from benchlib import scopes


def read(ctx):
    return scopes.contract_ms(ctx, "generate", "serve_step")
