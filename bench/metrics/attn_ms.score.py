"""Device ms per prompt (`prefill_step` execution) in ops under an
attention dispatch scope, ``contract.attn.*`` (score cells)."""
from benchlib import scopes


def read(ctx):
    got = scopes._by_scope(ctx, "score", "prefill_step")
    if got is None:
        return None
    seconds, runs = got
    attn = sum(v for k, v in seconds.items()
               if k is not None and k.startswith(scopes.CONTRACT + "attn."))
    return 1e3 * attn / runs if attn else None
