"""Device ms per prompt (`prefill_step` execution) in ops under an SSD
dispatch scope, ``contract.ssd.*``: the chunk scan's intra-chunk branch
(score cells)."""
from benchlib import scopes


def read(ctx):
    got = scopes._by_scope(ctx, "score", "prefill_step")
    if got is None:
        return None
    seconds, runs = got
    ssd = sum(v for k, v in seconds.items()
              if k is not None and k.startswith(scopes.CONTRACT + "ssd."))
    return 1e3 * ssd / runs if ssd else None
