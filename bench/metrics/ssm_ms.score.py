"""Device ms per prompt (`prefill_step` execution) in ops whose innermost
program scope is ``block.ssm``: the Mamba layers' work outside their
contractions, conv and state passes, most of it the chunk scan's (score
cells)."""
from benchlib import scopes


def read(ctx):
    got = scopes._by_scope(ctx, "score", "prefill_step")
    if got is None:
        return None
    seconds, runs = got
    return 1e3 * seconds["block.ssm"] / runs if "block.ssm" in seconds else None
