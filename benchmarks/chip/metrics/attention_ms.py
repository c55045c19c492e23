"""Device time of one decode step in attention: self time of the ops
under the ``attn`` scope (the ln1 norm, paged decode attention and the KV
append), per ``jit__decode_step`` run wholly inside the traced slice
(``scopes.py``)."""
from benchmarks.chip import scopes


def read(ctx):
    r = scopes.reading(ctx)
    return None if r is None else r.total("attn")
