"""Device time of one decode step in the expert cache: self time of the
ops under the ``moe_probe``, ``moe_gather``, ``moe_commit`` and
``moe_prefetch`` scopes, per ``jit__decode_step`` run wholly inside the
traced slice (``scopes.py``)."""
from benchmarks.chip import scopes


def read(ctx):
    r = scopes.reading(ctx)
    if r is None:
        return None
    return r.total("moe_probe", "moe_gather", "moe_commit", "moe_prefetch")
