"""Mean host time of one engine decode step, from its dispatch to the
drain of its counters (``engine/decode_step`` spans)."""


def read(ctx):
    spans = [s for s in ctx.trace.spans if s.key == "engine/decode_step"]
    if not spans:
        return None
    return sum(s.end - s.start for s in spans) / len(spans) / 1e6
