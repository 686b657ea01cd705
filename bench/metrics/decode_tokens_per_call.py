"""Decode tokens per fused decode call over the window (engine counters
``decode_tokens`` / ``decode_calls``)."""


def read(run):
    c = run.counters
    return c["decode_tokens"] / c["decode_calls"] if c["decode_calls"] else None
