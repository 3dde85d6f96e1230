"""Serving between rounds: publish of the round's globals, prefill and
greedy decode, each token read back.  The mean length of the benchmark's
host span ``bench:serve`` over the traced window's rounds."""


def read(ctx):
    spans = [s for s in ctx.trace.span("serve")
             if s[0] >= ctx.lo and s[1] <= ctx.hi]
    if not spans:
        return None
    return sum(e - s for s, e, _ in spans) / len(spans) * 1e-9
