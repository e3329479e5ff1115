"""The engine's iteration as the requests feel it, from the engine's own
spans: the 95th percentile, over the traced span, of the time from one decode
step's ``serve.emit`` (``kind`` ``decode`` or ``spec``: the token loop that
hands a step's tokens to the requests) to the next step's, each gap counted
once for every row the later step held, since each of them waited that long
for its token.  It is ``itl_p95_ms`` seen from inside, over the 4 s of the
traced span (some 17 steps of some 20 rows) instead of the whole run.  A
``serve.iteration`` span is NOT this: an iteration ends wherever the host
happens to wait, so one that drains a prefill's first token and a decode
step lasts 321 ms and its neighbour 187, while the tokens leave 187.5 or
254-255 ms apart (PERF.md section 6, PR 24)."""

LAYER = "serving engine"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "itl_p95_ms"


def read(records, trace, env):
    if records.get("kind") != "serve":
        return None
    from benchmarks.lib import spans as S
    from benchmarks.lib.stats import percentile
    spans = S.of_run(trace)
    if spans is None:
        return None
    steps = [s for s in spans.named("serve.emit", trace.window)
             if s.fields.get("kind") in ("decode", "spec")]
    gaps = []
    for a, b in zip(steps, steps[1:]):
        if b.fields["step"] == a.fields["step"] + 1:
            gaps += [(b.start - a.start) / 1e6] * b.fields["rows"]
    return percentile(gaps, 95) if gaps else None
