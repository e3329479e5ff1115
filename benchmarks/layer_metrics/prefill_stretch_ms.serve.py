"""What a prefill work unit adds to an iteration: the median duration of the
``serve.iteration`` spans that hold at least one ``serve.prefill_unit`` less
the median of those that hold none, both over iterations that began with a
running request (``active`` > 0), wholly inside the traced window.  One unit
is admitted an iteration, so this stretch times the units queued ahead of a
request is its wait for a first token."""

LAYER = "serving engine"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "ttft_p95_ms"


def read(records, trace, env):
    if records.get("kind") != "serve":
        return None
    from benchmarks.lib import spans as S
    from benchmarks.lib.stats import median
    spans = S.of_run(trace)
    if spans is None:
        return None
    with_unit, without = [], []
    for s in spans.named("serve.iteration", trace.window):
        if s.fields.get("active", 0) > 0:
            (with_unit if spans.children(s, "serve.prefill_unit")
             else without).append(s.ms)
    if not with_unit or not without:
        return None
    return median(with_unit) - median(without)
