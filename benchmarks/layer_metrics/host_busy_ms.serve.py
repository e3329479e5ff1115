"""What the host itself spends on an iteration: the median, over the
``serve.iteration`` spans of the traced window that dispatched work (a
``serve.decode_dispatch`` or a ``serve.prefill_unit`` inside), of the
iteration's duration less its ``serve.fetch`` spans, in which the host only
waits for the device.  While this is far below the decode step it hides under
the one-step lookahead; once the step is short it is what sets the pace."""

LAYER = "serving engine"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "itl_p95_ms"


def read(records, trace, env):
    if records.get("kind") != "serve":
        return None
    from benchmarks.lib import spans as S
    from benchmarks.lib.stats import median
    spans = S.of_run(trace)
    if spans is None:
        return None
    own = []
    for s in spans.named("serve.iteration", trace.window):
        inside = spans.children(s)
        if any(c.name in ("serve.decode_dispatch", "serve.prefill_unit")
               for c in inside):
            waited = sum(c.ms for c in inside if c.name == "serve.fetch")
            own.append(s.ms - waited)
    return median(own) if own else None
