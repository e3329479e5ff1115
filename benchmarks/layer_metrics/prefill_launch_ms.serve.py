"""The host's side of a prefill work unit: the median duration of the
``serve.prefill_unit`` spans of the traced window (the unit's arrays built,
their uploads, the program's call; the device runs the unit after the span
has closed).  ``prefill_stretch_ms.serve`` reads the same cost as a
difference of whole iterations; this is the span itself.  A request's first
token waits for this much host work a unit queued ahead of it.  The engine's
account keeps the same seconds and their count over the whole run
(``prefill_unit``)."""

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
    units = spans.named("serve.prefill_unit", trace.window)
    return median([s.ms for s in units]) if units else None
