"""What the host spends launching a decode step: the median duration of the
``serve.decode_dispatch`` spans of the traced window (the live rows listed,
the sampler's work read off the handles, the program's call: every argument
is already on the device).  It is the part of ``host_busy_ms.serve`` that no
prefill unit and no token loop accounts for, and the first thing a multi-step
decode would take off an iteration.  The engine's account keeps the same
seconds over the whole run (``phase_s["decode_dispatch"]``)."""

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
    sent = spans.named("serve.decode_dispatch", trace.window)
    return median([s.ms for s in sent]) if sent else None
