"""Which class of token gap a cell's percentile reads: of the
``serve.iteration`` spans of the traced window that hold a
``serve.decode_dispatch``, the per cent that hold a ``serve.prefill_unit``
too.  A token gap is a decode step, or a decode step that carries a prefill
unit and is several times longer; ``itl_p95_ms`` reads the second class where
10 % or more of the steps carry a unit and the first where 2.5 % or fewer do
(``benchmarks/README.md``, the rule since PR 37).  A cell that reads between
the two is on the edge: its 95th percentile reads one class or the other by
the run.  The engine's account keeps the same share over the whole run
(``step_carries_prefill_pct``).  One pass over the loop's thread."""

LAYER = "serving engine"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "itl_p95_ms"


def read(records, trace, env):
    if records.get("kind") != "serve":
        return None
    from benchmarks.lib import spans as S
    spans = S.of_run(trace)
    if spans is None:
        return None
    steps = carried = 0
    for it in spans.named("serve.iteration", trace.window):
        inside = {c.name for c in spans.children(it)}
        if "serve.decode_dispatch" in inside:
            steps += 1
            carried += "serve.prefill_unit" in inside
    return 100.0 * carried / steps if steps else None
