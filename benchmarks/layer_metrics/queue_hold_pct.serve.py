"""How often the scheduler leaves the queue's head waiting: of the
``serve.iteration`` spans of the traced window, the per cent that hold a
``serve.hold``, the instant ``_schedule_prefills`` stops admitting with a
request still queued.  The split by the span's ``reason`` (``no_slot``,
``no_blocks``, ``budget``: the iteration's ``prefills_per_step`` spent) is
printed on an earlier line: ``budget`` says the unit budget paces admission,
``no_slot`` / ``no_blocks`` that the pool does.  Nothing from a trace that
holds no such span at all: a program that does not say (the parent of the PR
that added the span), or a cell whose queue never stands.  The engine's
account keeps the same counts over the whole run (``held``)."""

LAYER = "serving engine"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "serve_tokens_per_s"


def read(records, trace, env):
    if records.get("kind") != "serve":
        return None
    import collections
    import json
    from benchmarks.lib import spans as S
    spans = S.of_run(trace)
    if spans is None or not spans.named("serve.hold"):
        return None
    its = spans.named("serve.iteration", trace.window)
    if not its:
        return None
    # a hold is inside its iteration, and an iteration has at most one
    why = collections.Counter(
        h.fields.get("reason", "?")
        for h in spans.named("serve.hold", (its[0].start, its[-1].end)))
    print(json.dumps({"queue_hold_by_reason": dict(sorted(why.items())),
                      "iterations": len(its)}), flush=True)
    return 100.0 * sum(why.values()) / len(its)
