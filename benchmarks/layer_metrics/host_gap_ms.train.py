"""The host's share of an epoch boundary: the median, over the boundaries of
the traced window, of the time from the end of one epoch's ``train.fetch``
(the device's losses have arrived: the chip is idle) to the start of the next
epoch's ``train.dispatch`` (logging, shuffling and shaping lie between).  The
transfer and launch inside ``train.dispatch`` are not in it."""

LAYER = "trainers"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "train_tokens_per_s"


def read(records, trace, env):
    if records.get("kind") != "train":
        return None
    from benchmarks.lib import spans as S
    from benchmarks.lib.stats import median
    spans = S.of_run(trace)
    if spans is None:
        return None
    fetched = spans.named("train.fetch", trace.window)
    sent = spans.named("train.dispatch", trace.window)
    gaps = []
    for f in fetched:
        nxt = next((d for d in sent
                    if d.thread == f.thread and d.start >= f.end), None)
        if nxt is not None:
            gaps.append((nxt.start - f.end) / 1e6)
    return median(gaps) if gaps else None
