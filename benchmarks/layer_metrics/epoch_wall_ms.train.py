"""Median wall time of a warm epoch as the trainer itself logs it
(``trainer.metrics``: host clock around shuffle, shaping, the epoch program and
the fetch of its losses, which waits for the device)."""

LAYER = "trainers"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "train_tokens_per_s"


def read(records, trace, env):
    if records.get("kind") != "train" or not records["epoch_events"]:
        return None
    from benchmarks.lib.stats import median
    return 1000.0 * median([e["seconds"] for e in records["epoch_events"]])
