"""Share of decode slots that held a running request, over the decode steps of
the window: the engine's ``active_slot_steps`` over ``decode_steps x
num_slots``, both as differences across the window."""

LAYER = "serving engine"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "serve_tokens_per_s"


def read(records, trace, env):
    if records.get("kind") != "serve" or records["decode_steps"] <= 0:
        return None
    return (100.0 * records["active_slot_steps"]
            / (records["decode_steps"] * records["num_slots"]))
