"""How uneven the held experts' load is in the decode steps of the window,
from the engine's counters: the fullest held expert's rows over the mean rows
a held expert (``moe_load_max`` and ``moe_assignments_held``, both summed over
layers and steps, the second spread over the experts held here).  1 is a
perfectly even router; a grouped matmul's time follows the touched experts'
weights, so skew costs little until an expert's rows outgrow a row tile."""

LAYER = "model step"
UNIT = "x"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "itl_p95_ms"


def read(records, trace, env):
    c = records.get("window_counters")
    if (records.get("kind") != "serve" or not c
            or not c.get("moe_assignments_held")):
        return None
    held = int(env["cfg"]["n_routed_experts"])
    return c["moe_load_max"] * held / c["moe_assignments_held"]
