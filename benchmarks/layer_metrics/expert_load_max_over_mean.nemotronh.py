"""How uneven the held experts' load is in the decode steps of the window of
the Nemotron-H serving cell,
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
    # the load's skew is the same arithmetic over the same records as the accepted
    # reader's: one copy of it
    from benchmarks.lib import manifest as mf
    return mf.load_layer_metric("expert_load_max_over_mean.hybrid").read(records, trace, env)
