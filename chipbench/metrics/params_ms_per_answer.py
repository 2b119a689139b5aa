"""Host time of the grid's lane parameters per answered question: the
time inside the program's ``fabric.grid_params`` spans (core/bench.py)
in which no device ran an op, in one traced warm-up question after the
window (chipbench/scopes.py)."""
from chipbench import scopes


def read(run):
    return scopes.idle_ms_per_answer(run, scopes.PARAMS_SPAN)
