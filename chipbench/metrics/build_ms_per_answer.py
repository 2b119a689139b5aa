"""Host time of the experiment build per answered question: the time
inside the program's ``fabric.build_case`` spans (core/bench.py) in
which no device ran an op, in one traced warm-up question after the
window (chipbench/scopes.py)."""
from chipbench import scopes


def read(run):
    return scopes.idle_ms_per_answer(run, scopes.BUILD_SPAN)
