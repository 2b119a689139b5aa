"""Device time of congestion control and phase bookkeeping per lane-step
executed: self time of the ops under the engine's ``envelope``, ``cc``
and ``progress`` scopes (core/fabric/simulator.py) over the lane-steps,
in one traced warm-up question after the window (chipbench/scopes.py)."""
from chipbench import scopes


def read(run):
    return scopes.per_lane_step_us(run, scopes.CC_PHASE)
