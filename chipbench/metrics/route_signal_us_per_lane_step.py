"""Device time of routing and of the per-step gathers of link state
through each flow's path, per lane-step executed: self time of the ops
under the engine's ``route``, ``signals`` and ``queue_delay`` scopes
(core/fabric/simulator.py) over the lane-steps, in one traced warm-up
question after the window (chipbench/scopes.py)."""
from chipbench import scopes


def read(run):
    return scopes.per_lane_step_us(run, scopes.ROUTE_SIGNAL)
