"""Device time of the step core (kernels/fabric_step.py) per lane-step
executed: the trace's step-core event durations over lanes x chunks x
chunk of the window's engine calls."""
from chipbench.tap import lane_step_totals
from chipbench.trace import complete


def read(run):
    _, executed = lane_step_totals(run.tap.engine_calls if run.tap else [])
    core = run.trace["step_core_s"] if complete(run.trace) else 0.0
    return 1e6 * core / executed if executed and core > 0 else None
