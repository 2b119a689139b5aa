"""Share of the lane-steps the batched engine executed after each lane
had answered: 1 - sum over lanes of the lane's own finish step (its
victim's last completion time / dt) / (lanes x steps executed). Counts
the chunk tail and the lockstep of a batch together. Read from the
engine outputs as they return to core/bench.py (chipbench/tap.py)."""
from chipbench.tap import lane_step_totals


def read(run):
    useful, executed = lane_step_totals(run.tap.engine_calls if run.tap
                                        else [])
    return 100.0 * (1.0 - useful / executed) if executed else None
