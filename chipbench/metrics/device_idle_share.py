"""Share of the traced window in which no operation ran on the device:
1 - busy union / window, averaged over the chips the cell uses."""
from chipbench.trace import complete


def read(run):
    if not complete(run.trace):
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
