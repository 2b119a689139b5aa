"""Share of the HBM roofline the step core reaches: the bytes each
lane-step needs at the problem's real (unpadded) dims
(chipbench/roofline.py), over the chip's peak bandwidth, divided by the
step core's device time. The step is bound by bytes; its arithmetic is
a few operations per byte moved."""
import numpy as np

from chipbench import roofline
from chipbench.trace import complete
from chipbench.tap import lane_steps, real_dims


def read(run):
    core = run.trace["step_core_s"] if complete(run.trace) else 0.0
    if not run.tap or core <= 0:
        return None
    total = 0.0
    for call in run.tap.engine_calls:
        _, steps = lane_steps(call)
        lanes_per_cell = int(np.asarray(call["params"].dt).shape[-1])
        for dims in real_dims(call["geom"]):
            total += lanes_per_cell * steps * roofline.step_core_bytes(*dims)
    if not total:
        return None
    bw = roofline.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * (total / bw) / core
