"""gridrec_mfu: the whole reconstruction step's share of the chip's peak.

Algorithmic operations a frame (the ramp filter's two FFTs and multiply,
the backprojection's 9 operations a pixel-angle, the scaling), times the
frames whose results became ready inside the traced window, over the
window's length times the peak FLOP/s."""
import numpy as np


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    frames = np.count_nonzero((run.done >= run.trace_t0) & (run.done <= run.trace_t1))
    if frames == 0:
        return None
    window = run.trace_t1 - run.trace_t0
    return 100.0 * frames * run.work["gridrec_flops"] / (window * run.peaks["flops_per_s"])
