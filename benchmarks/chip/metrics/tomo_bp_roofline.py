"""tomo_bp_roofline: the tomo backprojection kernel's share of its roofline.

Each kernel event in the trace (``backproject_pallas`` in its op name: the
single-frame and the stacked kernel) is attributed to the batch whose
``bench.process`` span began last before it. Its least time is its batch's
real frames times the larger of (operations / peak FLOP/s) and (bytes /
peak HBM bytes/s), with the algorithm's counts from the kind's work
function: 9 operations a pixel-angle interpolation, sinogram in and image
out. Padded stack rows and the window of bins the kernel sweeps are not
counted, so they read as waste. The share is the summed least time over
the summed kernel time. At the paper's sizes the operations bound binds.
"""
PATTERN = r"backproject_pallas"


def least_seconds(work: dict, peaks: dict, frames: int) -> float:
    return frames * max(work["bp_flops"] / peaks["flops_per_s"],
                        work["bp_bytes"] / peaks["hbm_bytes_per_s"])


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    events = [(dt, b) for dt, b in run.trace.kernel_events(PATTERN) if b is not None]
    if not events:
        return None
    least = sum(least_seconds(run.work, run.peaks, len(run.batches[b].messages))
                for _, b in events)
    return 100.0 * least / sum(dt for dt, _ in events)
