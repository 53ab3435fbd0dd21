"""throughput_msgs_s: messages whose results became ready on the device
inside the window, divided by the window's length."""
import numpy as np


def read(run):
    return float(np.count_nonzero(run.done <= run.close)) / run.seconds
