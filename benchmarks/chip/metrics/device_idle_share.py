"""device_idle_share: 100 * (1 - device busy / traced window), where busy is
the union of the intervals in which an XLA op ran on the chip (trace's
``XLA Ops`` line), averaged over the chips used."""


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0 or not t.ops:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
