"""Launchers: mesh construction and the train/serve drivers."""
import time


def instrumented(app, bus, label: str):
    """Wrap a MASA app's ``process`` so every train/serve step publishes
    its wall time and token throughput to the MetricsBus — the signals a
    demand estimator (or a human watching ``scheduler.*``) needs to size
    the pilot. Shared by the train and serve drivers."""

    def process(state, msgs):
        t0 = time.monotonic()
        items0 = app.stats.items
        state = app.process(state, msgs)
        dt = time.monotonic() - t0
        toks = app.stats.items - items0
        bus.publish(f"{label}.step_time", dt, stream=label)
        bus.publish(f"{label}.tokens_per_sec",
                    toks / dt if dt > 0 else 0.0, stream=label)
        return state

    return process
