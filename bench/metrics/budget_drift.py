"""Scheduler: measured over predicted iteration seconds of the window's
iterations -- how far the calibrated latency model that sizes the token
budget is off (``RealEngine.measured_iter_seconds`` /
``predicted_iter_seconds``)."""


def read(r):
    c0, c1 = r.counters
    predicted = c1["predicted_s"] - c0["predicted_s"]
    if predicted <= 0:
        return None
    return (c1["measured_s"] - c0["measured_s"]) / predicted
