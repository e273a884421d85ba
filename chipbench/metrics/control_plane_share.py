"""Dispatcher: share of the traced window in which the host was inside
none of the benchmark's spans around the calls into ``JaxTrainer``
(``run_stage*``, ``run_chain*``, ``evaluate``)."""


def read(view):
    if view.window_s <= 0:
        return None
    return 1.0 - view.span_s / view.window_s
