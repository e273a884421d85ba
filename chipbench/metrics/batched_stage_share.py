"""Dispatcher: share of the stages run in the window that ran inside a
batched sibling group (``EngineStats.batched_stages / stages_run``)."""


def read(view):
    if not view.counters["stages_run"]:
        return None
    return view.counters["batched_stages"] / view.counters["stages_run"]
