"""Checkpoint plane: the synchronous host slice of a boundary save
(``EngineStats.ckpt_save_seconds / ckpt_saves``)."""


def read(view):
    if not view.counters["ckpt_saves"]:
        return None
    return view.counters["ckpt_save_seconds"] * 1e3 / \
        view.counters["ckpt_saves"]
