"""Data plane: device busy time of the traced window (the union of the
intervals in which an operation ran) per member-step run in it."""


def read(view):
    if not view.member_steps or view.busy_s <= 0:
        return None
    return view.busy_s * 1e3 / view.member_steps
