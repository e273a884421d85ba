"""Stage tree: trial-steps delivered to tuners per member-step run."""


def read(view):
    if not view.member_steps:
        return None
    return view.trial_steps / view.member_steps
