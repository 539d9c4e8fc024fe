"""Device memory of the compiled train step on each chip, from its
``memory_analysis``: arguments, outputs and temporaries less what the
outputs alias, in GiB."""


def read(m):
    return None if m.step_bytes is None else m.step_bytes / 2 ** 30
