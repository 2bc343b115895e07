"""Time a collective runs on a chip while no compute operation does, over
the traced window."""


def read(obs, params, ctx):
    red = obs["trace"]
    if not red["collective_s"]:
        return None
    return 100.0 * red["collective_exposed_s"] / red["window_s"]
