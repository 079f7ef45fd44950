"""Share of the traced window in which no operation ran on the device:
100 * (1 - union of op intervals / window), averaged over the chips."""


def read(obs, name):
    tr = obs.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
