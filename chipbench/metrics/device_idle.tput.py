"""Share of the traced window in which no operation ran on the devices
(mean over the cell's devices), in percent."""


def read(obs):
    tr = obs.get("trace")
    return None if tr is None else tr.idle_share * 100.0
