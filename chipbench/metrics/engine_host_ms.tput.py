"""Mean host time the engine spends on a flush before the device sync:
padding (prep_s) plus dispatch (dispatch_s) of FlushRecord, in ms."""


def read(obs):
    fl = obs.get("flushes")
    if not fl:
        return None
    return sum(f.prep_s + f.dispatch_s for f in fl) / len(fl) * 1e3
