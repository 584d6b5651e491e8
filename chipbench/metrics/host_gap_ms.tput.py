"""Mean worker time from one flush's device sync to the next flush's
dispatch (FlushRecord.gap_s), in ms: the stretch in which the device holds
no work of the serving worker's. The window's first flush is left out, as
its gap reaches back before the window. None where the records carry no
gap (a program without the field)."""


def read(obs):
    fl = obs.get("flushes")
    if not fl or len(fl) < 2 or not hasattr(fl[0], "gap_s"):
        return None
    rest = fl[1:]
    return sum(f.gap_s for f in rest) / len(rest) * 1e3
