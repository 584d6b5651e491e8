"""Share of the window spent in Python GC pauses (sum of FlushRecord.gc_s
/ window), in percent. The window's first flush is left out, as its
pauses reach back before the window. None where the records carry no GC
time."""


def read(obs):
    fl = obs.get("flushes")
    if not fl or not hasattr(fl[0], "gc_s"):
        return None
    return sum(f.gc_s for f in fl[1:]) / obs["window_s"] * 100.0
