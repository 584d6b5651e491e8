"""XLA compiles and compile-cache loads between the window's flushes
(sum of FlushRecord.compiles). The window's first flush is left out, as
its count reaches back before the window. None where the records carry no
count."""


def read(obs):
    fl = obs.get("flushes")
    if not fl or not hasattr(fl[0], "compiles"):
        return None
    return sum(f.compiles for f in fl[1:])
