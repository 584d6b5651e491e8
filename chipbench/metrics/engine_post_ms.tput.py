"""Mean host time the engine spends on a flush after the device sync:
rows into results (unpack_s) plus the guardrail pass (guard_s) of
FlushRecord, in ms. None where the records carry neither field."""


def read(obs):
    fl = obs.get("flushes")
    if not fl or not hasattr(fl[0], "unpack_s"):
        return None
    return sum(f.unpack_s + f.guard_s for f in fl) / len(fl) * 1e3
