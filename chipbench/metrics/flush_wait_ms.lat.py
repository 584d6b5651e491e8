"""Mean time the oldest request of a flush waited in its queue
(FlushRecord.wait_s), in ms."""


def read(obs):
    fl = obs.get("flushes")
    if not fl:
        return None
    return sum(f.wait_s for f in fl) / len(fl) * 1e3
