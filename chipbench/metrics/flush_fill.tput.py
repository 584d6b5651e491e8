"""Mean share of the scheduler's max_batch that a flush carried, in
percent."""


def read(obs):
    fl = obs.get("flushes")
    if not fl:
        return None
    return sum(f.n_requests for f in fl) / len(fl) / obs["max_batch"] * 100.0
