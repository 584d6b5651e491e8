"""Least time the chips could take for the model work delivered in the
window (contractions of real atoms and edges, forward and forces; the
int8 peak for forward quantized matmuls, the bf16 peak for the rest),
over the window's wall time on the cell's chips, in percent."""


def read(obs):
    if obs.get("least_s") is None:
        return None
    return obs["least_s"] / (obs["window_s"] * obs["chips"]) * 100.0
