"""Least time of every edge-softmax launch in the traced segments (real
edges, the larger of the bf16 compute and the HBM bound) over the summed
device time of the edge-softmax kernel, in percent."""


def read(obs):
    tr = obs.get("trace")
    if tr is None:
        return None
    spent = tr.kernel_s.get("_edge_softmax_kernel", 0.0)
    if spent <= 0.0 or not obs.get("edge_softmax_least_s"):
        return None
    return obs["edge_softmax_least_s"] / spent * 100.0
