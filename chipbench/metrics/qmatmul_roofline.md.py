"""Least time of every quantized-matmul launch in the traced segments
(unpadded shapes, the larger of the int8 compute and the HBM bound) over
the summed device time of the W8A8 and W4A8 kernels, in percent."""

KERNELS = ("w8a8_matmul", "w4a8_matmul")   # op names in a TPU trace


def read(obs):
    tr = obs.get("trace")
    if tr is None:
        return None
    spent = sum(tr.kernel_s.get(k, 0.0) for k in KERNELS)
    if spent <= 0.0 or not obs.get("qmatmul_least_s"):
        return None
    return obs["qmatmul_least_s"] / spent * 100.0
