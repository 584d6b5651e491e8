"""GAQ core: the paper's contribution as composable JAX modules."""
from .quantizers import (
    QuantConfig,
    abs_max_scale,
    dequantize,
    fake_quant,
    fake_quant_ste,
    pack_int4,
    qmax,
    quantize,
    unpack_int4,
)
from .codebook import (
    covering_radius,
    fibonacci_snap,
    fibonacci_sphere,
    make_codebook,
    nearest_code,
    nearest_fibonacci_code,
    octahedral_sphere,
    quantize_direction,
)
from .mddq import (MDDQConfig, mddq_decode, mddq_encode, mddq_fake_quant,
                   snap_path)
from .ste import geometric_ste_direction, identity_ste
from .lee import lee, lee_regularizer, random_rotation, random_rotations
from .attention_norm import (
    cosine_attention_logits,
    l2_normalize,
    robust_attention_weights,
)

__all__ = [
    "QuantConfig", "abs_max_scale", "dequantize", "fake_quant",
    "fake_quant_ste", "pack_int4", "qmax", "quantize", "unpack_int4",
    "covering_radius", "fibonacci_snap", "fibonacci_sphere", "make_codebook",
    "nearest_code", "nearest_fibonacci_code", "octahedral_sphere",
    "quantize_direction",
    "MDDQConfig", "mddq_decode", "mddq_encode", "mddq_fake_quant",
    "snap_path",
    "geometric_ste_direction", "identity_ste",
    "lee", "lee_regularizer", "random_rotation", "random_rotations",
    "cosine_attention_logits", "l2_normalize", "robust_attention_weights",
]
