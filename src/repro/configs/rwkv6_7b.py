"""rwkv6-7b [ssm]: 32L d=4096 (attention-free) d_ff=14336 vocab=65536.

Finch — data-dependent per-channel decay, 64 heads of size 64, DDLerp
token-shift, squared-ReLU channel mix.  Bounded state ⇒ runs long_500k.
LayerNorm epsilon 1e-5 (``layer_norm_epsilon``; GroupNorm's is that
times ``head_size_divisor`` 8 squared).  The low-rank widths are the
published code's for d_model 4096: 64 for the token mixes, 128 for the
decay (32 and 64 at the smaller Finch sizes).
[arXiv:2404.05892; hf RWKV/v6-Finch-7B-HF]
"""

from repro.models.base import ArchConfig, RwkvConfig

CONFIG = ArchConfig(
    name="rwkv6-7b",
    family="rwkv6",
    n_layers=32,
    d_model=4096,
    n_heads=64,                 # d_model / head_size
    n_kv_heads=64,
    head_dim=64,
    d_ff=14336,
    vocab_size=65536,
    rms_eps=1e-5,
    mlp_activation="relu2",
    rwkv=RwkvConfig(head_size=64, lora_mix=64, lora_decay=128),
)


def reduced() -> ArchConfig:
    return CONFIG.with_(n_layers=3, d_model=128, n_heads=4, n_kv_heads=4,
                        head_dim=32, d_ff=256, vocab_size=512,
                        rwkv=RwkvConfig(head_size=32, lora_mix=8,
                                        lora_decay=8))
