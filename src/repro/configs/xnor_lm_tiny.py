"""XNOR LM (binarized transformer) serving configs.

Not one of the published-architecture table entries (``ARCH_MODULES``):
this is the repo's own binary workload — `models/xnor_lm.py` — registered
under ``BINARY_LM_MODULES`` so `launch/serve.py --arch xnor-lm-tiny`
resolves here. CONFIG is a small-but-real shape; SMOKE_CONFIG is the CPU
test/CI shape.
"""
from repro.models.xnor_lm import XnorLMConfig

CONFIG = XnorLMConfig(vocab_size=256, d_model=128, n_layers=4, n_heads=4,
                      d_ff=256, max_len=256)

SMOKE_CONFIG = XnorLMConfig(vocab_size=64, d_model=64, n_layers=2, n_heads=2,
                            d_ff=96, max_len=64)

SHAPES = [(1, 16), (4, 32)]
