"""h2o-danube-1.8b — llama+mistral mix with sliding-window attention.

[arXiv:2401.16818; hf]  24L d_model=2560 32H (kv=8) d_ff=6912
vocab=32000, SWA window 4096 → the KV cache is bounded by the window,
which is what makes the ``long_500k`` decode shape runnable.

Presets (``get_config(arch, preset=...)``):

* ``one_chip`` — one TPU v5e chip's share of a training deployment that
  runs the 24 layers as 6 pipeline stages of 4, one chip per stage.  Every
  width is the published one; only the depth is cut, to the 4 layers one
  stage holds.  The model is dense, so one layer is one whole period of its
  layer pattern.  Each stage holds the whole (unsliced) vocabulary here,
  which over-counts the embedding and head for the middle stages.
"""
from repro.models.common import ModelConfig

CONFIG = ModelConfig(
    arch_id="h2o-danube-1.8b", family="dense",
    n_layers=24, d_model=2560, vocab=32000,
    attn_type="gqa", n_heads=32, n_kv_heads=8, head_dim=80,
    d_ff=6912, window=4096,
    tie_embeddings=False,
)

TINY = CONFIG.replace(
    n_layers=2, d_model=64, vocab=512, n_heads=4, n_kv_heads=2,
    head_dim=16, d_ff=128, window=32,
)

ONE_CHIP = CONFIG.replace(n_layers=4)

PRESETS = {
    "one_chip": {
        "config": ONE_CHIP,
        "source": "arXiv:2401.16818 (H2O-Danube-1.8B), Table 1",
        "deployment": "24 layers as 6 pipeline stages of 4 layers, "
                      "one chip per stage",
        "reduced": {"n_layers": (24, 4)},
    },
}
