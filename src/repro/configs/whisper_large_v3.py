"""whisper-large-v3 — Whisper's flagship, served at its published widths.
[openai/whisper-large-v3 config.json]

32 encoder + 32 decoder layers at d_model 1280, 20 heads of 64, d_ff 5120,
128 mel bins, vocab 51866: 1.54B parameters. Same encoder-decoder layout
and conv frontend stub as whisper-tiny/small.
"""
from repro.configs.base import ModelConfig, reduced

CONFIG = ModelConfig(
    name="whisper-large-v3",
    family="audio",
    num_layers=32,              # decoder layers
    num_encoder_layers=32,
    d_model=1280,
    num_heads=20,
    num_kv_heads=20,
    head_dim=64,
    d_ff=5120,
    vocab_size=51_866,
    vocab_pad=6,              # -> %16==0 so the readout shards on the model axis
    norm="layernorm",
    act="gelu",
    qkv_bias=True,
    pos_embedding="learned",
    tie_embeddings=True,
    is_encoder_decoder=True,
    encoder_ctx=1500,
    n_mels=128,
    quant="q8_0",
)

SMOKE = reduced(CONFIG)
