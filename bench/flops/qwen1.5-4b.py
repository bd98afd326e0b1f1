"""Model FLOPs of one training step of Qwen1.5-4B, from its shapes.

Matmul FLOPs only, at 2 per multiply-add: the q/k/v and output projections,
the SwiGLU MLP, the attention scores and their product with the values over
the whole sequence (the trainer's chunked attention computes every chunk,
masked, so the S^2 terms are counted whole), and the untied LM head. The
input-embedding gather and the elementwise work are not counted. Training
is three forward passes; recomputation under remat is not counted.
"""


def forward_per_token(cfg: dict, seq: int) -> float:
    d, h, kv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, f = d // h, cfg["intermediate_size"]
    proj = 2 * d * (h + 2 * kv) * hd + 2 * h * hd * d
    mlp = 3 * 2 * d * f
    attn = 2 * 2 * seq * h * hd
    return cfg["num_hidden_layers"] * (proj + mlp + attn) + 2 * d * cfg["vocab_size"]


def train_flops(cfg: dict, *, batch: int, seq: int) -> float:
    return 3.0 * forward_per_token(cfg, seq) * batch * seq
