"""Model FLOPs of one training step of mamba2-370m, from its shapes.

Matmul FLOPs only, at 2 per multiply-add: the in- and out-projections, the
SSD chunked scan as the trainer computes it (chunk length ``chunk_size``:
C.B per chunk, the masked diagonal blocks, the chunk states and the
off-diagonal outputs) and the tied LM head. The input-embedding gather, the
convolution and the elementwise work are not counted. Training is three
forward passes (forward, and backward to activations and to weights);
recomputation under remat is not counted.
"""


def forward_per_token(cfg: dict) -> float:
    d, e, p = cfg["d_model"], cfg["expand"], cfg["headdim"]
    n, q = cfg["d_state"], cfg["chunk_size"]
    di = e * d
    h = di // p
    mult = cfg["pad_vocab_size_multiple"]
    vocab = -(-cfg["vocab_size"] // mult) * mult
    proj = 2 * d * (2 * di + 2 * n + h) + 2 * di * d
    ssd = 2 * q * n + 2 * h * q * p + 2 * h * p * n + 2 * h * p * n
    return cfg["n_layer"] * (proj + ssd) + 2 * d * vocab


def train_flops(cfg: dict, *, batch: int, seq: int) -> float:
    return 3.0 * forward_per_token(cfg) * batch * seq
