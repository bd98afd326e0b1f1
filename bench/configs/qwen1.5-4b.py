"""Plain reference of Qwen1.5-4B (Qwen2 architecture): weights from a seed,
and the training loss in float32.

Pre-RMSNorm decoder blocks: multi-head causal softmax attention (20 heads of
128, q/k/v with biases, rotary positions on the two halves of each head) and
a SwiGLU MLP, each added to the residual; a final RMSNorm and an untied LM
head. Attention is written out plainly, a block of queries at a time
(each query's scores against every key, masked above the diagonal), so that
one layer's scores fit beside the gradients.

The parameter tree uses the trainer's layout (names, stacked layers, and
norm weights stored as offsets from 1).
"""

import functools

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


def dims(cfg):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(d=d, h=h, hd=d // h, kv=cfg["num_key_value_heads"],
                f=cfg["intermediate_size"], layers=cfg["num_hidden_layers"],
                vocab=cfg["vocab_size"])


def init_params(key, cfg):
    m = dims(cfg)
    d, h, hd, kv, f, L, v = (m[k] for k in ("d", "h", "hd", "kv", "f", "layers", "vocab"))
    dt = jnp.bfloat16 if cfg["torch_dtype"] == "bfloat16" else jnp.float32
    ks = iter(jax.random.split(key, 16))

    def normal(shape):
        return (jax.random.normal(next(ks), shape, jnp.float32) * 0.02).astype(dt)

    blocks = {
        "bk": jnp.zeros((L, kv * hd), dt), "bq": jnp.zeros((L, h * hd), dt),
        "bv": jnp.zeros((L, kv * hd), dt),
        "ln1": jnp.zeros((L, d), dt), "ln2": jnp.zeros((L, d), dt),
        "w_down": normal((L, f, d)), "w_gate": normal((L, d, f)),
        "w_up": normal((L, d, f)),
        "wk": normal((L, d, kv * hd)), "wo": normal((L, h * hd, d)),
        "wq": normal((L, d, h * hd)), "wv": normal((L, d, kv * hd)),
    }
    return {"blocks": (blocks,), "embed": normal((v, d)),
            "final_norm": jnp.zeros((d,), dt), "lm_head": normal((d, v))}


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def rope(x, positions, theta):
    """x [B,S,H,D]: rotate the pair (x[i], x[i + D/2]) by position * theta^(-2i/D)."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, x, positions, m, theta, q):
    b, s, _ = x.shape
    qh = (q(x) @ q(p["wq"]) + p["bq"]).reshape(b, s, m["h"], m["hd"])
    kh = (q(x) @ q(p["wk"]) + p["bk"]).reshape(b, s, m["kv"], m["hd"])
    vh = (q(x) @ q(p["wv"]) + p["bv"]).reshape(b, s, m["kv"], m["hd"])
    qh, kh = rope(qh, positions, theta), rope(kh, positions, theta)
    rep = m["h"] // m["kv"]
    kh, vh = q(jnp.repeat(kh, rep, axis=2)), q(jnp.repeat(vh, rep, axis=2))
    rows = min(QUERY_BLOCK, s)

    @jax.checkpoint
    def block(args):
        qb, start = args                                    # [B,R,H,D], first row
        scores = jnp.einsum("bqhd,bkhd->bhqk", q(qb), kh) / jnp.sqrt(jnp.float32(m["hd"]))
        causal = (start + jnp.arange(rows))[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", q(probs), vh)

    qb = qh.reshape(b, s // rows, rows, m["h"], m["hd"]).transpose(1, 0, 2, 3, 4)
    out = jax.lax.map(block, (qb, jnp.arange(0, s, rows)))
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, s, -1)
    return q(out) @ q(p["wo"])


def mlp(p, x, q):
    return q(jax.nn.silu(q(x) @ q(p["w_gate"])) * (q(x) @ q(p["w_up"]))) @ q(p["w_down"])


def cross_entropy(h, w_out, labels, q, rows=256):
    """Mean next-token cross-entropy, the logits made ``rows`` positions at a time."""
    bs, s, d = h.shape
    hc = h.reshape(bs, s // rows, rows, d).transpose(1, 0, 2, 3)
    yc = labels.reshape(bs, s // rows, rows).transpose(1, 0, 2)

    @jax.checkpoint
    def chunk(args):
        hh, yy = args
        logits = q(hh) @ q(w_out.astype(jnp.float32))
        lse = jax.scipy.special.logsumexp(logits, -1)
        tgt = jnp.take_along_axis(logits, yy[..., None], -1)[..., 0]
        return jnp.sum(lse - tgt)

    return jnp.sum(jax.lax.map(chunk, (hc, yc))) / (bs * s)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rows(table, ids, rows_of):
    return table[ids].astype(jnp.float32)


def _rows_fwd(table, ids, rows_of):
    return _rows(table, ids, rows_of), ids


def _rows_bwd(rows_of, ids, ct):
    n, dtype = rows_of
    grad = jnp.zeros((n, ct.shape[-1]), jnp.float32).at[ids.reshape(-1)].add(
        ct.reshape(-1, ct.shape[-1]))
    return grad.astype(dtype), None


_rows.defvjp(_rows_fwd, _rows_bwd)


def embed_rows(table, ids):
    """float32 rows of a stored table; the gradient is summed in float32 and
    rounded once to the table's dtype."""
    return _rows(table, ids, (table.shape[0], table.dtype))


def f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


def loss(params, batch, cfg, q):
    """Mean cross-entropy on one batch, in float32 from the stored weights."""
    m = dims(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = embed_rows(params["embed"], batch["inputs"])
    positions = batch["positions"]

    @jax.checkpoint
    def layer(h, p):
        p = f32(p)
        h = h + attention(p, rmsnorm(h, p["ln1"], eps), positions, m, theta, q)
        return h + mlp(p, rmsnorm(h, p["ln2"], eps), q), None

    h, _ = jax.lax.scan(layer, h, params["blocks"][0])
    h = rmsnorm(h, params["final_norm"].astype(jnp.float32), eps)
    return cross_entropy(h, params["lm_head"], batch["labels"], q,
                         rows=min(256, h.shape[1]))
