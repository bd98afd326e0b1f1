"""Plain reference of mamba2-370m (arXiv:2405.21060): weights from a seed,
and the training loss in float32.

A stack of 48 Mamba-2 mixer blocks with pre-RMSNorm residuals, tied
embeddings and a final RMSNorm. One mixer: projections to z, x, B, C and dt;
a depthwise causal convolution with SiLU over x and over (B, C); the SSD
recurrence ``h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t``, ``y_t = C_t h_t +
D x_t`` per head, computed by the paper's minimal chunked form
(``ssd_minimal_discrete``: diagonal blocks as masked matrices, chunk states
combined by a segment-sum over chunks); a gated RMSNorm
``rmsnorm(y * silu(z))``; the out-projection.

The parameter tree uses the trainer's layout (names, stacked layers, and
norm weights stored as offsets from 1). Departures from the source, all
listed in the configuration file: the projections are separate matrices (the
same maps as the packed in_proj), and the gated norm uses
``gated_norm_epsilon``.
"""

import jax
import jax.numpy as jnp

CHUNK = 256   # the source's chunk_size; any chunk gives the same sums


def dims(cfg):
    d, e, p = cfg["d_model"], cfg["expand"], cfg["headdim"]
    mult = cfg["pad_vocab_size_multiple"]
    vocab = -(-cfg["vocab_size"] // mult) * mult
    return dict(d=d, di=e * d, n=cfg["d_state"], h=e * d // p, p=p,
                k=cfg["d_conv"], layers=cfg["n_layer"], vocab=vocab)


def init_params(key, cfg):
    m = dims(cfg)
    d, di, n, h, k, L = m["d"], m["di"], m["n"], m["h"], m["k"], m["layers"]
    dt = jnp.bfloat16 if cfg["dtype"] == "bfloat16" else jnp.float32
    ks = iter(jax.random.split(key, 16))

    def unif(shape, bound):
        return jax.random.uniform(next(ks), shape, jnp.float32, -bound, bound)

    dt0 = jnp.exp(jax.random.uniform(next(ks), (L, h), jnp.float32,
                                     jnp.log(1e-3), jnp.log(1e-1)))
    dt0 = jnp.maximum(dt0, 1e-4)
    blocks = {
        "ln1": jnp.zeros((L, d), dt),
        "ssm_A_log": jnp.log(jax.random.uniform(next(ks), (L, h), jnp.float32, 1.0, 16.0)),
        "ssm_D": jnp.ones((L, h), jnp.float32),
        "ssm_conv_b_bc": unif((L, 2 * n), k ** -0.5).astype(dt),
        "ssm_conv_b_x": unif((L, di), k ** -0.5).astype(dt),
        "ssm_conv_bc": unif((L, k, 2 * n), k ** -0.5).astype(dt),
        "ssm_conv_x": unif((L, k, di), k ** -0.5).astype(dt),
        "ssm_dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
        "ssm_norm": jnp.zeros((L, di), dt),
        "ssm_w_bc": unif((L, d, 2 * n), d ** -0.5).astype(dt),
        "ssm_w_dt": unif((L, d, h), d ** -0.5).astype(dt),
        "ssm_w_out": (unif((L, di, d), di ** -0.5) / L ** 0.5).astype(dt),
        "ssm_w_x": unif((L, d, di), d ** -0.5).astype(dt),
        "ssm_w_z": unif((L, d, di), d ** -0.5).astype(dt),
    }
    embed = (jax.random.normal(next(ks), (m["vocab"], d), jnp.float32) * 0.02).astype(dt)
    return {"blocks": (blocks,), "embed": embed, "final_norm": jnp.zeros((d,), dt)}


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def causal_conv(x, w, b):
    """Depthwise causal convolution: out[t] = sum_i w[i] x[t - K + 1 + i]."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    s = x.shape[1]
    return sum(xp[:, i:i + s] * w[i] for i in range(k)) + b


def segsum(x):
    """out[..., i, j] = sum_{j < r <= i} x[..., r]; -inf above the diagonal."""
    t = x.shape[-1]
    cs = jnp.cumsum(x, -1)
    seg = cs[..., :, None] - cs[..., None, :]
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), seg, -jnp.inf)


def ssd(x, a, b, c, q):
    """x [B,S,H,P] (already times dt), a [B,S,H] (dt * A), b, c [B,S,N]."""
    bs, s, h, p = x.shape
    ln = min(CHUNK, s)
    nc = s // ln
    x = x.reshape(bs, nc, ln, h, p)
    b = b.reshape(bs, nc, ln, -1)
    c = c.reshape(bs, nc, ln, -1)
    a = a.reshape(bs, nc, ln, h).transpose(0, 3, 1, 2)             # [B,H,C,L]
    a_cum = jnp.cumsum(a, -1)
    lmat = jnp.exp(segsum(a))                                       # [B,H,C,L,L]
    y_diag = jnp.einsum("bcln,bcsn,bhcls,bcshp->bclhp", q(c), q(b), q(lmat), q(x))
    decay_states = jnp.exp(a_cum[..., -1:] - a_cum)                 # [B,H,C,L]
    states = jnp.einsum("bcln,bhcl,bclhp->bchpn", q(b), q(decay_states), q(x))
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    decay_chunk = jnp.exp(segsum(jnp.pad(a_cum[..., -1], ((0, 0), (0, 0), (1, 0)))))
    states = jnp.einsum("bhzc,bchpn->bzhpn", q(decay_chunk), q(states))[:, :-1]
    y_off = jnp.einsum("bcln,bchpn,bhcl->bclhp", q(c), q(states), q(jnp.exp(a_cum)))
    return (y_diag + y_off).reshape(bs, s, h, p)


def mixer(p, u, m, gated_eps, q):
    bs, s, _ = u.shape
    z = q(u) @ q(p["ssm_w_z"])
    xr = q(u) @ q(p["ssm_w_x"])
    bc = q(u) @ q(p["ssm_w_bc"])
    dt = jax.nn.softplus(q(u) @ q(p["ssm_w_dt"]) + p["ssm_dt_bias"])
    x = jax.nn.silu(causal_conv(xr, p["ssm_conv_x"], p["ssm_conv_b_x"]))
    bc = jax.nn.silu(causal_conv(bc, p["ssm_conv_bc"], p["ssm_conv_b_bc"]))
    b, c = bc[..., :m["n"]], bc[..., m["n"]:]
    x = x.reshape(bs, s, m["h"], m["p"])
    a = -jnp.exp(p["ssm_A_log"])
    y = ssd(x * dt[..., None], dt * a, b, c, q) + p["ssm_D"][:, None] * x
    y = rmsnorm(y.reshape(bs, s, m["di"]) * jax.nn.silu(z), p["ssm_norm"], gated_eps)
    return q(y) @ q(p["ssm_w_out"])


def cross_entropy(h, w_out, labels, q, rows=256):
    """Mean next-token cross-entropy, the logits made ``rows`` positions at a time."""
    bs, s, d = h.shape
    hc = h.reshape(bs, s // rows, rows, d).transpose(1, 0, 2, 3)
    yc = labels.reshape(bs, s // rows, rows).transpose(1, 0, 2)

    @jax.checkpoint
    def chunk(args):
        hh, yy = args
        logits = q(hh) @ q(w_out)
        lse = jax.scipy.special.logsumexp(logits, -1)
        tgt = jnp.take_along_axis(logits, yy[..., None], -1)[..., 0]
        return jnp.sum(lse - tgt)

    return jnp.sum(jax.lax.map(chunk, (hc, yc))) / (bs * s)


def f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


def loss(params, batch, cfg, q):
    """Mean cross-entropy on one batch, in float32 from the stored weights."""
    m = dims(cfg)
    eps, gated_eps = cfg["norm_epsilon"], cfg["gated_norm_epsilon"]
    embed = params["embed"].astype(jnp.float32)
    h = embed[batch["inputs"]]

    @jax.checkpoint
    def layer(h, p):
        p = f32(p)
        return h + mixer(p, rmsnorm(h, p["ln1"], eps), m, gated_eps, q), None

    h, _ = jax.lax.scan(layer, h, params["blocks"][0])
    h = rmsnorm(h, params["final_norm"].astype(jnp.float32), eps)
    return cross_entropy(h, embed.T, batch["labels"], q,
                         rows=min(256, h.shape[1]))
