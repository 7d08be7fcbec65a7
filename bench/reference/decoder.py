"""Plain reference of a dense decoder's training steps, in float32.

It follows the published Qwen2 / GLM-4 block as far as the program runs it
(the configuration file lists where the program departs): token embedding,
per layer RMSNorm -> grouped-query causal attention with q/k/v biases and
rotary positions -> residual -> RMSNorm -> SwiGLU MLP -> residual, a final
RMSNorm and the (tied or separate) output head.  The loss is next-token
cross-entropy over every position but the last, plus 1e-4 times the mean
squared log-partition (z-loss), averaged over the batch's tokens.  The
optimizer is AdamW (decoupled weight decay, bias correction) after
clipping the global gradient norm, with a linear warm-up of the learning
rate.

It imports nothing of the program.  Every matmul runs at
`Precision.HIGHEST`.  Rows are processed one at a time inside a scan, so a
batch's activations never sit on the device at once.
"""
from __future__ import annotations

import functools

from weights import delta_norms, leaf_norms, make_params


def _rms(x, scale, eps=1e-6):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _rope(x, theta):
    """x: (S, n, hd); halves layout, positions 0..S-1."""
    import jax.numpy as jnp
    S, hd = x.shape[0], x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq      # (S, half)
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mm(eq, a, b):
    import jax
    import jax.numpy as jnp
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _layer(cfg, x, p):
    import jax
    import jax.numpy as jnp
    H, K, hd = cfg["n_heads"], cfg["n_kv"], cfg["head_dim"]
    S = x.shape[0]
    a = p["attn"]
    h = _rms(x, p["ln1"]["scale"])
    q = _mm("sd,dhk->shk", h, a["wq"])
    k = _mm("sd,dhk->shk", h, a["wk"])
    v = _mm("sd,dhk->shk", h, a["wv"])
    if "bq" in a:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = _rope(q, cfg["rope_theta"]).reshape(S, K, H // K, hd)
    k = _rope(k, cfg["rope_theta"])
    s = _mm("qkgh,tkh->kgqt", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    o = _mm("kgqt,tkh->qkgh", jax.nn.softmax(s, axis=-1), v)
    x = x + _mm("shk,hkd->sd", o.reshape(S, H, hd), a["wo"])
    f = p["ffn"]
    h2 = _rms(x, p["ln2"]["scale"])
    gate = jax.nn.silu(_mm("sd,df->sf", h2, f["wg"]))
    up = _mm("sd,df->sf", h2, f["wi"])
    return x + _mm("sf,fd->sd", gate * up, f["wo"]), None


def row_sums(cfg, params, tokens):
    """(cross-entropy sum, z sum) of one row of token ids (S,)."""
    import jax
    import jax.numpy as jnp
    x = params["embed"]["tok"][tokens]
    x, _ = jax.lax.scan(functools.partial(_layer, cfg), x,
                        params["groups"]["b0_dense"])
    x = _rms(x, params["final_norm"]["scale"])
    head = params["embed"].get("unembed")
    if head is None:
        head = params["embed"]["tok"].T
    logits = _mm("sd,dv->sv", x, head)[:-1]
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, tokens[1:, None], axis=-1)[:, 0]
    return jnp.sum(lse - ll), jnp.sum(lse * lse)


def loss_and_grads(cfg, params, rows):
    """Mean loss and its gradient over a batch of rows (B, S)."""
    import jax
    import jax.numpy as jnp
    n = rows.shape[0] * (rows.shape[1] - 1)

    def row_loss(p, toks):
        ce, z = row_sums(cfg, p, toks)
        return (ce + 1e-4 * z) / n

    grad = jax.value_and_grad(row_loss)

    def body(acc, toks):
        loss, g = grad(params, toks)
        return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], g)), None

    zeros = jax.tree.map(jnp.zeros_like, params)
    (loss, grads), _ = jax.lax.scan(body, (jnp.zeros(()), zeros), rows)
    return loss, grads


def adamw_step(opt, params, grads, m, v, step):
    """Clip to the global norm, then one AdamW update at 0-based `step`."""
    import jax
    import jax.numpy as jnp
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    grads = jax.tree.map(
        lambda g: g * jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm,
                                                                     1e-9)),
        grads)
    t = step + 1.0
    lr = jnp.where(t < opt["warmup_steps"],
                   opt["learning_rate"] * t / opt["warmup_steps"],
                   opt["learning_rate"])
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, m_, v_: p - lr * ((m_ / c1) / (jnp.sqrt(v_ / c2) + eps)
                                    + wd * p),
        params, m, v)
    return params, m, v, grads


def train_readings(cfg: dict, opt: dict, key, batches) -> dict:
    """Run len(batches) steps from the seeded weights; return the numbers
    the comparison reads: each step's loss, the per-leaf norms of the first
    (clipped) gradient, and the per-leaf norms of the parameters' change.

    `batches`: (T, B, S) int32 token ids on the host.  Each step is its own
    jitted call, so the activations of one never meet the next's state.
    """
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        params = jax.jit(functools.partial(make_params, cfg))(key)
        lg = jax.jit(functools.partial(loss_and_grads, cfg))
        step_fn = jax.jit(functools.partial(adamw_step, opt),
                          donate_argnums=(0, 2, 3))
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        losses, g1 = [], None
        for t in range(len(batches)):
            loss, grads = lg(params, jnp.asarray(batches[t]))
            params, m, v, clipped = step_fn(params, grads, m, v,
                                            jnp.float32(t))
            del grads
            if g1 is None:
                g1 = jax.device_get(jax.jit(leaf_norms)(clipped))
            del clipped
            losses.append(float(loss))
        dp = jax.device_get(jax.jit(functools.partial(delta_norms, cfg))(
            params, key))
    return {"losses": losses,
            "grad": {k: float(x) for k, x in g1.items()},
            "update": {k: float(x) for k, x in dp.items()}}

