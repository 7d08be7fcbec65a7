"""Weights and train state made from the seed, owned by the benchmark.

The program under test and the plain reference both start from these
arrays, and neither makes them: a dense decoder's parameters are laid out
as the program stores them (one stacked leaf per kind over the layers),
drawn leaf by leaf from `--seed` in one jitted call on the device.
`delta_norms` draws the same leaves again inside its own program, so the
change of the parameters after a few steps is measured without a second
copy of them in memory.
"""
from __future__ import annotations

import math


def param_layout(cfg: dict) -> dict:
    """{path: (shape, std)} of a dense decoder in the program's layout.

    std 0 means ones (norm scales); every other leaf is normal(0, std).
    """
    L, d, H, K = cfg["n_layers"], cfg["d_model"], cfg["n_heads"], cfg["n_kv"]
    hd, ff, V = cfg["head_dim"], cfg["d_ff"], cfg["vocab"]
    g = "groups/b0_dense/"
    out = {
        "embed/tok": ((V, d), 0.02),
        "final_norm/scale": ((d,), 0.0),
        g + "ln1/scale": ((L, d), 0.0),
        g + "ln2/scale": ((L, d), 0.0),
        g + "attn/wq": ((L, d, H, hd), 1 / math.sqrt(d)),
        g + "attn/wk": ((L, d, K, hd), 1 / math.sqrt(d)),
        g + "attn/wv": ((L, d, K, hd), 1 / math.sqrt(d)),
        g + "attn/wo": ((L, H, hd, d), 1 / math.sqrt(H * hd)),
        g + "ffn/wi": ((L, d, ff), 1 / math.sqrt(d)),
        g + "ffn/wg": ((L, d, ff), 1 / math.sqrt(d)),
        g + "ffn/wo": ((L, ff, d), 1 / math.sqrt(ff)),
    }
    if not cfg["tie_embeddings"]:
        out["embed/unembed"] = ((d, V), 0.02)
    if cfg["qkv_bias"]:
        out[g + "attn/bq"] = ((L, H, hd), 0.02)
        out[g + "attn/bk"] = ((L, K, hd), 0.02)
        out[g + "attn/bv"] = ((L, K, hd), 0.02)
    return dict(sorted(out.items()))


def nest(flat: dict) -> dict:
    """{"a/b": x} -> {"a": {"b": x}}."""
    out: dict = {}
    for path, v in flat.items():
        *head, last = path.split("/")
        node = out
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return out


def flatten(tree: dict, prefix: str = "") -> dict:
    """{"a": {"b": x}} -> {"a/b": x}, in sorted key order."""
    out = {}
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            out.update(flatten(v, f"{prefix}{k}/"))
        else:
            out[f"{prefix}{k}"] = v
    return out


def seed_key(seed: int):
    """A PRNG key from any whole seed, 64-bit ones included."""
    import jax
    key = jax.random.PRNGKey(seed % (1 << 31))
    return jax.random.fold_in(key, (seed >> 31) % (1 << 31))


def _leaf(key, index: int, shape, std: float):
    import jax
    import jax.numpy as jnp
    if std == 0.0:
        return jnp.ones(shape, jnp.float32)
    k = jax.random.fold_in(key, index)
    return jax.random.normal(k, shape, jnp.float32) * std


def make_params(cfg: dict, key) -> dict:
    """Nested params drawn from `key` (call under jit)."""
    flat = {path: _leaf(key, i, shape, std)
            for i, (path, (shape, std)) in enumerate(param_layout(cfg).items())}
    return nest(flat)


def init_state_fn(cfg: dict, shardings):
    """jitted seed -> {"params", "opt": {"m", "v"}, "step"} (AdamW state)."""
    import jax
    import jax.numpy as jnp

    def init(seed_key_):
        params = make_params(cfg, seed_key_)
        zeros = jax.tree.map(jnp.zeros_like, params)
        return {"params": params,
                "opt": {"m": zeros, "v": jax.tree.map(jnp.zeros_like, params)},
                "step": jnp.zeros((), jnp.int32)}

    return jax.jit(init, out_shardings=shardings)


def leaf_norms(tree: dict) -> dict:
    """{path: float32 L2 norm} of a nested tree (call under jit)."""
    import jax.numpy as jnp
    return {p: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for p, x in flatten(tree).items()}


def delta_norms(cfg: dict, params: dict, key) -> dict:
    """{path: ||params - initial params||}; the initial leaves are drawn
    again here (call under jit)."""
    import jax.numpy as jnp
    layout = param_layout(cfg)
    flat = flatten(params)
    out = {}
    for i, (path, (shape, std)) in enumerate(layout.items()):
        d = flat[path].astype(jnp.float32) - _leaf(key, i, shape, std)
        out[path] = jnp.sqrt(jnp.sum(jnp.square(d)))
    return out
