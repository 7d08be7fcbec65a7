"""Weights and train state made from the seed, owned by the benchmark.

The program under test and the plain reference both start from these
arrays, and neither makes them: the parameters are laid out as the
program stores them (the configuration's module under `layouts/` says
how), drawn leaf by leaf from `--seed` in one jitted call on the device.
`delta_norms` draws the same leaves again inside its own program, so the
change of the parameters after a few steps is measured without a second
copy of them in memory.
"""
from __future__ import annotations

import harness


def param_layout(cfg: dict) -> dict:
    """{path: (shape, std)} in the program's layout, from the module of
    `layouts/` that the configuration names.  std 0 means ones (norm
    scales); every other leaf is normal(0, std).  A leaf's index in this
    order is its fold-in index."""
    return harness.layout_module(cfg).param_layout(cfg)


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
