"""Weights from ``--seed``: one jitted call on the device, in the dtype the
configuration keeps them in.

The benchmark draws the weights, hands them to the program (installed into
its parameter tree by name) and to the plain reference. Neither side makes
a weight of its own, and the reference takes nothing the program has made.

A spec is ``(name, shape, mean, std)`` with ``name = "<vertex>/<param>"``;
each reference module lists its model's specs. Leaf ``i`` is drawn from
``fold_in(key(seed), i)`` with the default (threefry) generator, so the
same seed gives the same values on every backend.
"""

from __future__ import annotations

import functools
from typing import Dict, Sequence, Tuple

import numpy as np

Spec = Tuple[str, Tuple[int, ...], float, float]


def seed_key_data(seed: int) -> np.ndarray:
    """Raw threefry key data for any non-negative whole seed (the
    driver's seeds pass 2**31, which a 32-bit ``PRNGKey(seed)`` refuses)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                    np.uint32)


def draw_leaves(specs: Sequence[Spec], key_data, dtype):
    """Traceable body: every leaf of ``specs`` as a flat dict."""
    import jax
    import jax.numpy as jnp
    key = jax.random.wrap_key_data(jnp.asarray(key_data, jnp.uint32))
    out = {}
    for i, (name, shape, mean, std) in enumerate(specs):
        z = jax.random.normal(jax.random.fold_in(key, i), shape,
                              jnp.float32)
        out[name] = (mean + std * z).astype(dtype)
    return out


def make_weights(specs: Sequence[Spec], seed: int, dtype,
                 sharding=None) -> Dict[str, "jax.Array"]:
    """All leaves in one jitted dispatch (optionally placed by
    ``sharding``, e.g. replicated over a mesh)."""
    specs = tuple((n, tuple(s), float(m), float(d)) for n, s, m, d in specs)
    return _drawer(specs, np.dtype(dtype).name, sharding)(
        seed_key_data(seed))


@functools.lru_cache(maxsize=8)
def _drawer(specs, dtype_name: str, sharding):
    """One traced program per (model, dtype): seeds are its argument."""
    import jax
    return jax.jit(lambda kd: draw_leaves(specs, kd, dtype_name),
                   out_shardings=sharding)


def as_tree(flat: Dict[str, "jax.Array"]) -> Dict[str, dict]:
    """``{"vertex/param": a}`` -> ``{"vertex": {"param": a}}``, the layout
    of the program's parameter tree."""
    tree: Dict[str, dict] = {}
    for name, a in flat.items():
        vertex, param = name.split("/")
        tree.setdefault(vertex, {})[param] = a
    return tree


def check_tree_matches(flat, program_tree) -> None:
    """The program's own parameter tree must hold exactly the leaves the
    reference lists, shape for shape: otherwise the two sides would not be
    the same model."""
    want = {n: tuple(a.shape) for n, a in flat.items()}
    got = {}
    for vertex, leaves in program_tree.items():
        for param, a in (leaves or {}).items():
            got[f"{vertex}/{param}"] = tuple(a.shape)
    if want != got:
        missing = sorted(set(want) - set(got))[:5]
        extra = sorted(set(got) - set(want))[:5]
        differ = sorted(n for n in set(want) & set(got)
                        if want[n] != got[n])[:5]
        raise RuntimeError(
            "the program's parameter tree is not the reference's model: "
            f"missing {missing}, unexpected {extra}, shapes differ "
            f"{[(n, want[n], got[n]) for n in differ]}")
