"""Seeded weights, made on the device in one jitted call.

The same values go into the program's state (flattened, padded, in its
storage types) and into the reference (in logical shapes), so the two are
compared on equal weights while the reference takes nothing the program
made. Each leaf draws from its own key, folded from the seed and the leaf's
name, so a leaf's values do not depend on which other leaves exist.

A table entry: ``{"shape": logical per-layer shape, "stack": layers or None,
"init": "normal" | "ones" | "bias", "scale": float}``. ``normal`` draws
N(0, scale^2); ``ones`` draws 1 + N(0, scale^2) for norm gains; ``bias``
draws N(0, scale^2). Gains and biases are not left at 1 and 0, so that the
comparison would see one misapplied.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

from .traffic import seed_words


def base_key(seed: int):
    hi, lo = seed_words(seed)
    k = jax.random.key(lo & 0x7FFFFFFF)
    for word in (lo >> 31, hi & 0x7FFFFFFF, hi >> 31):
        k = jax.random.fold_in(k, word)
    return k


def flat_values(key, name: str, entry: dict):
    """The leaf as (stack, n) or (n,) float32, row-major over its shape."""
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    n = math.prod(entry["shape"])
    rows = entry["stack"] or 1
    z = jax.random.normal(k, (rows, n), jnp.float32) * entry["scale"]
    if entry["init"] == "ones":
        z = z + 1.0
    return z if entry["stack"] else z[0]


def logical_values(key, name: str, entry: dict):
    v = flat_values(key, name, entry)
    lead = (entry["stack"],) if entry["stack"] else ()
    return v.reshape(lead + tuple(entry["shape"]))


def check_layout(table: dict, specs: dict) -> None:
    """The program's leaves are the table's, with the same shapes."""
    if set(table) != set(specs):
        raise ValueError(f"program leaves {sorted(set(specs) ^ set(table))} "
                         "differ from the reference's")
    for n, s in specs.items():
        if tuple(s.shape) != tuple(table[n]["shape"]) \
                or (s.stack or None) != table[n]["stack"]:
            raise ValueError(f"leaf {n}: program {s.shape} x {s.stack}, "
                             f"reference {table[n]['shape']} x {table[n]['stack']}")


def _padded(v, length: int):
    pad = [(0, 0)] * (v.ndim - 1) + [(0, length - v.shape[-1])]
    return jnp.pad(v, pad)


def program_state(abstract: dict, table: dict, seed: int):
    """The engine's state tree (``ZeroEngine.abstract_state`` layout) holding
    the seeded weights: primaries in their storage type, the float32 master,
    Adam moments at zero and step 0, placed by the engine's shardings."""
    names = sorted(table)
    shardings = jax.tree.map(lambda s: s.sharding, abstract)

    def build(key):
        master = {n: _padded(flat_values(key, n, table[n]),
                             abstract["master"][n].shape[-1]) for n in names}
        return dict(
            primaries={n: master[n].astype(abstract["primaries"][n].dtype)
                       for n in names},
            master=master,
            opt_m={n: jnp.zeros_like(master[n]) for n in names},
            opt_v={n: jnp.zeros_like(master[n]) for n in names},
            step=jnp.zeros((), jnp.int32))

    return jax.jit(build, out_shardings=shardings)(base_key(seed))


def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


def change_norms(master: dict, table: dict, seed: int) -> dict[str, float]:
    """Per leaf, the norm of the float32 master's change since the seeded
    start (the padding holds zeros on both sides)."""
    def f(master, key):
        return {n: _norm(m - _padded(flat_values(key, n, table[n]),
                                     m.shape[-1]))
                for n, m in master.items()}
    out = jax.jit(f)(master, base_key(seed))
    return {n: float(v) for n, v in out.items()}


def leaf_norms(tree: dict, scale: float = 1.0) -> dict[str, float]:
    out = jax.jit(lambda t: {n: _norm(x) * scale for n, x in t.items()})(tree)
    return {n: float(v) for n, v in out.items()}
