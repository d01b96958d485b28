"""Random weights from a seed: made on the device, in one jitted call, in
float32 (the type both programs train and serve in).

The benchmark makes the weights, not the program, so that the program and
the plain reference get the same arrays and neither takes anything from
the other.  Which leaves a model has is its family's answer
(``families/<family>.py``: ``leaves``): ``{flat name: (shape, kind)}``
with kind ``matrix``, ``gain`` or ``bias``, of any rank from 1 up.  Names
are the reference's, flattened with dots (``wte``, ``blocks.<l>.wq``,
``blocks.<l>.experts.w1``, ...); a part that is a number is an index into
a list of the reference's tree.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

MATRIX_STD = 0.02          # GPT-2's initialiser, also Cerebras-GPT's base
NOISE = 0.02               # gains are 1 + noise and biases noise, so that
                           # leaving one out shows in the comparison


def key_of(seed: int):
    """A PRNG key for any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


Leaves = Dict[str, Tuple[Tuple[int, ...], str]]


def builder(shapes: Leaves):
    """The traceable function key -> {flat name: array}.  A leaf's values
    depend on the seed, its shape, its kind and its place among the sorted
    names, and on nothing else."""
    names = sorted(shapes)

    def build(key):
        out = {}
        for i, name in enumerate(names):
            shape, kind = shapes[name]
            r = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            out[name] = {"matrix": MATRIX_STD * r, "gain": 1.0 + NOISE * r,
                         "bias": NOISE * r}[kind]
        return out

    return build


def make(shapes: Leaves, seed: int, shardings: Optional[dict] = None
         ) -> Dict[str, jax.Array]:
    """All leaves in one jitted call.  ``shardings`` ({flat name:
    Sharding}) places each leaf as it is made, so a model larger than one
    chip never sits whole on any."""
    fn = jax.jit(builder(shapes), out_shardings=shardings) if shardings \
        else jax.jit(builder(shapes))
    return fn(key_of(seed))


def change_norms(now: Dict[str, jax.Array], shapes: Leaves, seed: int
                 ) -> Dict[str, float]:
    """Norm of (leaf now - leaf as made from the seed), leaf by leaf, in
    one jitted call that makes each seeded leaf only to subtract it."""
    build = builder(shapes)

    @jax.jit
    def norms(now, key):
        made = build(key)
        return {k: jnp.sqrt(jnp.sum(jnp.square(v - made[k])))
                for k, v in now.items()}

    return {k: float(v) for k, v in norms(now, key_of(seed)).items()}


def unflatten(flat: Dict[str, jax.Array]) -> dict:
    """The reference's tree from the flat names: nested dictionaries, and
    a list where every key of a level is a number."""
    tree: dict = {}
    for name, arr in flat.items():
        *path, leaf = name.split(".")
        node = tree
        for part in path:
            node = node.setdefault(part, {})
        node[leaf] = arr

    def lists(node):
        if not isinstance(node, dict):
            return node
        node = {k: lists(v) for k, v in node.items()}
        if all(k.isdigit() for k in node):
            return [node[k] for k in sorted(node, key=int)]
        return node

    return lists(tree)


def flatten(tree, prefix: str = "") -> Dict[str, jax.Array]:
    """The flat names from the reference's tree."""
    if isinstance(tree, dict):
        items = tree.items()
    elif isinstance(tree, (list, tuple)):
        items = enumerate(tree)
    else:
        return {prefix[:-1]: tree}
    out: Dict[str, jax.Array] = {}
    for k, v in items:
        out.update(flatten(v, f"{prefix}{k}."))
    return out


SKETCH_K = 32


def sketch_key(seed: int):
    return jax.random.fold_in(key_of(seed), 0x5ce7c4)


def grad_readings(shapes: Leaves):
    """The traceable function ({flat name: gradient leaf}, key) -> {"norm":
    {name: scalar}, "sketch": {name: [SKETCH_K]}}.  The key
    (``sketch_key(seed)``) is an argument, not a constant of the program,
    so that every seed runs the one compiled program.

    The sketch of a leaf is SKETCH_K random projections ``u^T G v`` (``u
    . g`` for a vector) with signs u, v drawn from the seed.  Two
    gradients' sketches differ, relative to the sketch's own norm, by about
    the relative norm of the gradients' difference - which the norms alone
    cannot show, since rounding noise hardly changes a norm.  A leaf of
    rank 3 or more (a stack of experts) is projected as the matrix of its
    last axis by all the others."""
    names = sorted(shapes)

    def signs(k, n):
        return jax.random.rademacher(k, (SKETCH_K, n), jnp.float32)

    def read(grads, key):
        norm, sketch = {}, {}
        for i, name in enumerate(names):
            g = grads[name].astype(jnp.float32)
            if g.ndim > 2:
                g = g.reshape(-1, g.shape[-1])
            ku, kv = jax.random.split(jax.random.fold_in(key, i))
            norm[name] = jnp.sqrt(jnp.sum(jnp.square(g)))
            u = signs(ku, g.shape[0])
            if g.ndim == 1:
                sketch[name] = u @ g
            else:
                sketch[name] = jnp.sum(
                    jnp.matmul(u, g, precision=jax.lax.Precision.HIGHEST)
                    * signs(kv, g.shape[1]), axis=-1)
        return {"norm": norm, "sketch": sketch}

    return read
