"""Random GPT-2-family weights from a seed: made on the device, in one
jitted call, in float32 (the type both programs train and serve in).

The benchmark makes the weights, not the program, so that the program and
the plain reference get the same arrays and neither takes anything from
the other.  ``spec`` says which leaves the architecture under test has;
names are the reference's (``references/gpt2_family.py``), flattened with
dots: ``wte``, ``wpe``, ``blocks.<l>.wq``, ..., ``head``.
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


def leaf_shapes(spec: dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """{flat name: (shape, kind)} with kind ``matrix``, ``gain`` or
    ``bias``.  ``spec`` keys: vocab, positions, hidden, ffn, layers, and
    the booleans attn_bias, ffn_bias, norm_params, untied_head, head_bias."""
    e, f, v = spec["hidden"], spec["ffn"], spec["vocab"]
    out = {"wte": ((v, e), "matrix"),
           "wpe": ((spec["positions"], e), "matrix")}
    for l in range(spec["layers"]):
        b = f"blocks.{l}."
        for n in ("wq", "wk", "wv", "wo"):
            out[b + n] = ((e, e), "matrix")
        out[b + "w1"] = ((e, f), "matrix")
        out[b + "w2"] = ((f, e), "matrix")
        if spec["attn_bias"]:
            for n in ("bq", "bk", "bv", "bo"):
                out[b + n] = ((e,), "bias")
        if spec["ffn_bias"]:
            out[b + "b1"] = ((f,), "bias")
            out[b + "b2"] = ((e,), "bias")
        if spec["norm_params"]:
            for n in ("ln1", "ln2"):
                out[b + n + "_g"] = ((e,), "gain")
                out[b + n + "_b"] = ((e,), "bias")
    if spec["norm_params"]:
        out["lnf_g"] = ((e,), "gain")
        out["lnf_b"] = ((e,), "bias")
    if spec["untied_head"]:
        out["head"] = ((e, v), "matrix")
    if spec["head_bias"]:
        out["head_b"] = ((v,), "bias")
    return out


def builder(spec: dict):
    """The traceable function key -> {flat name: array}."""
    shapes = leaf_shapes(spec)
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


def make(spec: dict, seed: int, shardings: Optional[dict] = None
         ) -> Dict[str, jax.Array]:
    """All leaves in one jitted call.  ``shardings`` ({flat name:
    Sharding}) places each leaf as it is made, so a model larger than one
    chip never sits whole on any."""
    fn = jax.jit(builder(spec), out_shardings=shardings) if shardings else \
        jax.jit(builder(spec))
    return fn(key_of(seed))


def change_norms(now: Dict[str, jax.Array], spec: dict, seed: int
                 ) -> Dict[str, float]:
    """Norm of (leaf now - leaf as made from the seed), leaf by leaf, in
    one jitted call that makes each seeded leaf only to subtract it."""
    build = builder(spec)

    @jax.jit
    def norms(now, key):
        made = build(key)
        return {k: jnp.sqrt(jnp.sum(jnp.square(v - made[k])))
                for k, v in now.items()}

    return {k: float(v) for k, v in norms(now, key_of(seed)).items()}


def unflatten(flat: Dict[str, jax.Array]) -> dict:
    """The reference's tree from the flat names."""
    tree: dict = {}
    blocks: Dict[int, dict] = {}
    for name, arr in flat.items():
        if name.startswith("blocks."):
            _, l, leaf = name.split(".")
            blocks.setdefault(int(l), {})[leaf] = arr
        else:
            tree[name] = arr
    tree["blocks"] = [blocks[l] for l in sorted(blocks)]
    return tree


SKETCH_K = 32


def flatten(tree: dict) -> Dict[str, jax.Array]:
    """The flat names from the reference's tree."""
    out = {k: v for k, v in tree.items() if k != "blocks"}
    for l, b in enumerate(tree["blocks"]):
        out.update({f"blocks.{l}.{k}": v for k, v in b.items()})
    return out


def sketch_key(seed: int):
    return jax.random.fold_in(key_of(seed), 0x5ce7c4)


def grad_readings(spec: dict):
    """The traceable function ({flat name: gradient leaf}, key) -> {"norm":
    {name: scalar}, "sketch": {name: [SKETCH_K]}}.  The key
    (``sketch_key(seed)``) is an argument, not a constant of the program,
    so that every seed runs the one compiled program.

    The sketch of a leaf is SKETCH_K random projections ``u^T G v`` (``u
    . g`` for a vector) with signs u, v drawn from the seed.  Two
    gradients' sketches differ, relative to the sketch's own norm, by about
    the relative norm of the gradients' difference - which the norms alone
    cannot show, since rounding noise hardly changes a norm."""
    shapes = leaf_shapes(spec)
    names = sorted(shapes)

    def signs(k, n):
        return jax.random.rademacher(k, (SKETCH_K, n), jnp.float32)

    def read(grads, key):
        norm, sketch = {}, {}
        for i, name in enumerate(names):
            g = grads[name].astype(jnp.float32)
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
