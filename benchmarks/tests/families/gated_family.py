"""A second family, for the tests: what a model that is not of the GPT-2
family asks of the harness, built from layers the program has.  No learned
positions; weighted norms; a gated feed-forward of two up-projections
(``layer.fc`` x ``layer.dotmul``) whose down-projection carries a bias
that the optimiser does not touch; one bilinear mixing term before the
head whose weight has rank 3 (``layer.tensor``).  Leaves nest deeper than
``blocks.<l>.<leaf>`` (``blocks.<l>.ffn.up``, ``mix.w``).

The serving side is a model object of its own (``GatedLM``) behind the
same ``ServingEngine``.  Found by the ``family`` of ``configs/tiny-gated.json``
under this rehearsal's base directory; nothing under ``harness/`` or
``drivers/`` knows it.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

FEEDING = {"tokens": 0, "target": 2}        # a sample's positions: unused


def layers(config: dict, group: str) -> int:
    return int(config[group]["num_layers"])


def leaves(config: dict, group: str
           ) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    d, f, v = (config["hidden_size"], config["intermediate_size"],
               config["vocab_size"])
    out = {"emb": ((v, d), "matrix"), "head": ((d, v), "matrix"),
           "mix.w": ((d, d, d), "matrix"),
           "mix.norm_g": ((d,), "gain"), "mix.norm_b": ((d,), "bias"),
           "final_g": ((d,), "gain"), "final_b": ((d,), "bias")}
    for l in range(layers(config, group)):
        b = f"blocks.{l}."
        for n in ("wq", "wk", "wv", "wo"):
            out[b + n] = ((d, d), "matrix")
        for n in ("norm1", "norm2"):
            out[b + n + "_g"] = ((d,), "gain")
            out[b + n + "_b"] = ((d,), "bias")
        out[b + "ffn.up"] = ((d, f), "matrix")
        out[b + "ffn.gate"] = ((d, f), "matrix")
        out[b + "ffn.down"] = ((f, d), "matrix")
        out[b + "ffn.down_b"] = ((d,), "bias")
    return out


def frozen(config: dict):
    """The train group's leaves that the optimiser leaves as they are."""
    return [f"blocks.{l}.ffn.down_b" for l in range(layers(config, "train"))]


def train_program(config: dict) -> dict:
    import paddle_tpu as paddle
    from paddle_tpu import layer
    from paddle_tpu.attr import ParamAttr

    d, f, v = (config["hidden_size"], config["intermediate_size"],
               config["vocab_size"])
    n = layers(config, "train")
    seq = paddle.data_type.integer_value_sequence
    tokens = layer.data(name="tokens", type=seq(v))
    target = layer.data(name="target", type=seq(v))
    x = layer.embedding(input=tokens, size=d, name="emb")
    names = {"emb.w": "emb", "head.w0": "head", "mix.w": "mix.w",
             "mix_norm.gamma": "mix.norm_g", "mix_norm.beta": "mix.norm_b",
             "final.gamma": "final_g", "final.beta": "final_b"}
    for l in range(n):
        p, r = f"b{l}_", f"blocks.{l}."
        a = layer.layer_norm(x, name=p + "norm1")
        a = layer.multi_head_attention(a, num_heads=config["num_heads"],
                                       causal=True, name=p + "attn")
        x = layer.addto(input=[x, a], name=p + "res1")
        h = layer.layer_norm(x, name=p + "norm2")
        up = layer.fc(input=h, size=f, act="gelu", bias_attr=False,
                      name=p + "up")
        gate = layer.fc(input=h, size=f, bias_attr=False, name=p + "gate")
        down = layer.fc(input=layer.dotmul(up, gate, name=p + "gated"),
                        size=d, bias_attr=ParamAttr(is_static=True),
                        name=p + "down")
        x = layer.addto(input=[x, down], name=p + "res2")
        for w in ("wq", "wk", "wv", "wo"):
            names[f"{p}attn.{w}"] = r + w
        names.update({
            p + "norm1.gamma": r + "norm1_g", p + "norm1.beta": r + "norm1_b",
            p + "norm2.gamma": r + "norm2_g", p + "norm2.beta": r + "norm2_b",
            p + "up.w0": r + "ffn.up", p + "gate.w0": r + "ffn.gate",
            p + "down.w0": r + "ffn.down", p + "down.b": r + "ffn.down_b"})
    m = layer.layer_norm(x, name="mix_norm")
    x = layer.addto(input=[x, layer.tensor(m, m, size=d, name="mix")],
                    name="mixed")
    x = layer.layer_norm(x, name="final")
    logits = layer.fc(input=x, size=v, bias_attr=False, name="head")
    cost = layer.classification_cost(input=logits, label=target)
    return {"cost": cost, "names": names, "feeding": FEEDING, "layers": n}


class GatedLM:
    """The engine's structural contract (``serving.engine.DecodeModel``),
    duck-typed; parameters under the reference's flat names."""

    def __init__(self, config: dict):
        self.vocab_size = config["vocab_size"]
        self.num_layers = layers(config, "serve")
        self.num_heads = config["num_heads"]
        self.head_dim = config["hidden_size"] // config["num_heads"]

    @staticmethod
    def _norm(x, g, b):
        import jax
        import jax.numpy as jnp

        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
        return (x - mean) * jax.lax.rsqrt(var + 1e-5) * g + b

    def embed(self, params, tokens, positions):
        return params["emb"][tokens]

    def qkv(self, params, layer, x):
        b = f"blocks.{layer}."
        a = self._norm(x, params[b + "norm1_g"], params[b + "norm1_b"])
        shape = x.shape[:-1] + (self.num_heads, self.head_dim)
        return tuple((a @ params[b + w]).reshape(shape)
                     for w in ("wq", "wk", "wv"))

    def attn_out(self, params, layer, ctx, x):
        import jax

        b = f"blocks.{layer}."
        x = x + ctx.reshape(x.shape) @ params[b + "wo"]
        h = self._norm(x, params[b + "norm2_g"], params[b + "norm2_b"])
        h = jax.nn.gelu(h @ params[b + "ffn.up"]) * (h @ params[b + "ffn.gate"])
        return x + h @ params[b + "ffn.down"] + params[b + "ffn.down_b"]

    def logits(self, params, x):
        import jax.numpy as jnp

        m = self._norm(x, params["mix.norm_g"], params["mix.norm_b"])
        x = x + jnp.einsum("...i,kij,...j->...k", m, params["mix.w"], m)
        return self._norm(x, params["final_g"], params["final_b"]) \
            @ params["head"]


def serve_program(config: dict, devs: Sequence) -> dict:
    if len(devs) != 1:
        raise ValueError("GatedLM has no placement over several chips")
    return {"model": GatedLM(config), "mesh": None, "placement": None,
            "names": {k: k for k in leaves(config, "serve")},
            "layers": layers(config, "serve")}


def reference_train_step(ref, config: dict, *, mode: str, optimizer: dict,
                         reduce_grads, block_rows: int, head_rows: int):
    return ref.make_train_step(
        n_head=config["num_heads"], mode=mode, lr=optimizer["learning_rate"],
        b1=optimizer["beta1"], b2=optimizer["beta2"],
        eps=optimizer["epsilon"], reduce_grads=reduce_grads,
        block_rows=block_rows, frozen=frozen(config))


def reference_logits(ref, config: dict, tree, tokens, positions, seg, *,
                     mode: str, block_rows: int):
    return ref.forward_logits(tree, tokens, seg, n_head=config["num_heads"],
                              mode=mode, block_rows=block_rows)


def train_step_flops(config: dict, doc_lengths: Sequence[int]) -> float:
    """Matrix products only, forward and backward: the four projections,
    the three of the gated feed-forward, the bilinear term (d^3 a token,
    contracted twice) and the head; attention's scores and values under
    the causal mask."""
    d, f, v = (config["hidden_size"], config["intermediate_size"],
               config["vocab_size"])
    n = layers(config, "train")
    tokens = sum(int(t) for t in doc_lengths)
    pairs = sum(int(t) * (int(t) + 1) // 2 for t in doc_lengths)
    per_token = n * (4 * d * d + 3 * d * f) + d * d * d + d * d + d * v
    return 6.0 * per_token * tokens + 3.0 * 2 * 2 * pairs * d * n
