"""What the benchmark knows of the ``falcon_h1`` family (Falcon-H1): a dense
decoder whose EVERY block runs, from one normalised input, grouped-query
attention (20 query heads on 4 KV heads of 128 in the 34B, rotary at theta
1e11) and a Mamba-2 state-space branch (32 heads of 128, state 256 a lane,
2 groups of B / C, a causal depthwise convolution of 4 taps, a gated
grouped RMSNorm), adds the two to the residual stream, and then a SwiGLU;
fixed scalar multipliers (muP) sit on the embedding, the head, the key,
the two branches' inputs and outputs, the five segments ``[z | x | B | C |
dt]`` of the state-space projection and the MLP; weighted RMSNorm, an
untied head.  Served one token a tick by ``serving.HybridSsmLM`` behind
``ServingEngine``, which keeps a slot's constant-size state (the
recurrence's and the convolution's) beside the attention branch's pages
in its one KV manager.  No training program.

The reference it is asked to call is ``references/falcon_h1.py``: the
recurrence token by token from a zero state, no chunking, no cache.

The leaves (``leaves``).  Under the reference's names: ``wte``, ``head``,
``norm_g`` and per block ``ln1_g``, ``ln2_g``, ``wq``, ``wk``, ``wv``,
``wo``, ``in_proj`` ``[E, 2 H_s P + 2 G N + H_s]``, ``conv_w`` ``[H_s P + 2
G N, K]``, ``conv_b``, ``dt_bias``, ``a_log``, ``d`` ``[H_s]``,
``ssm_norm_g`` ``[H_s P]``, ``out_proj``, ``ffn_gate``, ``ffn_up``,
``ffn_down``.  ``harness/weights.py`` makes three kinds of leaf (matrix
0.02 n, gain 1 + 0.02 n, bias 0.02 n), so the leaves no config fixes are
seeded by the nearest of them: the convolution's taps and ``d`` as gains,
the convolution's bias, ``dt_bias`` and ``a_log`` as biases (``A`` about
-1, ``dt`` about ``softplus(0) = 0.69``: a state that halves a token; the
configuration's ``assumed`` says what the library draws instead).

The vocabulary's slice.  ``vocab_size`` in the configuration is the rows
of the embedding and of the head held here (``reduced``);
``published.vocab_size`` is the whole.  The traffic draws its ids from the
slice, the logits and the greedy choice are over the slice, in the
program and in the reference alike.

What ``reference_logits`` judges.  Row ``p`` holds the reference's logits
for the token at position ``p + 1`` (next-token logits, no shift of the
driver's slice), from ONE full forward over the prompt and the served
tokens, while the program came there by chunked prefill (a state carried
from chunk to chunk) and then one token a tick through the state and the
cache.  The head's product is made for the rows the driver reads only
(``references/falcon_h1.py RowLogits``).
"""

from __future__ import annotations

# the program's model first: a tree without it fails here, at once, on an
# ImportError, before anything touches the device
from paddle_tpu.serving.hybrid_ssm_lm import HybridSsmLM

from typing import Dict, Sequence, Tuple  # noqa: E402

from harness import cells  # noqa: E402

# the model's parameter of a block -> the reference's leaf of it
BLOCK = {"ln1": "ln1_g", "wq": "wq", "wk": "wk", "wv": "wv", "wo": "wo",
         "ssm_in": "in_proj", "conv_w": "conv_w", "conv_b": "conv_b",
         "dt_bias": "dt_bias", "a_log": "a_log", "d": "d",
         "ssm_norm": "ssm_norm_g", "ssm_out": "out_proj", "ln2": "ln2_g",
         "ffn_gate": "ffn_gate", "ffn_up": "ffn_up", "ffn_down": "ffn_down"}
TOP = {"emb": "wte", "out": "head", "norm": "norm_g"}
# the config's scalar multipliers, under the names both sides read
MULTIPLIERS = ("embedding_multiplier", "lm_head_multiplier",
               "key_multiplier", "attention_in_multiplier",
               "attention_out_multiplier", "ssm_in_multiplier",
               "ssm_out_multiplier", "ssm_multipliers", "mlp_multipliers")


def layers(config: dict, group: str) -> int:
    """How many blocks the group's program runs (every block is alike)."""
    return int(config[group]["n_layer"])


def multipliers(config: dict) -> dict:
    return {k: (tuple(float(x) for x in config[k])
                if isinstance(config[k], (list, tuple)) else float(config[k]))
            for k in MULTIPLIERS}


def sizes(config: dict) -> dict:
    """The state-space branch's sizes from the published keys, checked
    against each other."""
    hs, p = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    if hs * p != int(config["mamba_d_ssm"]):
        raise cells.CellError(
            f"mamba_n_heads x mamba_d_head ({hs} x {p}) is not mamba_d_ssm "
            f"({config['mamba_d_ssm']})")
    for key in ("mamba_proj_bias", "attention_bias", "mlp_bias",
                "projectors_bias", "mamba_norm_before_gate"):
        if config.get(key):
            raise cells.CellError(f"{key} true is not built")
    if not (config["mamba_conv_bias"] and config["mamba_rms_norm"]):
        raise cells.CellError("the branch is built with the convolution's "
                              "bias and the gated norm")
    return {"hs": hs, "p": p, "n": int(config["mamba_d_state"]),
            "g": int(config["mamba_n_groups"]),
            "taps": int(config["mamba_d_conv"]),
            "chunk": int(config["mamba_chunk_size"])}


def leaves(config: dict, group: str
           ) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """{flat name: (shape, kind)} under the reference's names."""
    e, v, d = config["hidden_size"], config["vocab_size"], config["head_dim"]
    q, kv = config["num_attention_heads"] * d, \
        config["num_key_value_heads"] * d
    f, s = config["intermediate_size"], sizes(config)
    ds, gn = s["hs"] * s["p"], s["g"] * s["n"]
    out = {"wte": ((v, e), "matrix"), "head": ((e, v), "matrix"),
           "norm_g": ((e,), "gain")}
    for l in range(layers(config, group)):
        b = f"blocks.{l}."
        out.update({
            b + "ln1_g": ((e,), "gain"), b + "ln2_g": ((e,), "gain"),
            b + "wq": ((e, q), "matrix"), b + "wk": ((e, kv), "matrix"),
            b + "wv": ((e, kv), "matrix"), b + "wo": ((q, e), "matrix"),
            b + "in_proj": ((e, 2 * ds + 2 * gn + s["hs"]), "matrix"),
            b + "conv_w": ((ds + 2 * gn, s["taps"]), "gain"),
            b + "conv_b": ((ds + 2 * gn,), "bias"),
            b + "dt_bias": ((s["hs"],), "bias"),
            b + "a_log": ((s["hs"],), "bias"),
            b + "d": ((s["hs"],), "gain"),
            b + "ssm_norm_g": ((ds,), "gain"),
            b + "out_proj": ((ds, e), "matrix"),
            b + "ffn_gate": ((e, f), "matrix"),
            b + "ffn_up": ((e, f), "matrix"),
            b + "ffn_down": ((f, e), "matrix")})
    return out


def serve_program(config: dict, devs: Sequence) -> dict:
    """The serving program (the README's ``serve_program``): the
    parameters go in under the model's own names and are used as they
    are."""
    if len(devs) > 1:
        raise cells.CellError("the falcon_h1 family is served on one chip: "
                              "a slot's recurrent state has no placement "
                              "over more")
    n, s = layers(config, "serve"), sizes(config)
    model = HybridSsmLM(
        vocab_size=config["vocab_size"], embed_dim=config["hidden_size"],
        num_layers=n, num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], ffn_dim=config["intermediate_size"],
        ssm_heads=s["hs"], ssm_head_dim=s["p"], ssm_state=s["n"],
        ssm_groups=s["g"], conv_taps=s["taps"], chunk=s["chunk"],
        rope_theta=config["rope_theta"], norm_eps=config["rms_norm_eps"],
        multipliers=multipliers(config))
    names = dict(TOP)
    for l in range(n):
        names.update({f"l{l}.{p}": f"blocks.{l}.{r}"
                      for p, r in BLOCK.items()})
    return {"model": model, "mesh": None, "placement": None, "names": names,
            "layers": n}


def arch(config: dict) -> dict:
    """What ``references/falcon_h1.py`` needs of the configuration."""
    s = sizes(config)
    return {"n_head": config["num_attention_heads"],
            "n_kv_head": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "theta": float(config["rope_theta"]),
            "ssm_heads": s["hs"], "ssm_head_dim": s["p"],
            "ssm_state": s["n"], "ssm_groups": s["g"],
            "multipliers": multipliers(config),
            "eps": float(config["rms_norm_eps"])}


def reference_logits(ref, config: dict, tree, tokens, positions, seg, *,
                     mode: str, block_rows: int):
    """Next-token logits at every row of one flat buffer, sliceable by
    rows (the module's doc).  One sequence a buffer: ``seg`` is not
    read."""
    return ref.logits(tree, tokens, positions, mode=mode,
                      block_rows=block_rows, **arch(config))
