"""What the benchmark knows of the ``laguna`` family (Laguna-XS.2): a
decoder whose layers are of two kinds, full attention (48 query heads,
YaRN rotary on half of a head's lanes) and a 512-token sliding window (64
query heads, plain rotary on all lanes), 3 window layers to 1 full one,
all on 8 KV heads of 128 with a per-head sigmoid gate on the attention
output; a dense SwiGLU in layer 0, then a sigmoid-routed expert layer
(256 experts, 8 a token, weights renormalised and scaled by 2.5, a frozen
correction bias) beside a shared expert; weighted RMSNorm, an untied
head.  Served one token a tick by ``serving.WindowMoeLM`` behind
``ServingEngine``.  No training program.

The reference it is asked to call is ``references/laguna.py``.

The leaves (``leaves``).  Under the reference's names: ``wte``, ``head``,
``norm_g`` and per block ``ln1_g``, ``ln2_g``, ``wq`` ``[E, H_l D]`` (``H_l``
from ``num_attention_heads_per_layer``: a leaf's shape differs by layer),
``wk``, ``wv``, ``wo``, ``gate`` ``[E, H_l]``; then ``ffn_gate``, ``ffn_up``,
``ffn_down`` where ``mlp_layer_types`` says ``dense``, else ``router`` ``[E,
published experts]``, ``bias`` (kind ``bias``: the correction bias, a
seeded leaf that nothing updates), ``w_gate``, ``w_up``, ``w_down`` stacked
over the experts HELD and ``shared_gate``, ``shared_up``, ``shared_down``.

The held share.  ``num_experts`` in the configuration is the number of
experts this chip holds (``reduced``); ``published.num_experts`` is the
router's width and ``serve.held_experts`` ``[first, count]`` says which
they are.  The program and the reference are given the same share: the
router scores all 256, a chosen expert outside the share adds nothing on
either side, the shared expert is computed whole on both.  That the
shares of a layer add up to the uncut layer is tested at a small size
(``tests/test_serving_window.py``).

What ``reference_logits`` judges.  Row ``p`` holds the reference's logits
for the token at position ``p + 1`` (next-token logits, no shift of the
driver's slice), from one full forward over the prompt and the served
tokens: no cache, every window layer's mask applied to the whole buffer.
The head's product is made for the rows the driver reads only
(``references/laguna.py RowLogits``): all 16,640 rows of 100,352 columns
would be 6.7 GB beside 9 GB of weights.
"""

from __future__ import annotations

# the program's model first: a tree without it fails here, at once, on an
# ImportError, before anything touches the device
from paddle_tpu.serving.window_moe_lm import WindowMoeLM

from typing import Dict, List, Optional, Sequence, Tuple  # noqa: E402

from harness import cells  # noqa: E402

# the model's parameter of a block -> the reference's leaf of it
ATTENTION = {"ln1": "ln1_g", "wq": "wq", "wk": "wk", "wv": "wv", "wo": "wo",
             "wgate": "gate", "ln2": "ln2_g"}
DENSE = {"ffn_gate": "ffn_gate", "ffn_up": "ffn_up", "ffn_down": "ffn_down"}
SPARSE = {k: k for k in ("router", "bias", "w_gate", "w_up", "w_down",
                         "shared_gate", "shared_up", "shared_down")}
TOP = {"emb": "wte", "out": "head", "norm": "norm_g"}


def layers(config: dict, group: str) -> int:
    """How many blocks the group's program runs (the first of the
    published pattern)."""
    return int(config[group]["n_layer"])


def layer_heads(config: dict, group: str) -> List[int]:
    return [int(h) for h in
            config["num_attention_heads_per_layer"][:layers(config, group)]]


def layer_windows(config: dict, group: str) -> List[Optional[int]]:
    return [int(config["sliding_window"]) if t == "sliding_attention"
            else None
            for t in config["layer_types"][:layers(config, group)]]


def layer_sparse(config: dict, group: str) -> List[bool]:
    return [t == "sparse"
            for t in config["mlp_layer_types"][:layers(config, group)]]


def held(config: dict, group: str) -> Tuple[int, int]:
    first, count = config[group]["held_experts"]
    if count != config["num_experts"]:
        raise cells.CellError(
            f"held_experts {config[group]['held_experts']} and num_experts "
            f"{config['num_experts']} differ: the key counts the experts "
            "held here")
    return int(first), int(count)


def leaves(config: dict, group: str
           ) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """{flat name: (shape, kind)} under the reference's names.  The
    experts of a layer are three leaves of rank 3, [held, ., .]."""
    e, v, d = config["hidden_size"], config["vocab_size"], config["head_dim"]
    kv = config["num_key_value_heads"] * d
    n_all = config["published"]["num_experts"]
    n, f = held(config, group)[1], config["moe_intermediate_size"]
    s, fd = config["shared_expert_intermediate_size"], \
        config["intermediate_size"]
    out = {"wte": ((v, e), "matrix"), "head": ((e, v), "matrix"),
           "norm_g": ((e,), "gain")}
    sparse = layer_sparse(config, group)
    for l, h in enumerate(layer_heads(config, group)):
        b = f"blocks.{l}."
        out.update({
            b + "ln1_g": ((e,), "gain"), b + "ln2_g": ((e,), "gain"),
            b + "wq": ((e, h * d), "matrix"), b + "wk": ((e, kv), "matrix"),
            b + "wv": ((e, kv), "matrix"), b + "wo": ((h * d, e), "matrix"),
            b + "gate": ((e, h), "matrix")})
        if not sparse[l]:
            out.update({b + "ffn_gate": ((e, fd), "matrix"),
                        b + "ffn_up": ((e, fd), "matrix"),
                        b + "ffn_down": ((fd, e), "matrix")})
            continue
        out.update({
            b + "router": ((e, n_all), "matrix"),
            b + "bias": ((n_all,), "bias"),
            b + "w_gate": ((n, e, f), "matrix"),
            b + "w_up": ((n, e, f), "matrix"),
            b + "w_down": ((n, f, e), "matrix"),
            b + "shared_gate": ((e, s), "matrix"),
            b + "shared_up": ((e, s), "matrix"),
            b + "shared_down": ((s, e), "matrix")})
    return out


def serve_program(config: dict, devs: Sequence) -> dict:
    """The serving program (the README's ``serve_program``): the
    parameters go in under the model's own names, stacked as the
    reference has them, and are used as they are."""
    if len(devs) > 1:
        raise cells.CellError("the laguna family is served on one chip: its "
                              "window rings and its expert layer have no "
                              "placement over more")
    n = layers(config, "serve")
    sparse = layer_sparse(config, "serve")
    rope = config["rope_parameters"]
    model = WindowMoeLM(
        vocab_size=config["vocab_size"], embed_dim=config["hidden_size"],
        layer_heads=layer_heads(config, "serve"),
        layer_windows=layer_windows(config, "serve"), layer_sparse=sparse,
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], dense_dim=config["intermediate_size"],
        num_experts=config["published"]["num_experts"],
        held=held(config, "serve"),
        experts_per_token=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"],
        shared_dim=config["shared_expert_intermediate_size"],
        routed_scaling=config["moe_routed_scaling_factor"],
        rope_full=rope["full_attention"],
        rope_window=rope["sliding_attention"],
        norm_eps=config["rms_norm_eps"])
    names = dict(TOP)
    for l in range(n):
        block = {**ATTENTION, **(SPARSE if sparse[l] else DENSE)}
        names.update({f"l{l}.{p}": f"blocks.{l}.{r}"
                      for p, r in block.items()})
    return {"model": model, "mesh": None, "placement": None, "names": names,
            "layers": n}


def arch(config: dict, group: str = "serve") -> dict:
    """What ``references/laguna.py`` needs of the configuration."""
    rope = config["rope_parameters"]
    return {"layer_heads": tuple(layer_heads(config, group)),
            "layer_windows": tuple(layer_windows(config, group)),
            "layer_sparse": tuple(layer_sparse(config, group)),
            "n_kv_head": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "top_k": config["num_experts_per_tok"],
            "scaling": float(config["moe_routed_scaling_factor"]),
            "held": held(config, group),
            "rope_full": rope["full_attention"],
            "rope_window": rope["sliding_attention"],
            "eps": float(config["rms_norm_eps"])}


def reference_logits(ref, config: dict, tree, tokens, positions, seg, *,
                     mode: str, block_rows: int):
    """Next-token logits at every row of one flat buffer, sliceable by
    rows (the module's doc).  One sequence a buffer: ``seg`` is not
    read."""
    return ref.logits(tree, tokens, positions, mode=mode,
                      block_rows=block_rows, **arch(config))
