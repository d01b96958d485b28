"""What the benchmark knows of the ``ouro`` family (Ouro: a looped language
model): a dense multi-head decoder (16 heads of 128 in the 2.6B, rotary at
theta 1e6 over all lanes, RMSNorm before AND after each sub-layer, SwiGLU
5632, an untied head over 49,152 tokens) whose WHOLE stack of
``num_hidden_layers`` layers runs ``total_ut_steps`` = 4 times over the
same weights, the final norm closing every pass, a learned exit gate
reading each pass's output (``early_exit_threshold`` 1.0: as published no
token leaves early).  Served one token a tick by ``serving.LoopedLM``
behind ``ServingEngine``, whose KV manager keeps ``total_ut_steps x
num_hidden_layers`` cache layers (weight layer ``l`` at pass ``t`` reads
and writes cache layer ``t * num_hidden_layers + l``).  No training
program.

The reference it is asked to call is ``references/ouro.py``: every pass a
full forward over the whole buffer, no cache.

The leaves (``leaves``).  Under the reference's names: ``wte``, ``head``,
``norm_g``, ``gate_w`` ``[E, 1]``, ``gate_b`` ``[1]`` and per layer
``norm1_g`` .. ``norm4_g``, ``wq``, ``wk``, ``wv``, ``wo``, ``ffn_gate``,
``ffn_up``, ``ffn_down``; each ONCE whatever the number of passes.

What ``reference_logits`` judges.  Row ``p`` holds the reference's
last-pass logits for the token at position ``p + 1`` (next-token logits,
no shift of the driver's slice), from ONE full forward over the prompt
and the served tokens, while the program came there by chunked prefill (a
chunk carried through all passes in its tick, its rows at pass ``t``
reading the earlier chunks' K/V of pass ``t``) and then one token a tick
through the cache.  The head's product is made for the rows the driver
reads only (``references/ouro.py RowLogits``).
"""

from __future__ import annotations

# the program's model first: a tree without it fails here, at once, on an
# ImportError, before anything touches the device
from paddle_tpu.serving.looped_lm import LoopedLM

from typing import Dict, Sequence, Tuple  # noqa: E402

from harness import cells  # noqa: E402

# the model's parameter of a layer -> the reference's leaf of it
BLOCK = {"ln1": "norm1_g", "ln2": "norm2_g", "ln3": "norm3_g",
         "ln4": "norm4_g", "wq": "wq", "wk": "wk", "wv": "wv", "wo": "wo",
         "ffn_gate": "ffn_gate", "ffn_up": "ffn_up", "ffn_down": "ffn_down"}
TOP = {"emb": "wte", "out": "head", "norm": "norm_g", "gate_w": "gate_w",
       "gate_b": "gate_b"}


def layers(config: dict, group: str) -> int:
    """How many WEIGHT layers the group's program holds (each runs
    ``total_ut_steps`` times a tick)."""
    return int(config["num_hidden_layers"])


def check(config: dict) -> None:
    """What of the published config this family builds."""
    if int(config["num_key_value_heads"]) != \
            int(config["num_attention_heads"]):
        raise cells.CellError("the ouro family is multi-head: "
                              "num_key_value_heads = num_attention_heads")
    if config.get("use_sliding_window") or config.get("rope_scaling"):
        raise cells.CellError("a sliding window or scaled rotary positions "
                              "are not built")
    if config.get("tie_word_embeddings") or config["hidden_act"] != "silu":
        raise cells.CellError("the family has an untied head and a SwiGLU")
    if float(config["early_exit_threshold"]) != 1.0:
        raise cells.CellError(
            "early_exit_threshold below 1.0 (a token that leaves before "
            "the last pass) is not built: every token runs every pass")


def leaves(config: dict, group: str
           ) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """{flat name: (shape, kind)} under the reference's names."""
    e, v, f = config["hidden_size"], config["vocab_size"], \
        config["intermediate_size"]
    q = config["num_attention_heads"] * config["head_dim"]
    out = {"wte": ((v, e), "matrix"), "head": ((e, v), "matrix"),
           "norm_g": ((e,), "gain"), "gate_w": ((e, 1), "matrix"),
           "gate_b": ((1,), "bias")}
    for l in range(layers(config, group)):
        b = f"blocks.{l}."
        out.update({
            b + "norm1_g": ((e,), "gain"), b + "norm2_g": ((e,), "gain"),
            b + "norm3_g": ((e,), "gain"), b + "norm4_g": ((e,), "gain"),
            b + "wq": ((e, q), "matrix"), b + "wk": ((e, q), "matrix"),
            b + "wv": ((e, q), "matrix"), b + "wo": ((q, e), "matrix"),
            b + "ffn_gate": ((e, f), "matrix"),
            b + "ffn_up": ((e, f), "matrix"),
            b + "ffn_down": ((f, e), "matrix")})
    return out


def serve_program(config: dict, devs: Sequence) -> dict:
    """The serving program (the README's ``serve_program``): the
    parameters go in under the model's own names and are used as they
    are, once for all passes."""
    if len(devs) > 1:
        raise cells.CellError("the ouro family is served on one chip: the "
                              "engine refuses a looped model a mesh")
    check(config)
    n = layers(config, "serve")
    model = LoopedLM(
        vocab_size=config["vocab_size"], embed_dim=config["hidden_size"],
        num_layers=n, num_heads=config["num_attention_heads"],
        head_dim=config["head_dim"], ffn_dim=config["intermediate_size"],
        loops=config["total_ut_steps"], rope_theta=config["rope_theta"],
        norm_eps=config["rms_norm_eps"])
    names = dict(TOP)
    for l in range(n):
        names.update({f"l{l}.{p}": f"blocks.{l}.{r}"
                      for p, r in BLOCK.items()})
    return {"model": model, "mesh": None, "placement": None, "names": names,
            "layers": n}


def arch(config: dict) -> dict:
    """What ``references/ouro.py`` needs of the configuration."""
    return {"n_head": config["num_attention_heads"],
            "head_dim": config["head_dim"],
            "theta": float(config["rope_theta"]),
            "eps": float(config["rms_norm_eps"]),
            "steps": int(config["total_ut_steps"])}


def reference_logits(ref, config: dict, tree, tokens, positions, seg, *,
                     mode: str, block_rows: int):
    """Last-pass next-token logits at every row of one flat buffer,
    sliceable by rows (the module's doc).  One sequence a buffer: ``seg``
    is not read."""
    return ref.logits(tree, tokens, positions, mode=mode,
                      block_rows=block_rows, **arch(config))
