"""What the benchmark knows of the ``sdar_moe`` family (SDAR-30B-A3B-Chat):
a decoder with weighted RMSNorm, rotary positions, grouped-query attention
with per-head q/k norms and a softmax-routed expert layer in every block,
which generates by diffusion over blocks, as ``serving.BlockMoeLM`` runs it
behind ``ServingEngine``: every expert and the whole vocabulary on the
chip, ``serve.n_layer`` of the published layers.  No training program.

The reference it is asked to call is ``references/sdar_moe.py``.

What the served-token check can know.  ``drivers/serve.py`` holds row
``p - 1`` of :func:`reference_logits` against the token served at position
``p``, from the prompt and the served tokens alone.  The logits that judge
position ``p`` are row ``p`` (this family does not shift) of the state in
which ``p`` was fixed: earlier blocks clean, its own block clean below some
offset and masked from there on.  Under ``sequential`` remasking with a
fixed number of passes and a prompt that ends on a block boundary that
offset is a function of ``p`` alone, ``(p % B) // (B / S) * (B / S)``,
which is what the cell runs; a test that knows where the prompt ended says
so with ``prompt_len`` (the block a prompt ends inside starts its passes at
the prompt's end).
"""

from __future__ import annotations

# the program's model first: a tree without it fails here, at once, on an
# ImportError, before anything touches the device
from paddle_tpu.serving.block_moe_lm import BlockMoeLM

from typing import Dict, Optional, Sequence, Tuple  # noqa: E402

import jax.numpy as jnp  # noqa: E402

from harness import cells  # noqa: E402

# the model's parameter of a block -> the reference's leaf of it
BLOCK = {"ln1": "ln1_g", "wq": "wq", "wk": "wk", "wv": "wv", "wo": "wo",
         "q_norm": "q_g", "k_norm": "k_g", "ln2": "ln2_g",
         "router": "router", "w_gate": "w_gate", "w_up": "w_up",
         "w_down": "w_down"}
TOP = {"emb": "wte", "out": "head", "norm": "norm_g"}


def layers(config: dict, group: str) -> int:
    """How many blocks the group's program runs."""
    return int(config[group]["n_layer"])


def leaves(config: dict, group: str
           ) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """{flat name: (shape, kind)} under the reference's names.  The
    experts of a layer are three leaves of rank 3, [experts, ., .]."""
    e, v, d = config["hidden_size"], config["vocab_size"], config["head_dim"]
    q, kv = config["num_attention_heads"] * d, \
        config["num_key_value_heads"] * d
    n, f = config["num_experts"], config["moe_intermediate_size"]
    out = {"wte": ((v, e), "matrix"), "head": ((e, v), "matrix"),
           "norm_g": ((e,), "gain")}
    for l in range(layers(config, group)):
        b = f"blocks.{l}."
        out.update({
            b + "ln1_g": ((e,), "gain"), b + "ln2_g": ((e,), "gain"),
            b + "wq": ((e, q), "matrix"), b + "wk": ((e, kv), "matrix"),
            b + "wv": ((e, kv), "matrix"), b + "wo": ((q, e), "matrix"),
            b + "q_g": ((d,), "gain"), b + "k_g": ((d,), "gain"),
            b + "router": ((e, n), "matrix"),
            b + "w_gate": ((n, e, f), "matrix"),
            b + "w_up": ((n, e, f), "matrix"),
            b + "w_down": ((n, f, e), "matrix")})
    return out


def serve_program(config: dict, devs: Sequence) -> dict:
    """The serving program (the README's ``serve_program``): the
    parameters go in under the model's own names, stacked as the
    reference has them, and are used as they are."""
    if len(devs) > 1:
        raise cells.CellError("the sdar_moe family is served on one chip: "
                              "its expert layer has no placement over more")
    dep = config["serve"]
    n = layers(config, "serve")
    model = BlockMoeLM(
        vocab_size=config["vocab_size"], num_layers=n,
        embed_dim=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], num_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"],
        block_length=dep["block_length"],
        denoise_steps=dep["denoise_steps"],
        mask_token_id=dep["mask_token_id"],
        rope_theta=config["rope_theta"], norm_eps=config["rms_norm_eps"])
    names = dict(TOP)
    for l in range(n):
        names.update({f"l{l}.{p}": f"blocks.{l}.{r}"
                      for p, r in BLOCK.items()})
    return {"model": model, "mesh": None, "placement": None, "names": names,
            "layers": n}


def arch(config: dict) -> dict:
    """What ``references/sdar_moe.py`` needs of the configuration."""
    return {"n_head": config["num_attention_heads"],
            "n_kv_head": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "top_k": config["num_experts_per_tok"],
            "block": config["serve"]["block_length"],
            "mask_token_id": config["serve"]["mask_token_id"],
            "theta": float(config["rope_theta"]),
            "eps": float(config["rms_norm_eps"])}


def fixing_states(config: dict, positions, prompt_len: Optional[int] = None):
    """(masked_from [S, T], state [T]): the ``S`` states a block goes
    through (state ``s`` is masked from offset ``first + s B / S`` on,
    ``first`` 0, or where the prompt ends for the block it ends inside),
    and the state in which each position was fixed."""
    dep = config["serve"]
    b, s = dep["block_length"], dep["denoise_steps"]
    g = b // s
    first = jnp.zeros_like(positions)
    if prompt_len is not None:
        first = jnp.where(positions // b == prompt_len // b,
                          prompt_len % b, 0)
    masked_from = first[None, :] + g * jnp.arange(s)[:, None]
    state = jnp.clip((positions % b - first) // g, 0, s - 1)
    return masked_from, state


def reference_logits(ref, config: dict, tree, tokens, positions, seg, *,
                     mode: str, block_rows: int,
                     prompt_len: Optional[int] = None):
    """``[T, V]`` whose row ``p - 1`` judges the token at position ``p``:
    the reference's logits at ``p`` in the state in which ``p`` was fixed
    (the module's doc), the mask token's at the least value (a pass never
    chooses it).  One sequence a buffer: ``seg`` is not read."""
    masked_from, state = fixing_states(config, positions, prompt_len)
    logits = ref.state_logits(tree, tokens, positions, masked_from, state,
                              mode=mode, block_rows=block_rows,
                              **arch(config))
    logits = logits.at[:, config["serve"]["mask_token_id"]].set(-1e30)
    return jnp.roll(logits, -1, axis=0)
