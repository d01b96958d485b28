"""What the benchmark knows of the GPT-2 family (pre-LN decoder, learned
positions, GELU, equal heads), as the two programs in the tree run it:
``models.transformer.build`` under ``trainer.SGD`` and ``serving.DecoderLM``
behind ``ServingEngine``.  Their departures from the published model are in
the configuration files.

A family's file answers the drivers' and the readers' questions about a
configuration of that family and knows nothing of timing (the README lists
the questions).  The reference it is asked to call is
``references/gpt2_family.py``, handed in as ``ref``.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

from harness import cells

FEEDING = {"tokens": 0, "pos": 1, "target": 2}
MODEL = cells.kernel("gpt2_model")      # operations from shapes

# what each program has of the leaves below (the reference applies a bias
# or a head that its tree holds, and none that it lacks)
GROUPS = {
    # models.transformer.build: no attention biases, an untied head with
    # a bias, LayerNorm with gain and bias
    "train": {"attn_bias": False, "ffn_bias": True, "norm_params": True,
              "untied_head": True, "head_bias": True},
    # serving.DecoderLM: no biases, parameter-free RMSNorm, an untied head
    "serve": {"attn_bias": False, "ffn_bias": False, "norm_params": False,
              "untied_head": True, "head_bias": False},
}
NORM = {"train": "layernorm", "serve": "rms_noparam"}


def layers(config: dict, group: str) -> int:
    """How many blocks the group's program runs."""
    return int(config[group]["n_layer"])


def leaves(config: dict, group: str
           ) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """{flat name: (shape, kind)} of the group's program, under the
    reference's names; kind is ``matrix``, ``gain`` or ``bias``."""
    has = GROUPS[group]
    e, f, v = config["n_embd"], config["n_inner"], config["vocab_size"]
    out = {"wte": ((v, e), "matrix"),
           "wpe": ((config["n_positions"], e), "matrix")}
    for l in range(layers(config, group)):
        b = f"blocks.{l}."
        for n in ("wq", "wk", "wv", "wo"):
            out[b + n] = ((e, e), "matrix")
        out[b + "w1"] = ((e, f), "matrix")
        out[b + "w2"] = ((f, e), "matrix")
        if has["attn_bias"]:
            for n in ("bq", "bk", "bv", "bo"):
                out[b + n] = ((e,), "bias")
        if has["ffn_bias"]:
            out[b + "b1"] = ((f,), "bias")
            out[b + "b2"] = ((e,), "bias")
        if has["norm_params"]:
            for n in ("ln1", "ln2"):
                out[b + n + "_g"] = ((e,), "gain")
                out[b + n + "_b"] = ((e,), "bias")
    if has["norm_params"]:
        out["lnf_g"] = ((e,), "gain")
        out["lnf_b"] = ((e,), "bias")
    if has["untied_head"]:
        out["head"] = ((e, v), "matrix")
    if has["head_bias"]:
        out["head_b"] = ((v,), "bias")
    return out


def train_program(config: dict) -> dict:
    """The training program through the program's public model builder:
    its ``cost``, ``names`` ({the trainer's parameter name: the
    reference's flat name}), the ``feeding`` of a sample's columns and how
    many ``layers`` run.  The driver makes the ``Parameters``, checks the
    names both ways and puts the seeded weights in."""
    from paddle_tpu.models import transformer

    n = layers(config, "train")
    *_, cost = transformer.build(
        vocab_size=config["vocab_size"], d_model=config["n_embd"],
        n_layers=n, n_heads=config["n_head"], max_len=config["n_positions"],
        ffn_mult=config["n_inner"] // config["n_embd"])
    names = {"tok_embed.w": "wte", "pos_embed.w": "wpe",
             "final_ln.gamma": "lnf_g", "final_ln.beta": "lnf_b",
             "lm_head.w0": "head", "lm_head.b": "head_b"}
    for l in range(n):
        p, r = f"blk{l}_", f"blocks.{l}."
        for w in ("wq", "wk", "wv", "wo"):
            names[f"{p}attn.{w}"] = r + w
        names.update({
            f"{p}ffn_up.w0": r + "w1", f"{p}ffn_up.b": r + "b1",
            f"{p}ffn_down.w0": r + "w2", f"{p}ffn_down.b": r + "b2",
            f"{p}ln1.gamma": r + "ln1_g", f"{p}ln1.beta": r + "ln1_b",
            f"{p}ln2.gamma": r + "ln2_g", f"{p}ln2.beta": r + "ln2_b"})
    return {"cost": cost, "names": names, "feeding": FEEDING, "layers": n}


def serve_program(config: dict, devs: Sequence) -> dict:
    """The serving program: the ``model`` object ``ServingEngine`` takes,
    the ``mesh`` over the cell's devices (``None`` on one), the
    ``placement`` of each parameter on it ({the model's parameter name: a
    PartitionSpec's entries}, the engine's own plan), ``names`` ({the
    model's parameter name: the reference's flat name}) and how many
    ``layers`` run."""
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.serving import DecoderLM

    n = layers(config, "serve")
    names = {"emb": "wte", "pos": "wpe", "out": "head"}
    for l in range(n):
        for w in ("wq", "wk", "wv", "wo", "w1", "w2"):
            names[f"l{l}.{w}"] = f"blocks.{l}.{w}"
    model = DecoderLM(
        vocab_size=config["vocab_size"], num_layers=n,
        num_heads=config["n_head"],
        head_dim=config["n_embd"] // config["n_head"],
        ffn_mult=config["n_inner"] // config["n_embd"],
        max_positions=config["n_positions"])
    mesh = placement = None
    if len(devs) > 1:
        mesh = make_mesh((len(devs),), ("model",), devs)
        placement = model.shard_plan(axis="model", tp=len(devs))
    return {"model": model, "mesh": mesh, "placement": placement,
            "names": names, "layers": n}


def reference_train_step(ref, config: dict, *, mode: str, optimizer: dict,
                         reduce_grads, block_rows: int, head_rows: int):
    """The reference's jitted train step (``ref.make_train_step``'s
    signature) in ``mode`` (``f32``; ``bf16``, ``fp8`` for the control)."""
    return ref.make_train_step(
        n_head=config["n_head"], norm=NORM["train"], mode=mode,
        lr=optimizer["learning_rate"], b1=optimizer["beta1"],
        b2=optimizer["beta2"], eps=optimizer["epsilon"],
        reduce_grads=reduce_grads, block_rows=block_rows,
        head_rows=head_rows)


def reference_logits(ref, config: dict, tree, tokens, positions, seg, *,
                     mode: str, block_rows: int):
    """The reference's next-token logits of the serving program at every
    row of one flat buffer: [T, V]."""
    return ref.forward_logits(tree, tokens, positions, seg,
                              n_head=config["n_head"], norm=NORM["serve"],
                              mode=mode, block_rows=block_rows)


def train_step_flops(config: dict, doc_lengths: Sequence[int]) -> float:
    """Forward and backward operations of one train step over documents
    of these lengths (``kernels/gpt2_model.py``)."""
    return MODEL.train_step_flops(
        doc_lengths, config["n_embd"], config["n_inner"],
        layers(config, "train"), config["vocab_size"])
