"""What the benchmark knows of the ``glm4_moe_lite`` family (GLM-4.7-Flash):
latent attention, a leading dense SwiGLU layer, expert layers with a
bias-corrected sigmoid router, a shared expert and dropless routing, and a
depth-1 multi-token-prediction module, as ``models.glm_moe_lite.build``
runs it under ``trainer.SGD``: ONE RANK'S SHARE of an expert-parallel group
(``train.experts_held`` of the published experts, a slice of the
vocabulary).  No serving program yet.

The reference it is asked to call is ``references/glm_moe_lite.py``.
"""

from __future__ import annotations

# the program's builder first: a tree without it fails here, at once, on
# an ImportError, before anything touches the device
from paddle_tpu.models import glm_moe_lite as program

from typing import Dict, Sequence, Tuple  # noqa: E402

from harness import cells  # noqa: E402

FEEDING = {"tokens": 0, "pos": 1, "target": 2}
MODEL = cells.kernel("glm_moe_lite_model")      # operations from shapes

ATTN = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")
FFN = ("w_gate", "w_up", "w_down")


def shapes(config: dict) -> dict:
    """The sizes the counting functions and the readers need."""
    t = config["train"]
    return {
        "hidden": config["hidden_size"], "heads": config["num_attention_heads"],
        "q_rank": config["q_lora_rank"], "kv_rank": config["kv_lora_rank"],
        "nope": config["qk_nope_head_dim"], "rope": config["qk_rope_head_dim"],
        "v": config["v_head_dim"], "dense_width": config["intermediate_size"],
        "expert_width": config["moe_intermediate_size"],
        "n_routed": config["published"]["n_routed_experts"],
        "held": t["experts_held"][1], "top_k": config["num_experts_per_tok"],
        "shared": config["n_shared_experts"], "vocab": config["vocab_size"],
        "dense_layers": t["dense_layers"], "moe_layers": t["moe_layers"],
        "mtp": t["mtp_modules"]}


def layers(config: dict, group: str) -> int:
    """Blocks the group's program runs, the MTP module's among them."""
    g = config[group]
    return g["dense_layers"] + g["moe_layers"] + g["mtp_modules"]


def _block(out: dict, b: str, s: dict, dense: bool) -> None:
    e, h = s["hidden"], s["heads"]
    out[b + "ln1_g"] = out[b + "ln2_g"] = ((e,), "gain")
    a = b + "attn."
    out[a + "wq_a"] = ((e, s["q_rank"]), "matrix")
    out[a + "q_norm_g"] = ((s["q_rank"],), "gain")
    out[a + "wq_b"] = ((s["q_rank"], h * (s["nope"] + s["rope"])), "matrix")
    out[a + "wkv_a"] = ((e, s["kv_rank"] + s["rope"]), "matrix")
    out[a + "kv_norm_g"] = ((s["kv_rank"],), "gain")
    out[a + "wkv_b"] = ((s["kv_rank"], h * (s["nope"] + s["v"])), "matrix")
    out[a + "wo"] = ((h * s["v"], e), "matrix")
    if dense:
        f = s["dense_width"]
        for n, shape in zip(FFN, ((e, f), (e, f), (f, e))):
            out[b + "ffn." + n] = (shape, "matrix")
        return
    m, f = b + "moe.", s["expert_width"]
    out[m + "router"] = ((e, s["n_routed"]), "matrix")
    out[m + "bias"] = ((s["n_routed"],), "bias")
    fs = s["shared"] * f
    for n, routed, shared in zip(
            FFN, ((e, f), (e, f), (f, e)), ((e, fs), (e, fs), (fs, e))):
        out[m + "experts." + n] = ((s["held"],) + routed, "matrix")
        out[m + "shared." + n] = (shared, "matrix")


def leaves(config: dict, group: str
           ) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """{flat name: (shape, kind)} under the reference's names.  The routed
    experts of a layer are three leaves of rank 3, [held, ., .]."""
    s = shapes(config)
    e, v = s["hidden"], s["vocab"]
    out = {"wte": ((v, e), "matrix"), "head": ((e, v), "matrix"),
           "lnf_g": ((e,), "gain")}
    for l in range(s["dense_layers"] + s["moe_layers"]):
        _block(out, f"blocks.{l}.", s, dense=l < s["dense_layers"])
    if s["mtp"]:
        for n in ("hnorm_g", "enorm_g", "norm_g"):
            out["mtp." + n] = ((e,), "gain")
        out["mtp.eh_proj"] = ((2 * e, e), "matrix")
        _block(out, "mtp.block.", s, dense=False)
    return out


def frozen(config: dict):
    """The router's correction biases: they choose experts and the
    optimiser leaves them alone."""
    return [k for k in leaves(config, "train") if k.endswith(".moe.bias")]


def _block_names(names: dict, p: str, r: str, dense: bool) -> None:
    names[p + "_ln1.gamma"] = r + "ln1_g"
    names[p + "_ln2.gamma"] = r + "ln2_g"
    for w in ATTN:
        names[f"{p}_attn.{w}"] = f"{r}attn.{w}"
    names[p + "_attn.q_norm"] = r + "attn.q_norm_g"
    names[p + "_attn.kv_norm"] = r + "attn.kv_norm_g"
    if dense:
        for w in FFN:
            names[f"{p}_ffn.{w}"] = f"{r}ffn.{w}"
        return
    names[p + "_moe.router"] = r + "moe.router"
    names[p + "_moe.bias"] = r + "moe.bias"
    for w in FFN:
        names[f"{p}_moe.{w}"] = f"{r}moe.experts.{w}"
        names[f"{p}_moe.shared_{w[2:]}"] = f"{r}moe.shared.{w}"


def train_program(config: dict) -> dict:
    """The training program through ``models.glm_moe_lite.build``: its
    ``cost`` list, ``names``, ``feeding`` and how many ``layers`` run."""
    s, t = shapes(config), config["train"]
    *_, cost = program.build(
        vocab_size=s["vocab"], hidden_size=s["hidden"],
        n_dense_layers=s["dense_layers"], n_moe_layers=s["moe_layers"],
        num_heads=s["heads"], q_lora_rank=s["q_rank"],
        kv_lora_rank=s["kv_rank"], qk_nope_head_dim=s["nope"],
        qk_rope_head_dim=s["rope"], v_head_dim=s["v"],
        intermediate_size=s["dense_width"],
        moe_intermediate_size=s["expert_width"],
        n_routed_experts=s["n_routed"],
        held_experts=tuple(t["experts_held"]),
        num_experts_per_tok=s["top_k"], n_shared_experts=s["shared"],
        routed_scaling_factor=config["routed_scaling_factor"],
        mtp_layers=s["mtp"], mtp_weight=t["mtp_weight"],
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=config["rms_norm_eps"], max_len=config["n_positions"],
        remat=bool(t.get("remat", False)))
    names = {"tok_embed.w": "wte", "lm_head.w0": "head",
             "final_ln.gamma": "lnf_g"}
    for l in range(s["dense_layers"] + s["moe_layers"]):
        _block_names(names, f"blk{l}", f"blocks.{l}.",
                     dense=l < s["dense_layers"])
    if s["mtp"]:
        names.update({"mtp_hnorm.gamma": "mtp.hnorm_g",
                      "mtp_enorm.gamma": "mtp.enorm_g",
                      "mtp_final_ln.gamma": "mtp.norm_g",
                      "mtp_eh_proj.w0": "mtp.eh_proj"})
        _block_names(names, "mtp", "mtp.block.", dense=False)
    return {"cost": cost, "names": names, "feeding": FEEDING,
            "layers": layers(config, "train")}


def arch(config: dict) -> dict:
    """The reference's ``arch`` argument."""
    t = config["train"]
    return {"n_head": config["num_attention_heads"],
            "nope": config["qk_nope_head_dim"],
            "rope": config["qk_rope_head_dim"],
            "theta": float(config["rope_theta"]),
            "eps": config["rms_norm_eps"],
            "top_k": config["num_experts_per_tok"],
            "scaling": config["routed_scaling_factor"],
            "first_held": t["experts_held"][0],
            "mtp_weight": t["mtp_weight"]}


def _outside_the_compile_cache(fn):
    """``fn`` with JAX's persistent compilation cache switched off around
    each call.  The reference's step at the cell's size is 557 MiB as a
    serialized executable (20 MiB of HLO: float32 products at the highest
    precision, every block recomputed), more than a whole cache of a few
    hundred MiB may hold: written there it would evict the train step's
    own entry and every other cell's, run after run.  The cache's place
    and size are left as they are."""
    import functools

    import jax
    from jax.experimental.compilation_cache import compilation_cache

    @functools.wraps(fn)
    def call(*args):
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            return fn(*args)
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()

    return call


def reference_train_step(ref, config: dict, *, mode: str, optimizer: dict,
                         reduce_grads, block_rows: int, head_rows: int):
    """The reference's jitted train step in ``mode`` (``f32``; ``bf16``,
    ``fp8`` for the control), given the same share as the program."""
    return _outside_the_compile_cache(ref.make_train_step(
        arch=arch(config), mode=mode, lr=optimizer["learning_rate"],
        b1=optimizer["beta1"], b2=optimizer["beta2"],
        eps=optimizer["epsilon"], reduce_grads=reduce_grads,
        block_rows=block_rows, head_rows=head_rows))


def train_step_flops(config: dict, doc_lengths: Sequence[int]) -> float:
    """Forward and backward operations of one train step over documents
    of these lengths (``kernels/glm_moe_lite_model.py``): by shapes, the
    routed experts at their expected rows, nothing recomputed."""
    return MODEL.train_step_flops(doc_lengths, shapes(config))
