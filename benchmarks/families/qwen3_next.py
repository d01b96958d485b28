"""What the benchmark knows of the ``qwen3_next`` family
(Qwen3-Next-80B-A3B): gated delta-rule (linear-attention) layers with a
gated grouped-query attention layer as every ``full_attention_interval``-th,
each followed by an expert layer with a softmax router, dropless routing
and a gated shared expert, as ``models.qwen3_next.build`` runs it under
``trainer.SGD``: ONE RANK'S SHARE of an expert-parallel group
(``train.experts_held`` of the published experts, a slice of the
vocabulary).  No serving program yet.

The reference it is asked to call is ``references/qwen3_next.py``.
"""

from __future__ import annotations

# the program's builder first: a tree without it fails here, at once, on
# an ImportError, before anything touches the device
from paddle_tpu.models import qwen3_next as program

from typing import Dict, Sequence, Tuple  # noqa: E402

from harness import cells  # noqa: E402

FEEDING = {"tokens": 0, "pos": 1, "target": 2}
MODEL = cells.kernel("qwen3_next_model")        # operations from shapes

FFN = ("w_gate", "w_up", "w_down")
# the parameters of the two mixing layers under the program's names; the
# reference's leaf has the same name, a norm's gain with ``_g`` behind it
DELTA = ("w_qkvz", "w_ba", "conv", "a_log", "dt_bias", "norm", "wo")
ATTN = ("wq", "wk", "wv", "q_norm", "k_norm", "wo")


def leaf_of(param: str) -> str:
    return param + "_g" if param.endswith("norm") else param


def shapes(config: dict) -> dict:
    """The sizes the counting functions and the readers need."""
    t = config["train"]
    return {
        "hidden": config["hidden_size"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "k_heads": config["linear_num_key_heads"],
        "v_heads": config["linear_num_value_heads"],
        "dk": config["linear_key_head_dim"],
        "dv": config["linear_value_head_dim"],
        "taps": config["linear_conv_kernel_dim"],
        "expert_width": config["moe_intermediate_size"],
        "shared_width": config["shared_expert_intermediate_size"],
        "n_routed": config["published"]["num_experts"],
        "held": t["experts_held"][1], "top_k": config["num_experts_per_tok"],
        "vocab": config["vocab_size"], "layers": t["layers"],
        "interval": config["full_attention_interval"]}


def layers(config: dict, group: str) -> int:
    """Blocks the group's program runs."""
    return config[group]["layers"]


def is_attention(l: int, s: dict) -> bool:
    return (l + 1) % s["interval"] == 0


def leaves(config: dict, group: str
           ) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """{flat name: (shape, kind)} under the reference's names.  The routed
    experts of a layer are three leaves of rank 3, [held, ., .]."""
    s = shapes(config)
    e, v, f = s["hidden"], s["vocab"], s["expert_width"]
    nq, nv = s["k_heads"] * s["dk"], s["v_heads"] * s["dv"]
    out = {"wte": ((v, e), "matrix"), "head": ((e, v), "matrix"),
           "lnf_g": ((e,), "gain")}
    for l in range(s["layers"]):
        b = f"blocks.{l}."
        out[b + "ln1_g"] = out[b + "ln2_g"] = ((e,), "gain")
        if is_attention(l, s):
            a, h, d = b + "attn.", s["heads"], s["head_dim"]
            out[a + "wq"] = ((e, h * 2 * d), "matrix")
            out[a + "wk"] = out[a + "wv"] = ((e, s["kv_heads"] * d), "matrix")
            out[a + "q_norm_g"] = out[a + "k_norm_g"] = ((d,), "gain")
            out[a + "wo"] = ((h * d, e), "matrix")
        else:
            a = b + "delta."
            out[a + "w_qkvz"] = ((e, 2 * nq + 2 * nv), "matrix")
            out[a + "w_ba"] = ((e, 2 * s["v_heads"]), "matrix")
            # taps of order one (1 + noise; the library's Conv1d draws them
            # uniform in +-0.5), so SiLU and the norms see values of order 1
            out[a + "conv"] = ((2 * nq + nv, s["taps"]), "gain")
            out[a + "a_log"] = out[a + "dt_bias"] = ((s["v_heads"],), "bias")
            out[a + "norm_g"] = ((s["dv"],), "gain")
            out[a + "wo"] = ((nv, e), "matrix")
        m, fs = b + "moe.", s["shared_width"]
        out[m + "router"] = ((e, s["n_routed"]), "matrix")
        out[m + "shared_mix"] = ((e, 1), "matrix")
        for n, routed, shared in zip(
                FFN, ((e, f), (e, f), (f, e)), ((e, fs), (e, fs), (fs, e))):
            out[m + "experts." + n] = ((s["held"],) + routed, "matrix")
            out[m + "shared." + n] = (shared, "matrix")
    return out


def train_program(config: dict) -> dict:
    """The training program through ``models.qwen3_next.build``: its
    ``cost``, ``names``, ``feeding`` and how many ``layers`` run."""
    s, t = shapes(config), config["train"]
    *_, cost = program.build(
        vocab_size=s["vocab"], hidden_size=s["hidden"],
        num_layers=s["layers"], full_attention_interval=s["interval"],
        num_heads=s["heads"], num_kv_heads=s["kv_heads"],
        head_dim=s["head_dim"],
        partial_rotary_factor=config["partial_rotary_factor"],
        linear_num_key_heads=s["k_heads"],
        linear_num_value_heads=s["v_heads"], linear_key_head_dim=s["dk"],
        linear_value_head_dim=s["dv"], linear_conv_kernel_dim=s["taps"],
        moe_intermediate_size=s["expert_width"],
        shared_expert_intermediate_size=s["shared_width"],
        num_experts=s["n_routed"], held_experts=tuple(t["experts_held"]),
        num_experts_per_tok=s["top_k"],
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=config["rms_norm_eps"], max_len=config["n_positions"],
        remat=bool(t.get("remat", False)))
    names = {"tok_embed.w": "wte", "lm_head.w0": "head",
             "final_ln.gamma": "lnf_g"}
    for l in range(s["layers"]):
        p, r = f"blk{l}", f"blocks.{l}."
        names[p + "_ln1.gamma"] = r + "ln1_g"
        names[p + "_ln2.gamma"] = r + "ln2_g"
        mix, node, leaf = (ATTN, "_attn.", "attn.") if is_attention(l, s) \
            else (DELTA, "_gdn.", "delta.")
        for param in mix:
            names[p + node + param] = r + leaf + leaf_of(param)
        names[p + "_moe.router"] = r + "moe.router"
        names[p + "_moe.shared_mix"] = r + "moe.shared_mix"
        for w in FFN:
            names[f"{p}_moe.{w}"] = f"{r}moe.experts.{w}"
            names[f"{p}_moe.shared_{w[2:]}"] = f"{r}moe.shared.{w}"
    return {"cost": cost, "names": names, "feeding": FEEDING,
            "layers": layers(config, "train")}


def arch(config: dict) -> dict:
    """The reference's ``arch`` argument."""
    return {"n_head": config["num_attention_heads"],
            "n_kv": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "rotary_dim": int(config["head_dim"]
                              * config["partial_rotary_factor"]),
            "theta": float(config["rope_theta"]),
            "eps": config["rms_norm_eps"],
            "lin_k_heads": config["linear_num_key_heads"],
            "lin_v_heads": config["linear_num_value_heads"],
            "lin_dk": config["linear_key_head_dim"],
            "lin_dv": config["linear_value_head_dim"],
            "top_k": config["num_experts_per_tok"],
            "first_held": config["train"]["experts_held"][0]}


def _outside_the_compile_cache(fn):
    """``fn`` with JAX's persistent compilation cache switched off around
    each call: the reference's step at the cell's size (float32 products
    at the highest precision, every block recomputed) is hundreds of MiB
    as a serialized executable, more than a whole cache may hold; written
    there it would evict the train step's own entry and every other
    cell's, run after run (as ``families/glm_moe_lite.py``)."""
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
    of these lengths (``kernels/qwen3_next_model.py``): by shapes, the
    routed experts at their expected rows, nothing recomputed."""
    return MODEL.train_step_flops(doc_lengths, shapes(config))
