"""User utilities: config dump, model diagram, torch parameter import.

Reference analog: python/paddle/utils — make_model_diagram.py (graphviz
dot export of a ModelConfig), dump_config.py / show_pb.py, and
torch2paddle.py (import torch-trained weights).
"""

from __future__ import annotations

import json
from typing import Dict, List, Optional

import numpy as np

from paddle_tpu.parameters import Parameters
from paddle_tpu.platform.enforce import enforce_that
from paddle_tpu.topology import Topology


def topology_to_config(topology: Topology) -> Dict:
    """Serialize a Topology to a JSON-able dict — the ModelConfig proto
    analog (config_parser output). Structural only: layer graph, sizes,
    parameter shapes; compute stays in python (the jit'd forward)."""
    layers: List[Dict] = []
    name_to_param = {}
    for node in topology.nodes:
        entry = {
            "name": node.name,
            "type": node.layer_type,
            "size": node.size,
            "inputs": [i.name for i in node.inputs],
            "is_sequence": bool(node.is_sequence),
        }
        if getattr(node, "img_shape", None):
            entry["img_shape"] = list(node.img_shape)
        if node.params:
            entry["params"] = {}
            for pname, spec in node.params.items():
                full = spec.attr.name or f"{node.name}.{pname}"
                entry["params"][pname] = {"name": full,
                                          "shape": list(spec.shape)}
                name_to_param[full] = list(spec.shape)
        layers.append(entry)
    return {
        "format": "paddle_tpu_model_config_v1",
        "layers": layers,
        "parameters": [{"name": k, "shape": v}
                       for k, v in sorted(name_to_param.items())],
        "input_layers": [n.name for n in topology.data_nodes],
        "output_layers": [n.name for n in topology.outputs],
    }


def dump_config(topology: Topology, indent: int = 2) -> str:
    """config dump (reference: paddle dump_config / utils.dump_v2_config)."""
    return json.dumps(topology_to_config(topology), indent=indent)


def make_model_diagram(topology: Topology,
                       graph_name: str = "model") -> str:
    """Graphviz dot text of the layer graph (reference:
    python/paddle/utils/make_model_diagram.py)."""
    cfg = topology_to_config(topology)
    lines = [f"digraph {graph_name} {{", "  rankdir=TB;"]
    for lay in cfg["layers"]:
        shape = "box"
        if lay["type"] == "data":
            shape = "oval"
        elif lay["name"] in cfg["output_layers"]:
            shape = "doubleoctagon"
        label = f"{lay['name']}\\n{lay['type']}"
        if lay["size"]:
            label += f" [{lay['size']}]"
        lines.append(f'  "{lay["name"]}" [shape={shape}, label="{label}"];')
    for lay in cfg["layers"]:
        for src in lay["inputs"]:
            lines.append(f'  "{src}" -> "{lay["name"]}";')
    lines.append("}")
    return "\n".join(lines)


def gradient_check(cost, parameters, feeds, *, sample_entries: int = 8,
                   eps: float = 1e-3, seed: int = 0,
                   rtol: float = 2e-2) -> Dict[str, float]:
    """Numeric-vs-analytic gradient check over a whole topology — the user
    surface of the reference trainer's gradient check job
    (Trainer::train's test_all_data_in_one_period gradient path and the
    per-layer testLayerGrad strategy, gserver/tests/LayerGradUtil.h:298).

    For each parameter, ``sample_entries`` random entries are perturbed
    (central differences, f64 accumulation of the cost) and compared to
    jax.grad of the summed cost. Returns {param_name: max relative error}
    and raises EnforceError when any exceeds ``rtol``.
    """
    import jax
    import jax.numpy as jnp

    from paddle_tpu.platform.flags import FLAGS
    from paddle_tpu.trainer import _reduce_cost  # local: avoids a cycle

    old_bf16 = FLAGS.use_bf16
    FLAGS.use_bf16 = False  # central differences drown in bf16 loss noise
    try:
        topo = Topology([cost])
        specs = topo.param_specs()
        pdict = {k: jnp.asarray(v) for k, v in dict(
            parameters.as_dict() if hasattr(parameters, "as_dict")
            else parameters).items() if k in specs}
        state = topo.init_state()

        def loss_fn(p):
            outs, _ = topo.forward(p, state, feeds, train=False)
            return _reduce_cost(outs[0])

        analytic = jax.grad(loss_fn)(pdict)
        loss_jit = jax.jit(loss_fn)
        rng = np.random.RandomState(seed)
        report: Dict[str, float] = {}

        def loss_at(name, val, i, delta):
            flat = np.asarray(val, np.float64).ravel()
            flat[i] += delta
            return float(loss_jit(
                {**pdict, name: jnp.asarray(flat.reshape(val.shape),
                                            val.dtype)}))

        for name, val in pdict.items():
            flat_size = int(val.size)
            idxs = rng.choice(flat_size, size=min(sample_entries, flat_size),
                              replace=False)
            ana_flat = np.asarray(analytic[name]).ravel()  # one D2H copy
            worst = 0.0
            for i in idxs:
                ana = float(ana_flat[i])

                def rel_err(e):
                    num = (loss_at(name, val, i, +e)
                           - loss_at(name, val, i, -e)) / (2 * e)
                    return abs(num - ana) / max(abs(num), abs(ana), 1e-4)

                err = rel_err(eps)
                if err > rtol:
                    # two ways central differences fail on a CORRECT
                    # gradient: a kink (relu/abs) inside ±eps — smaller
                    # eps shrinks the window — and f32 loss resolution
                    # drowning a small slope — larger eps lifts the
                    # signal above the ~1e-7 relative ulp. Retry both
                    # before calling it wrong (the reference's
                    # perturbation checks share these caveats,
                    # LayerGradUtil.h:203); a genuinely wrong analytic
                    # gradient fails at every eps.
                    err = min(err, rel_err(eps / 8), rel_err(eps * 8))
                worst = max(worst, err)
            report[name] = worst
        # full report first, ONE failure listing every offender
        bad = {k: v for k, v in report.items() if v > rtol}
        enforce_that(not bad, "gradient check failed: " + ", ".join(
            f"{k}: rel err {v:.4g} > {rtol}" for k, v in sorted(bad.items())),
            context="gradient_check")
        return report
    finally:
        FLAGS.use_bf16 = old_bf16


def compare_topologies(node_a, node_b, feeds_a, feeds_b=None, *,
                       seed: int = 0, param_link: Optional[Dict[str, str]] = None,
                       check_inputs: tuple = (), rtol: float = 1e-4,
                       atol: float = 1e-5):
    """Assert two differently-expressed topologies compute the SAME function:
    identical outputs AND identical gradients on the same data.

    The network-equivalence harness (reference:
    gserver/tests/test_NetworkCompare.cpp + trainer/tests/
    test_CompareTwoNets.cpp — config pairs trained side by side with
    compareGradient): express one computation two ways (fc vs
    mixed-projections, lstmemory vs a recurrent_group of lstm_step, flash vs
    plain attention kernels, ...) and require bit-level agreement to float
    tolerance.

    Parameters are LINKED BY NAME: each topology is initialized with the
    same seed, then every parameter name they share (plus ``param_link``
    entries mapping b-name → a-name) is copied from A into B, so linked
    weights are identical. Use ``ParamAttr(name=...)`` in the configs to
    give corresponding weights the same name. Gradients of the
    mean-reduced first output are compared for every linked parameter and
    for each feed name in ``check_inputs`` (feeds must then be identical
    dense arrays in both feed dicts).
    Returns (out_a, out_b, grads_a, grads_b).
    """
    import jax
    import jax.numpy as jnp

    from paddle_tpu.platform.flags import FLAGS
    from paddle_tpu.sequence import SequenceBatch
    from paddle_tpu.trainer import _reduce_cost

    feeds_b = feeds_a if feeds_b is None else feeds_b
    param_link = dict(param_link or {})

    old_bf16 = FLAGS.use_bf16
    FLAGS.use_bf16 = False  # bit-compare needs one rounding behavior
    try:
        topo_a, topo_b = Topology([node_a]), Topology([node_b])
        pa = dict(Parameters.from_topology(topo_a, seed=seed).as_dict())
        pb = dict(Parameters.from_topology(topo_b, seed=seed).as_dict())
        shared = sorted(set(pa) & set(pb))
        for nb in shared:
            param_link.setdefault(nb, nb)
        enforce_that(bool(param_link) or bool(check_inputs),
                     "nothing to compare gradients through — link weights "
                     "via ParamAttr names or pass check_inputs",
                     context="compare")
        for nb, na in param_link.items():
            enforce_that(np.shape(pa[na]) == np.shape(pb[nb]),
                         f"linked param shape mismatch {na}~{nb}",
                         context="compare")
            pb[nb] = pa[na]

        def run(topo, params, feeds):
            in_names = list(check_inputs)

            # one forward+backward: params and checked inputs differentiate
            # together (argnums pair), instead of a second full pass
            def loss_fn(p, fvals):
                f = {**feeds, **dict(zip(in_names, fvals))}
                outs, _ = topo.forward(p, topo.init_state(), f, train=False)
                o = outs[0]
                return _reduce_cost(o), (o.data if isinstance(o, SequenceBatch)
                                         else o)

            fvals = [jnp.asarray(feeds[n], jnp.float32) for n in in_names]
            (loss, out), (gp, gf) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True)(params, fvals)
            return out, gp, dict(zip(in_names, gf))

        out_a, gpa, gia = run(topo_a, pa, feeds_a)
        out_b, gpb, gib = run(topo_b, pb, feeds_b)

        oa, ob = np.asarray(out_a), np.asarray(out_b)
        # image layers may emit [B,H,W,C] where an equivalent mixed/operator
        # path emits the flat [B,H*W*C]; canonicalize to per-example rows
        np.testing.assert_allclose(oa.reshape(oa.shape[0], -1),
                                   ob.reshape(ob.shape[0], -1),
                                   rtol=rtol, atol=atol,
                                   err_msg="outputs differ")
        for nb, na in sorted(param_link.items()):
            np.testing.assert_allclose(
                np.asarray(gpa[na]), np.asarray(gpb[nb]), rtol=rtol,
                atol=atol, err_msg=f"grad differs for linked param {na}~{nb}")
        for n in check_inputs:
            np.testing.assert_allclose(
                np.asarray(gia[n]), np.asarray(gib[n]), rtol=rtol, atol=atol,
                err_msg=f"grad differs for input {n}")
        return out_a, out_b, gpa, gpb
    finally:
        FLAGS.use_bf16 = old_bf16


def param_to_text(value, path: str) -> None:
    """Dump one parameter as the embedding-model text format (reference:
    v1_api_demo/model_zoo/embedding/paraconvert.py binary2text — header
    line ``version,floatSize,paraCount`` then comma-joined rows)."""
    arr = np.asarray(value, dtype=np.float32)
    rows = arr.reshape(-1, arr.shape[-1]) if arr.ndim > 1 else arr.reshape(1, -1)
    with open(path, "w") as f:
        f.write(f"0,4,{arr.size}\n")
        for row in rows:
            f.write(",".join(f"{x:.7f}" for x in row) + "\n")


def text_to_param(path: str, dim: Optional[int] = None) -> np.ndarray:
    """Load a text-format parameter file (paraconvert.py text2binary
    analog). Returns [rows, dim] float32 (or flat when rows carry no
    consistent dim)."""
    with open(path) as f:
        header = f.readline().strip().split(",")
        count = int(header[2])
        rows = [np.array(line.strip().split(","), dtype=np.float32)
                for line in f if line.strip()]
    flat = np.concatenate(rows) if rows else np.zeros(0, np.float32)
    if flat.size != count:
        raise ValueError(f"{path}: header says {count} values, got {flat.size}")
    if dim:
        return flat.reshape(-1, dim)
    widths = {r.size for r in rows}
    return flat.reshape(len(rows), rows[0].size) if len(widths) == 1 else flat


def extract_embedding(parameters: Parameters, name: str,
                      word_ids) -> np.ndarray:
    """Slice pretrained embedding rows for a word subset (reference:
    v1_api_demo/model_zoo/embedding/extract_para.py — the paragraph-vector
    extraction workflow: trained table -> the rows your task dict needs)."""
    table = np.asarray(parameters[name])
    return table[np.asarray(list(word_ids), dtype=np.int64)]


def torch2paddle(state_dict, parameters: Parameters,
                 name_map: Optional[Dict[str, str]] = None,
                 transpose_linear: bool = True) -> List[str]:
    """Import a torch ``state_dict`` into ``parameters``
    (reference: python/paddle/utils/torch2paddle.py).

    Matching is by ``name_map`` (torch name -> our param name) when given,
    else by identical name, else by unique shape match. torch Linear
    weights are [out, in]; ours are [in, out] (``transpose_linear``).
    Returns the list of imported parameter names."""
    ours = {k: np.asarray(v) for k, v in parameters.items()}
    imported: List[str] = []
    by_shape: Dict[tuple, List[str]] = {}
    for k, v in ours.items():
        by_shape.setdefault(tuple(v.shape), []).append(k)

    for tname, tval in state_dict.items():
        arr = np.asarray(tval.detach().cpu().numpy()
                         if hasattr(tval, "detach") else tval)
        target = None
        if name_map and tname in name_map:
            target = name_map[tname]
        elif tname in ours:
            target = tname
        else:
            cands = by_shape.get(tuple(arr.shape), [])
            cands_t = by_shape.get(tuple(arr.T.shape), []) \
                if arr.ndim == 2 else []
            if len(cands) == 1:
                target = cands[0]
            elif not cands and len(cands_t) == 1 and transpose_linear:
                target = cands_t[0]
        if target is None:
            continue
        dst_shape = ours[target].shape
        if arr.shape != dst_shape:
            if transpose_linear and arr.ndim == 2 \
                    and arr.T.shape == dst_shape:
                arr = arr.T
            else:
                continue
        elif (transpose_linear and arr.ndim == 2
              and arr.shape[0] == arr.shape[1]
              and tname.rsplit(".", 1)[-1] == "weight"):
            # square torch Linear weights match both ways; torch stores
            # [out, in] so '*.weight' still needs the transpose (square
            # embedding tables named '.weight' would be misflipped — pass
            # an explicit name_map for those)
            arr = arr.T
        parameters[target] = arr.astype(ours[target].dtype)
        imported.append(target)
    return imported
