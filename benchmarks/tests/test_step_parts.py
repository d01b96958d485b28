"""``harness/step_parts.py``: a fusion's members from the HLO proto of a
trace file, the rules that put each device operation down to one phase and
one part, and the seven readers built on them (PR 39)."""

import importlib.util
import json
import os
import re
import types

import pytest

from harness import cells, step_parts as S, trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "data", "small_train_v5e.xplane.pb")
TRAIN = ["train-1.3b-seq2048", "train-1.3b-packed",
         "train-glm47flash-ep8-seq4096", "train-qwen3next-ep16-seq8192"]
# name: (source, layer, cells)
NEW = {
    "update_ms_per_step.train": ("device_trace", "optimizer", TRAIN),
    "norm_ms_per_step.train": ("device_trace", "compiled train step", TRAIN),
    "loss_ms_per_step.train": ("device_trace", "compiled train step", TRAIN),
    "recompute_ms_per_step.train": ("device_trace", "compiled train step",
                                    TRAIN[2:]),
    "step_unattributed_ms.train": ("device_trace", "compiled train step",
                                   TRAIN),
    "feed_ms_per_step.train": ("program_span", "pass and batch loop, feeder",
                               TRAIN),
    "dispatch_ms_per_step.train": ("program_span",
                                   "pass and batch loop, feeder", TRAIN),
}


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"),
        os.path.join(cells.ROOT, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---- a made-up HLO proto, as the metadata plane of a TPU's trace keeps it -----

def varint(n):
    out = b""
    while True:
        out += bytes([n & 0x7F | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def field(number, payload):
    return varint(number << 3 | 2) + varint(len(payload)) + payload


def number(n, value):
    return varint(n << 3) + varint(value)


def instruction(name, opcode, op_name="", called=(), packed=False):
    ins = field(1, name.encode()) + field(2, opcode.encode())
    if op_name:
        ins += field(7, field(2, op_name.encode()))
    if packed:                                   # proto3 packs repeated ints
        ins += field(38, b"".join(varint(c) for c in called))
    else:
        ins += b"".join(number(38, c) for c in called)
    return field(2, ins)


def trace_file(tmp_path, computations, name="made_up"):
    """An ``.xplane.pb`` with one program: {computation id: [instruction]}."""
    module = field(1, b"jit_step") + b"".join(
        field(3, field(1, f"comp{cid}".encode()) + b"".join(rows)
              + number(5, cid)) for cid, rows in computations.items())
    stat = number(1, 1) + field(6, field(1, module))
    meta = number(1, 1) + field(5, stat)
    planes = field(1, field(2, b"/device:TPU:0")) + field(1, field(
        2, b"/host:metadata") + field(4, number(1, 1) + field(2, meta)))
    path = tmp_path / f"{name}.xplane.pb"
    path.write_bytes(planes)
    return str(path)


UP = "jit(step)/jvp(fc)/blk0_ffn_up/dot_general"
DW = "jit(step)/transpose(jvp(fc))/blk0_ffn_up/dot_general"
SCAN = "jit(step)/jvp(remat_blk0)/gated_delta_net/blk0_gdn/gdn/gdn.scan/while"
AGAIN = ("jit(step)/transpose(jvp(remat_blk0))/jvp(remat_blk0)/checkpoint/"
         "rematted_computation/layer_norm/blk0_ln1/mul")
HEAD = "jit(step)/jvp(lm_head_cost)/head/while/body/closed_call/"
KINDS = "fc|layer_norm|gated_delta_net|lm_head_cost|classification_cost"


def parents(op_name):
    """The same operation as the parent's program names it: no kinds, the
    optimizer and the costs' reduction bare."""
    op_name = re.sub(rf"jvp\(({KINDS})\)(\)?)/(\w+)/", r"jvp(\3)\2/", op_name)
    op_name = re.sub(rf"/({KINDS})/", "/", op_name)
    return re.sub(r"/opt/opt\.\w+|jvp\(step\.loss\)/", "", op_name)


def step_program(scoped=True):
    """The step's program; ``scoped=False`` gives the parent's shape of it."""
    name = (lambda op: op) if scoped else parents
    main = [
        instruction("fusion.1", "fusion", name(UP), called=[10]),
        instruction("fusion.2", "fusion", name(DW), called=[11], packed=True),
        instruction("while.3", "while", name(SCAN), called=[13, 14]),
        instruction("copy.5", "copy"),
        instruction("fusion.6", "fusion", name(AGAIN)),
        instruction("fusion.7", "fusion", name(
            "jit(step)/transpose(jvp(classification_cost))/cost_0/sub")),
        instruction("fusion.8", "fusion",
                    name("jit(step)/jvp(step.loss)/reduce_sum")),
        instruction("fusion.9", "fusion",
                    name("jit(step)/opt/opt.clip/sqrt")),
        instruction("fusion.10", "fusion", "jit(step)/mul"),
        instruction("fusion.11", "fusion", name(HEAD + "head.xent/exp")),
        instruction("fusion.12", "fusion",
                    name(HEAD + "head.logits/dot_general")),
    ]
    return {
        1: main,
        10: [instruction("p.0", "parameter"),
             instruction("dot.1", "dot", name(UP)),
             instruction("add.1", "add", name(
                 "jit(step)/jvp(layer_norm)/jvp(blk0_ln1)/add"))],
        11: [instruction("conv.1", "convolution", name(DW)),
             instruction("mul.1", "multiply",
                         name("jit(step)/opt/opt.update/mul")),
             instruction("fusion.20", "fusion", "", called=[12])],
        12: [instruction("sqrt.1", "sqrt",
                         name("jit(step)/opt/opt.update/sqrt"))],
        13: [instruction("fusion.4", "fusion",
                         name(SCAN + "/body/closed_call/dot_general"))],
        14: [instruction("lt.1", "compare", name(SCAN + "/cond/lt"))],
    }


def op(name, t0, seconds):
    return T.Op(f"%{name}", f"%{name} = f32[8,8] fusion()", t0, t0 + seconds)


OPS = [op("fusion.1", 0.0, 1.0),          # forward fc, shares a norm's add
       op("fusion.2", 1.0, 2.0),          # the update behind fc's dW
       op("while.3", 3.0, 2.0),           # forward gated_delta_net, 3..5
       op("fusion.4", 3.5, 1.0),          # its body, inside
       op("copy.5", 5.0, 0.5),            # no op_name
       op("fusion.6", 5.5, 0.5),          # recompute layer_norm
       op("fusion.7", 6.0, 0.5),          # backward classification_cost
       op("fusion.8", 6.5, 0.25),         # forward step.loss
       op("fusion.9", 6.75, 0.25),        # update opt.clip
       op("fusion.10", 7.0, 0.5),         # a bare op_name
       op("fusion.11", 7.5, 0.5),         # lm_head_cost / head.xent
       op("fusion.12", 8.0, 1.0),         # lm_head_cost / head.logits
       op("fusion.1", 9.5, 1.0)]          # cut by the window's end at 10


def made_up_run(tmp_path, scoped=True, spans=()):
    tr = T.Trace([T.Chip(0, list(OPS), [])],
                 [("dispatch", 0.1, 0.2), ("dispatch", 4.0, 4.1)],
                 (0.0, 10.0))
    path = trace_file(tmp_path, step_program(scoped),
                      name="scoped" if scoped else "parent")
    return {"kind": "train", "trace": tr, "program_spans": list(spans),
            "tracing": types.SimpleNamespace(file=lambda: path)}


# ---- the member reader --------------------------------------------------------

def test_a_fusions_members_are_read_with_nested_fusions_theirs(tmp_path):
    prog = S.program(trace_file(tmp_path, step_program()))
    assert prog["%fusion.1"].members == [
        ("dot", UP), ("add", "jit(step)/jvp(layer_norm)/jvp(blk0_ln1)/add")]
    # packed ids; the parameter left out; the nested fusion's sqrt with them
    assert prog["%fusion.2"].members == [
        ("convolution", DW), ("multiply", "jit(step)/opt/opt.update/mul"),
        ("sqrt", "jit(step)/opt/opt.update/sqrt")]
    assert prog["%fusion.2"].op_name == DW
    assert prog["%while.3"].members == [] and prog["%copy.5"].op_name == ""
    assert "%fusion.4" in prog and "%sqrt.1" in prog   # every computation's
    empty = tmp_path / "no_hlo.xplane.pb"
    empty.write_bytes(field(1, field(2, b"/device:TPU:0")))
    assert S.program(str(empty)) == {}


@pytest.mark.parametrize("op_name, want", [
    (UP, ("forward", "fc", "")),
    (DW, ("backward", "fc", "")),
    (SCAN, ("forward", "gated_delta_net", "gdn.scan")),
    (AGAIN, ("recompute", "layer_norm", "")),
    ("jit(step)/transpose(jvp(remat_blk0))/jvp(remat_blk0)/checkpoint/"
     "multi_head_attention/blk0_attn/attn.proj/dot_general",
     ("backward", "multi_head_attention", "attn.proj")),
    ("jit(guarded_step)/opt/opt.update/mul", ("update", "opt.update", "")),
    ("jit(step)/opt/mul", ("update", "opt", "")),
    ("jit(step)/jvp(step.loss)/jit(_where)/select_n",
     ("forward", "step.loss", "")),
    ("jit(step)/step.guard/select_n", ("other", "step.guard", "")),
    (HEAD + "head.xent/exp", ("forward", "lm_head_cost", "head.xent")),
    # merged instructions keep both names: the first decides
    ("jit(step)/jvp(remat_b)/fc/f/attn.core/min;jit(step)/jvp(remat_b)/fc/f",
     ("forward", "fc", "attn.core")),
    ("jit(step)/mul", None),
    ("jit(step)/transpose(jvp())/jit(clip)/max", None),
    ("", None),
])
def test_an_op_name_gives_phase_part_and_inner_scope(op_name, want):
    assert S.classify(op_name) == want


# ---- the rules, on a made-up run ----------------------------------------------

def test_each_operation_gets_one_class_and_its_time_counts_once(tmp_path):
    run = made_up_run(tmp_path)
    got = S.attribute(run["trace"], S.program(run["tracing"].file()))
    sec = got["seconds"]
    assert sec == pytest.approx({
        ("forward", "fc"): 1.5,                 # the second cut at 10.0
        ("update", "opt.update"): 2.0,          # opt + dot: once, here
        ("forward", "gated_delta_net"): 2.0,    # the while and its body
        ("other", "unattributed"): 1.0,         # no op_name; a bare one
        ("recompute", "layer_norm"): 0.5,
        ("backward", "classification_cost"): 0.5,
        ("forward", "step.loss"): 0.25,
        ("update", "opt.clip"): 0.25,
        ("forward", "lm_head_cost"): 1.5})
    # nothing in two totals: the classes sum to the busy time
    assert sum(sec.values()) == pytest.approx(T.busy_seconds(run["trace"]))
    assert ("backward", "fc") not in sec
    # ... and the shared operations are listed again, by pair
    assert got["shared"] == pytest.approx({
        ("update opt.update", "backward fc"): 2.0,
        ("forward fc", "forward layer_norm"): 1.5})
    assert got["inner"] == pytest.approx({
        ("forward", "gated_delta_net", "gdn.scan"): 2.0,
        ("forward", "lm_head_cost", "head.xent"): 0.5,
        ("forward", "lm_head_cost", "head.logits"): 1.0})
    assert got["unattributed"] == [("copy f32[8,8]", 0.5),
                                   ("fusion f32[8,8]", 0.5)]


def test_a_fusion_without_a_name_takes_its_members_commonest_class():
    norm = "jit(step)/transpose(jvp(rms_norm))/ln/mul"
    conv = "jit(step)/transpose(jvp(gated_delta_net))/g/gdn/gdn.conv/mul"
    nameless = S.Instr("fusion", "", [("multiply", conv), ("copy", ""),
                                      ("multiply", norm), ("add", conv)])
    assert S.class_of(nameless) == (
        ("backward", "gated_delta_net"), "gdn.conv",
        [("backward gated_delta_net/gdn.conv", "backward rms_norm")])
    bare = S.Instr("fusion", "jit(step)/mul", [("copy", ""), ("add", "")])
    assert S.class_of(bare)[0] == ("other", S.UNATTRIBUTED)
    assert S.class_of(None)[0] == ("other", S.UNATTRIBUTED)
    # an update among the members goes before the fusion's own name
    fused = S.Instr("fusion", norm, [("multiply", "jit(step)/opt/opt.clip/mul"),
                                     ("dot", conv)])
    assert S.class_of(fused) == (("update", "opt.clip"), "", [
        ("update opt.clip", "backward gated_delta_net/gdn.conv")])


def test_a_while_gets_what_its_body_left():
    ops = [op("while.3", 3.0, 2.0), op("fusion.4", 3.5, 1.0),
           op("fusion.4", 4.75, 0.5), op("fusion.1", 6.0, 1.0)]
    assert S.exclusive_seconds(ops, 0.0, 10.0) == pytest.approx(
        [0.75, 1.0, 0.5, 1.0])         # whoever started last has the instant
    assert S.exclusive_seconds(ops, 3.25, 6.5) == pytest.approx(
        [0.5, 1.0, 0.5, 0.5])


def test_the_log_has_the_table_the_pairs_and_the_loose_operations(tmp_path,
                                                                  capsys):
    parts = S.of_run(made_up_run(tmp_path))
    assert parts["steps"] == 2 and parts["step_ms"] == pytest.approx(4750.0)
    out = capsys.readouterr().out
    assert "step parts: 4750.00 ms a step over 2 steps" in out
    assert "update opt.update + backward fc: 1000.00 ms" in out
    assert "forward gated_delta_net/gdn.scan 1000.00" in out
    assert "copy f32[8,8] 250.000 ms" in out
    row = next(line for line in out.splitlines() if "opt.update" in line
               and "+" not in line)
    assert row.split()[1:] == ["opt.update", "0.00", "0.00", "0.00",
                               "1000.00", "0.00", "1000.00", "21.1%"]
    assert S.of_run(dict(made_up_run(tmp_path), trace=None)) is None


# ---- the seven readers --------------------------------------------------------

SPANS = [("step.feed", 0.0, 0.004), ("step.dispatch", 0.004, 0.006),
         ("step.feed", 4.0, 4.002), ("step.dispatch", 4.002, 4.005),
         ("step.feed", 8.0, 8.003), ("step.flush", 8.0, 9.0)]


def test_the_readers_on_a_made_up_run(tmp_path, capsys):
    run = made_up_run(tmp_path, spans=SPANS)
    got = {name: reader(name).read(run) for name in NEW}
    assert got == pytest.approx({
        "update_ms_per_step.train": 1125.0,       # opt.update + opt.clip
        "norm_ms_per_step.train": 250.0,
        "loss_ms_per_step.train": 625.0,  # cost 250, step.loss 125, xent 250
        "recompute_ms_per_step.train": 250.0,
        "step_unattributed_ms.train": 500.0,
        "feed_ms_per_step.train": 3.0,
        "dispatch_ms_per_step.train": 2.5})
    out = capsys.readouterr().out
    assert out.count("step parts:") == 1          # one table a run
    assert "shared with a product: backward fc 1000.00 ms" in out
    assert "leaves out head.logits 500.00 ms" in out


def test_the_readers_give_nothing_on_a_parents_program(tmp_path):
    run = made_up_run(tmp_path, scoped=False)
    prog = S.program(run["tracing"].file())
    assert not S.has_scopes(prog)
    # the attribution itself still stands, instance names as parts
    sec = S.attribute(run["trace"], prog)["seconds"]
    assert sum(sec.values()) == pytest.approx(T.busy_seconds(run["trace"]))
    assert sec[("backward", "blk0_ffn_up")] == pytest.approx(2.0)
    for name in NEW:
        assert reader(name).read(run) is None, name
    serve = {"kind": "serve", "trace": run["trace"],
             "tracing": run["tracing"], "program_spans": SPANS}
    for name in NEW:
        assert reader(name).read(serve) is None, name


# ---- the real trace of the GPT-2 family's step on a v5e -----------------------

def test_the_real_trace_holds_an_update_fused_behind_a_weight_gradient():
    prog = S.program(FIXTURE)
    assert len(prog) == 3888 and not S.has_scopes(prog)   # PR 24's program
    fused = prog["%divide_subtract_fusion.3"]
    down = "jit(step)/transpose(jvp(blk1_ffn_down))/dot_general"
    assert fused.op_name == down                 # the root's, as XLA gives it
    assert ("convolution", down) in fused.members
    bare = [o for _, o in fused.members if o.count("/") == 1]
    assert {"jit(step)/sqrt", "jit(step)/div", "jit(step)/mul"} <= set(bare)
    # a fusion nested in another hands up its members
    assert not [i for i in prog.values()
                if any(opcode == "fusion" for opcode, _ in i.members)]
    ln2 = [o for _, o in prog["%fusion.83"].members if "blk1_ln2" in o]
    assert ln2                       # a norm inside the product of ffn_up


def test_the_real_traces_classes_sum_to_its_busy_time():
    tr = T.load(FIXTURE)
    got = S.attribute(tr, S.program(FIXTURE))
    assert sum(got["seconds"].values()) == pytest.approx(
        T.busy_seconds(tr), rel=1e-9)
    loose = got["seconds"][("other", S.UNATTRIBUTED)]
    # XLA's own copies and the parent's bare optimizer
    assert 0.10 < loose / T.busy_seconds(tr) < 0.16
    assert got["unattributed"][0][0].startswith("copy-done")
    assert not any(phase == "update" for phase, _ in got["seconds"])


# ---- the manifest -------------------------------------------------------------

def test_the_manifest_lists_the_seven_and_each_has_a_reader():
    with open(os.path.join(cells.REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    listed = {m["name"]: m for m in manifest["per_layer"]}
    for name, (source, layer, where) in NEW.items():
        m = listed[name]
        assert (m["source"], m["layer"], m["workloads"]) == \
            (source, layer, where), name
        assert (m["unit"], m["better"], m["moves"]) == \
            ("ms", "lower", "train_tokens_per_s"), name
        assert callable(reader(name).read)
