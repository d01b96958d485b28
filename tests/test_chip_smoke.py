"""chip_smoke.py rehearsed on the CPU at a tiny size: control flow,
arguments and the shape of the result line.  The script has no option
or variable that admits a CPU — these tests patch its device check —
and the last case runs it unpatched to see it refuse.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from paddle_tpu.serving import engine as engine_mod  # noqa: E402

TINY = chip_smoke.SmokeConfig(
    vocab=64, layers=1, heads=4, head_dim=8, seq=32, batch=4, steps=3,
    prompt_lens=(20, 12, 6, 3), new_tokens=4, pool_bytes=1 << 21,
    buckets=(16,))


@pytest.fixture
def on_cpu(monkeypatch):
    """Admit the CPU devices, send the engine down its kernel branch
    (interpret mode here) and stand in for the Mosaic-kernel count, which
    only a TPU lowering can satisfy."""
    monkeypatch.setattr(chip_smoke, "require_tpu",
                        lambda chips: jax.devices())
    monkeypatch.setattr(engine_mod, "attention_path",
                        lambda *a, **kw: "kernel")
    counted = []

    def count(eng, pb):
        assert eng._ragged_kernel
        counted.append(pb)
        return eng.kv_cfg.num_layers

    monkeypatch.setattr(chip_smoke, "count_kernels_in_step", count)
    return counted


def test_one_chip_phases_on_cpu(on_cpu, capsys):
    assert chip_smoke.main([], cfg=TINY) == 0
    dev = jax.devices()[0]
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}
    assert on_cpu == [0, 16]        # decode-only and decode+prefill steps


def test_four_chip_phase_on_virtual_devices(on_cpu, capsys, monkeypatch):
    # the CPU backend reports no memory statistics
    monkeypatch.setattr(chip_smoke, "peak_bytes", lambda dev: 1)
    assert chip_smoke.main(["--chips", "4"], cfg=TINY) == 0
    out = capsys.readouterr().out
    assert json.loads(out.strip().splitlines()[-1])["ok"] is True
    assert "train dp x4 zero1" in out and "train control x1 zero1" in out
    assert "serve tp4 vs replicated" in out
    assert on_cpu == [0, 16]


def test_kernel_check_refuses_the_reference_path():
    """Unpatched, the tiny engine on the CPU takes the reference path and
    the smoke run must call that a failure."""
    from paddle_tpu.serving import DecoderLM

    model = DecoderLM(vocab_size=TINY.vocab, num_layers=1, num_heads=2,
                      head_dim=8, max_positions=TINY.seq)
    params = model.init_params(jax.random.PRNGKey(0))
    eng, streams, _ = chip_smoke.run_engine(TINY, model, params, [[3, 4, 5]])
    assert len(streams[0]) == TINY.new_tokens \
        or streams[0][-1] == TINY.vocab - 1
    with pytest.raises(chip_smoke.SmokeFailure, match="reference"):
        chip_smoke.count_kernels_in_step(eng, 0)


def test_divergence_must_be_a_tie():
    from paddle_tpu.serving import DecoderLM, greedy_decode_reference

    model = DecoderLM(vocab_size=TINY.vocab, num_layers=1, num_heads=4,
                      head_dim=8, max_positions=TINY.seq)
    params = model.init_params(jax.random.PRNGKey(0))
    prompt = [5, 9, 2, 7]
    want = greedy_decode_reference(model, params, prompt, 4, eos_id=0)
    logits = chip_smoke.oracle_logits(model, params, prompt, TINY.seq)
    assert int(logits.argmax()) == want[0]
    chip_smoke.compare_streams("t", TINY, model, params, [prompt],
                               [want], [want])
    wrong = [int(logits.argmin())] + want[1:]
    with pytest.raises(chip_smoke.SmokeFailure, match="no tie"):
        chip_smoke.compare_streams("t", TINY, model, params, [prompt],
                                   [wrong], [want])


def test_script_fails_without_a_tpu():
    proc = subprocess.run([sys.executable, os.path.join(REPO,
                                                        "chip_smoke.py")],
                          capture_output=True, text=True, timeout=300,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr
