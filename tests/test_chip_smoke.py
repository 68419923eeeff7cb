"""chip_smoke.py off the chip: it must refuse to run without a TPU, and
its phase functions must work end to end at a tiny size (the CPU
rehearsal of ``/opt/skills/guides/on-chip-measurement`` section 2, kept
so every later PR repeats it for free)."""

import json
import os
import subprocess
import sys

import chip_smoke
from llm_in_practise_tpu.ops import attention, flash_attention

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Every mechanism of the real plan at toy widths: GQA with a head_dim the
# flash kernel takes, a vocabulary over the tokenizer's 800 ids, a prompt
# longer than the prefill chunk, a sequence past flash's crossover, and
# matmuls the fused kernels can tile (multiples of 256 x 256).
TINY = chip_smoke.Plan(
    geom=dict(hidden_size=128, intermediate_size=256, n_head=4,
              n_kv_head=2, head_dim=64),
    vocab=1024, serve_layers=1, train_layers=1, tp_layers=1,
    slots=4, cache_len=256, chunk=64, long_prompt=80,
    train_batch=1, train_seq=640, train_steps=12,
    matmul_shapes=((256, 512),), matmul_ms=(16,),
    flash_shape=(1, 128, 2, 64), on_chip=False)


def test_command_line_fails_without_a_chip():
    """Under ``JAX_PLATFORMS=cpu`` the script exits non-zero, names the
    platform it found, and never prints a result."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "found platform 'cpu'" in out.stderr
    assert '"ok": true' not in out.stdout
    assert out.stdout.strip() == ""


def _phase_lines(capsys) -> dict:
    lines = [json.loads(line) for line in
             capsys.readouterr().out.splitlines() if line.startswith("{")]
    return {line["phase"]: line for line in lines if "phase" in line}


def test_serve_phase_at_a_tiny_config(capsys):
    chip_smoke.phase_serve(TINY, 0, chip_smoke.baseline(TINY))
    lines = _phase_lines(capsys)
    assert lines["serve"]["layout"]["kv"] == "paged"
    assert lines["serve"]["mixed_blocks"] > 0
    assert lines["serve.released"]["ledger_bytes_held"] == 0


def test_train_phase_at_a_tiny_config(monkeypatch, capsys):
    # steer the CPU run onto the chip's choice: flash attention,
    # interpreted (the program itself has no such option)
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(flash_attention, "interpret_default", lambda: True)
    chip_smoke.phase_train(TINY, 0, chip_smoke.baseline(TINY))
    losses = _phase_lines(capsys)["train"]["losses"]
    assert len(losses) == TINY.train_steps and losses[-1] < losses[0]


def test_kernels_phase_at_a_tiny_config(capsys):
    chip_smoke.phase_kernels(TINY, 0)
    line = _phase_lines(capsys)["kernels"]
    assert line["xla_fallbacks"] == 0 and line["checks"] >= 9
