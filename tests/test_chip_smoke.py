"""chip_smoke.py on the CPU: its failure contract, and a toy-size rehearsal
of the phases whose models can be made small (on-chip-measurement guide §2,
first rehearsal).  The real sizes run only on the chip."""
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402

TINY = {
    "bilstm": {"batch": 8, "seq": 12, "embed": 8, "hidden": 8, "classes": 4,
               "steps": 6, "lr": 0.05},
    "lm": {"vocab": 64, "d_model": 32, "heads": 4, "layers": 2, "hidden": 64,
           "prompt": 12, "words": 6, "slots": 2, "prompts": 3, "page": 4,
           "spec_k": 2},
    "tp_prompts": 2, "replica_layers": 1, "replica_requests": 5,
}


def last_json_line(stdout):
    return json.loads(stdout.strip().splitlines()[-1])


def test_no_chip_is_a_failure_not_a_cpu_run():
    """``JAX_PLATFORMS=cpu python chip_smoke.py`` exits non-zero, before
    any phase, with ``ok: false``."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    last = last_json_line(out.stdout)
    assert last["ok"] is False and "cpu" in last["error"]
    assert '"phase": "train' not in out.stdout


def test_alone_in_a_directory_it_fails(tmp_path):
    """A directory that holds chip_smoke.py and nothing else of the repo:
    non-zero exit, no ``ok: true``."""
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         capture_output=True, text=True, timeout=300,
                         env={**env, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


@pytest.mark.parametrize("error", [AssertionError("loss did not fall"),
                                   RuntimeError("compile refused"),
                                   KeyboardInterrupt()])
def test_a_failed_phase_fails_the_run(monkeypatch, capsys, error):
    def broken(*args):
        chip_smoke.say(phase="start")
        raise error

    monkeypatch.setattr(chip_smoke, "run", broken)
    assert chip_smoke.main([]) == 1
    last = last_json_line(capsys.readouterr().out)
    assert last["ok"] is False and type(error).__name__ in last["error"]


def test_success_prints_exactly_the_device_line(monkeypatch, capsys):
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "run", lambda *args: device)
    assert chip_smoke.main([]) == 0
    assert last_json_line(capsys.readouterr().out) == {"ok": True,
                                                       "device": device}


def test_a_wrong_chip_count_fails_before_any_phase():
    """The driver's one-chip run on a host that shows another count (here:
    the suite's 8 CPU devices) must not quietly use a subset."""
    with pytest.raises(AssertionError, match="asked for 1 chip"):
        chip_smoke.run(TINY, 1, "cpu", 21)


@pytest.fixture
def meter():
    return chip_smoke.CompileMeter()


@pytest.fixture
def f32_everywhere():
    """The decode phases claim token parity: both sides in full f32 (the
    suite's conftest already pins the matmul precision)."""
    from bigdl_tpu import tensor as bt
    before = bt.policy()
    bt.set_policy(bt.FP32)
    yield
    bt.set_policy(before)


def test_rehearse_bilstm_training(meter, capsys):
    chip_smoke.phase_train_bilstm(TINY, "cpu", meter, 21)
    line = last_json_line(capsys.readouterr().out)
    assert line["phase"] == "train_bilstm" and line["steps"] == 6
    assert line["losses"][-1] < line["losses"][0]


def test_rehearse_decode_parity_with_kernels_off_and_on(meter, capsys,
                                                        f32_everywhere):
    """XLA attention, the paged-attention kernel and the spec-verify kernel
    (interpreted here) all reproduce serial lm_decode."""
    chip_smoke.phase_decode(TINY, "cpu", meter, 21)
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert [l["phase"] for l in lines] == [
        "lm_decode_serial_reference", "decode_paged_xla",
        "decode_paged_attention_kernel", "decode_spec_verify_kernel"]
    assert all(l["parity"] == "token-identical" for l in lines[1:])


def test_a_differing_token_fails_the_decode_phase(meter, monkeypatch,
                                                  f32_everywhere):
    real = chip_smoke.decode

    def off_by_one(*args, **kwargs):
        rows, stats, seconds = real(*args, **kwargs)
        rows[1][-1] = (rows[1][-1] + 1) % TINY["lm"]["vocab"]
        return rows, stats, seconds

    monkeypatch.setattr(chip_smoke, "decode", off_by_one)
    with pytest.raises(AssertionError, match="rows \\[1\\] differ"):
        chip_smoke.phase_decode(TINY, "cpu", meter, 21)


def test_rehearse_four_chip_decode_phases(meter, capsys, f32_everywhere):
    """The ``--chips 4`` decode phases on four of the suite's virtual CPU
    devices: tensor-parallel parity (kernels off and on) and one replica
    per device behind the router."""
    devices = jax.devices()[:4]
    chip_smoke.phase_tensor_parallel_decode(TINY, devices, "cpu", meter, 21)
    chip_smoke.phase_replicas(TINY, devices, "cpu", meter, 21)
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    replicas = lines[-1]
    assert replicas["phase"] == "decode_replicas"
    assert sorted(replicas["replica_device"].values()) == sorted(
        str(d) for d in devices)
    assert sum(replicas["retired_per_replica"].values()) == 5
