"""Guards the driver contract: every bench.py config must BUILD and
TRACE (abstract eval — no compile, no device work), and the summary
line must parse with the required keys.  Round 2 lost its entire
driver-verified perf record to a bench that could not finish; this
keeps the apparatus itself from bit-rotting between rounds."""
import json

import jax
import numpy as np
import pytest


@pytest.fixture(scope="module")
def bench():
    import importlib
    import bench as b
    importlib.reload(b)
    return b


def test_all_five_configs_present(bench):
    cfgs = bench.configs()
    names = [c[0] for c in cfgs]
    for c in cfgs:
        assert len(c) == 6, f"config tuple arity changed: {c[0]}"
    for want in ("LeNet", "VGG-16", "Inception", "Bi-LSTM", "ResNet-50"):
        assert any(want in n for n in names), (want, names)


def test_every_config_builds_and_traces(bench):
    # iterates configs() itself so a 6th config can never silently
    # escape coverage
    from bigdl_tpu import tensor as bt
    from bigdl_tpu.utils.random import set_seed
    set_seed(1)
    bt.set_policy(bt.BF16_COMPUTE)
    try:
        for name, build, recs, unit, aflops, n_disp in bench.configs():
            model, criterion, x, y = build()
            step, params, net_state, opt_state = bench.make_step(
                model, criterion)
            # abstract evaluation only: catches shape/dtype/tracing
            # breakage in seconds without compiling anything
            out = jax.eval_shape(step, params, net_state, opt_state, x, y,
                                 jax.random.PRNGKey(0))
            assert out[-1].shape == (), name   # scalar loss
            # the path bench_config actually runs: the scanned chunk
            import jax.numpy as jnp
            n = 2
            xs = jnp.stack([x] * n)
            ys = jnp.stack([y] * n)
            cstep, cp, cns, cos = bench.make_chunk_step(model, criterion, n)
            cout = jax.eval_shape(cstep, cp, cns, cos, xs, ys,
                                  jax.random.PRNGKey(0))
            assert cout[-1].shape == (), name
            assert recs > 0 and unit.endswith("/sec"), name
    finally:
        bt.set_policy(bt.FP32)


_ENTRY = {"config": "Inception-v1 x", "unit": "images/sec", "value": 3000.0,
          "step_time_ms": 42.0, "mfu": 0.14, "device": "TPU v5 lite"}


def test_summary_line_contract(bench):
    d = json.loads(bench._summary_line([_ENTRY], _ENTRY, 186.9))
    for key in ("metric", "value", "unit", "vs_baseline"):
        assert key in d, key
    assert d["value"] == 3000.0
    assert d["vs_baseline"] == round(0.14 / 0.4, 4)
    assert d["detail"]["device"] == "TPU v5 lite"
    assert d["detail"]["measured_matmul_roofline_tflops"] == 186.9


def test_summary_without_mfu_claims_no_baseline_ratio(bench):
    """A run with no MFU (the CPU has no datasheet peak) reports none —
    it used to print vs_baseline 1.0, i.e. "exactly on target"."""
    cpu = dict(_ENTRY, mfu=None, device="cpu")
    d = json.loads(bench._summary_line([cpu], cpu, None))
    assert d["vs_baseline"] is None and d["detail"]["mfu"] is None
    # an unmeasured roofline is null, never a number from another run
    assert d["detail"]["measured_matmul_roofline_tflops"] is None


def test_summary_line_carries_trimmed_eval(bench):
    d = json.loads(bench._summary_line(
        [_ENTRY], _ENTRY, None,
        {"records_per_sec": 9000.0, "top1": 0.1, "config": "dropped"}))
    assert d["detail"]["eval"] == {"records_per_sec": 9000.0, "top1": 0.1}


def _fake_child(tmp_path, monkeypatch, body):
    """Point ``bench._subprocess_json`` at a scripted child."""
    import importlib
    import textwrap
    import bench as b
    importlib.reload(b)
    fake = tmp_path / "fake_child.py"
    fake.write_text(textwrap.dedent(body))
    real = b.os.path.abspath(b.__file__)
    orig = b.os.path.abspath
    monkeypatch.setattr(
        b.os.path, "abspath",
        lambda p: str(fake) if orig(p) == real else orig(p))
    monkeypatch.setattr(b, "_BENCH_DEADLINE", b.time.monotonic() + 600)
    return b


def test_subprocess_timeout_keeps_printed_entries_and_fails(
        tmp_path, monkeypatch):
    """A child that hangs AFTER printing a config entry keeps the
    measured entry — and the config still counts as failed."""
    b = _fake_child(tmp_path, monkeypatch, """
        import json, time
        print(json.dumps({"config": "Inception-v1 fake", "value": 1.0}),
              flush=True)
        time.sleep(600)
    """)
    # 20s: the child prints immediately then sleeps 600 — the timeout only
    # needs to cover interpreter startup, which can stretch under a loaded
    # host (this test once flaked at 3s while a bench ran concurrently)
    out, err = b._subprocess_json("x", timeout_s=20)
    assert out and out[0]["config"] == "Inception-v1 fake"
    assert err and "timed out" in err


@pytest.mark.parametrize("body,reason", [
    ("import sys; print('boom', file=sys.stderr); sys.exit(3)",
     "exit code 3"),
    ("pass", "printed no result"),
])
def test_subprocess_failure_is_reported_not_retried(
        tmp_path, monkeypatch, body, reason):
    b = _fake_child(tmp_path, monkeypatch, body)
    out, err = b._subprocess_json("x", timeout_s=60)
    assert out == [] and reason in err


def test_failed_config_exits_nonzero_and_names_it(bench, monkeypatch,
                                                  capsys):
    """The old bench printed ``value: 0`` and exited 0 when nothing was
    measured.  Now a failed config is named on stderr, the exit code is
    non-zero, and a run without its headline prints no summary line."""
    monkeypatch.setattr(bench.sys, "argv", ["bench.py"])
    monkeypatch.setattr(
        bench, "_subprocess_json",
        lambda arg, timeout_s: ([], "exit code 1: no chip"))
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code == 1
    out, err = capsys.readouterr()
    assert "metric" not in out
    assert "'inception' failed: exit code 1: no chip" in err


def test_one_failed_config_fails_a_run_with_a_headline(bench, monkeypatch,
                                                       capsys):
    def child(arg, timeout_s):
        if arg == "inception":
            return [dict(_ENTRY, config="Inception-v1 bs128")], None
        return [], "timed out after 300s"

    monkeypatch.setattr(bench.sys, "argv", ["bench.py"])
    monkeypatch.setattr(bench, "_subprocess_json", child)
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code == 1
    out, err = capsys.readouterr()
    # the measured headline is still reported ...
    assert json.loads(out.strip().splitlines()[-1])["value"] == 3000.0
    # ... and every failed config is named
    assert "'resnet' failed: timed out" in err


def test_summary_line_fits_driver_tail_window(bench):
    """VERDICT r5 weak 1 (BENCH_r05 ``parsed: null``): the driver keeps
    only the last ~2000 bytes of stdout, so the FULLY-POPULATED summary
    — six configs with real-length names, bands, flops, losses, plus the
    eval block — must serialize under 2000 bytes.  The
    full per-config detail now rides the per-config lines main()
    re-emits; the summary carries a config/value/mfu digest only."""
    names = [
        "LeNet-5 bs256 (MNIST, local)",
        "VGG-16 bs128 (CIFAR-10)",
        "Inception-v1 bs128 (ImageNet sync-SGD)",
        "Bi-LSTM bs128 T500 (text classifier)",
        "ResNet-50 bs64 (ImageNet streaming cfg)",
        "Transformer-enc bs16 T512 d1024 (attention family)",
    ]
    entries = [{
        "config": n, "unit": "tokens/sec", "value": 14081444.54,
        "step_time_ms": 27.653, "step_time_ms_band": [27.653, 27.687],
        "mfu": 0.2133, "step_tflops": 112.6,
        "flops_per_step": 4033624145920.0,
        "loss": 9.170179691864178e-05, "device": "TPU v5 lite",
    } for n in names]
    eval_entry = {
        "records_per_sec": 9925.15, "step_time_ms": 12.897,
        "top1": 0.0, "top5": 0.0,
        "config": "Inception-v1 bs128 (ImageNet eval forward)",
        "unit": "images/sec",
    }
    line = bench._summary_line(entries, entries[2], 186.9, eval_entry)
    assert len(line.encode()) < 2000, (len(line.encode()), line)
    d = json.loads(line)
    assert d["vs_baseline"] == round(0.2133 / 0.4, 4)
    assert len(d["detail"]["configs"]) == 6
    # the digest keeps each config addressable in the per-config lines
    assert {c["config"] for c in d["detail"]["configs"]} == set(names)
    assert d["detail"]["eval"]["records_per_sec"] == 9925.15
    # headline keys the driver greps for
    for key in ("metric", "value", "unit", "vs_baseline"):
        assert key in d, key
