#!/usr/bin/env bash
# Observability smoke (ISSUE 3): prove the telemetry subsystem end to
# end on CPU, no chip needed.
#
#   1. the fast obs-marked pytest set (taps/events/spans/bundles/summary)
#   2. a 5-step LeNet-5 run with taps+events on: every JSONL line must
#      validate against the event schema, the tap cadence must hold, and
#      the step-time overhead vs taps-off must be in the noise
#   3. a BIGDL_FAULTS proc_kill drill under the heartbeat watchdog: the
#      survivor must exit 43 AND leave a crash bundle the report renders
#   4. the performance-observatory drill (ISSUE 13): a 5-step LeNet run
#      must leave ledger events + a finite, stable step-wall gauge (and no
#      train_mfu on the CPU, which has no datasheet peak), an
#      injected queue-depth spike must fire then resolve an alert, and
#      obs_report must render the ledger + alert sections
#   5. the request-forensics drill: the forensic-marked tests, then a
#      2-replica fleet under load with an injected serve_kill and a
#      chaos-slowed request — every anomalous request must keep a
#      complete monotone recorded timeline while healthy traffic at
#      sample=0 emits ZERO trace events, tools/request_replay.py must
#      reproduce a recorded greedy decode token-identically, and the
#      report's Forensics section must render under --strict
#
#   scripts/obs_smoke.sh            # full smoke
#
# Flags/schema: docs/observability.md.
set -euo pipefail
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu

echo "== obs smoke 1/5: fast obs-marked tests =="
python -m pytest tests/test_obs.py tests/test_obs_metrics.py \
    tests/test_obs_ledger.py tests/test_obs_alerts.py -q \
    -m "obs and not slow" \
    -p no:cacheprovider -p no:randomly

RUN=$(mktemp -d)
echo "== obs smoke 2/5: 5-step LeNet with taps+events ($RUN) =="
BIGDL_OBS_DIR="$RUN" BIGDL_OBS_TAPS=1 BIGDL_OBS_TAPS_CADENCE=2 \
python - "$RUN" <<'PY'
import json, sys, time
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")

import bigdl_tpu.nn as nn
from bigdl_tpu.dataset import DataSet, Sample
from bigdl_tpu.dataset.transformer import SampleToBatch
from bigdl_tpu.models.lenet import LeNet5
from bigdl_tpu.obs import events as obs_events
from bigdl_tpu.obs.events import read_events, validate_event
from bigdl_tpu.optim import LocalOptimizer, max_iteration
from bigdl_tpu.utils.random import set_seed
from bigdl_tpu.utils.table import T

run_dir = sys.argv[1]
rng = np.random.RandomState(0)
samples = [Sample(rng.rand(28, 28).astype(np.float32),
                  np.asarray([float(rng.randint(1, 11))]))
           for _ in range(64)]
ds = DataSet.array(samples) >> SampleToBatch(8)


def train(steps, taps_on):
    set_seed(1)
    opt = LocalOptimizer(LeNet5(10), ds, nn.ClassNLLCriterion())
    opt.set_state(T(learningRate=0.05))
    opt.set_taps(enabled=taps_on, cadence=2)
    opt.set_end_when(max_iteration(steps))
    t0 = time.perf_counter()
    opt.optimize()
    return opt, time.perf_counter() - t0


opt, _ = train(5, taps_on=True)
assert list(opt._taps_monitor.materialized_steps) == [2, 4, 5], \
    opt._taps_monitor.materialized_steps

events = read_events(obs_events.get().path)
for e in events:
    validate_event(e)
steps = [e for e in events if e["type"] == "step"]
assert len(steps) == 5, len(steps)
assert sum(1 for e in steps if "taps" in e) == 2  # cadence boundaries 2,4
assert events[0]["type"] == "run_start" and events[-1]["type"] == "run_end"
print(f"OK: {len(events)} events validate; taps at cadence 2")

# overhead: WARM median per-step wall, taps on vs off.  The per-step
# walls ride the step events' throughput field (ring-only log); the
# first two iterations are dropped — they carry the jit compile, which
# differs between the two programs and is not step time.
def step_walls(taps_on, steps=40):
    obs_events.configure(None)   # fresh ring-only log
    set_seed(1)
    opt = LocalOptimizer(LeNet5(10), ds, nn.ClassNLLCriterion())
    opt.set_state(T(learningRate=0.05))
    opt.set_taps(enabled=taps_on, cadence=2)
    opt.set_end_when(max_iteration(steps))
    opt.optimize()
    ev = [e for e in obs_events.get().ring_events() if e["type"] == "step"]
    walls = sorted(8.0 / e["throughput"] for e in ev[2:])
    return walls[len(walls) // 2]


step_walls(False, steps=10)           # process warm-up, discarded
on, off = step_walls(True), step_walls(False)
ratio = on / off
print(f"warm median step wall: taps-on {on*1e3:.2f} ms, "
      f"taps-off {off*1e3:.2f} ms (ratio {ratio:.3f})")
assert ratio < 1.3, f"taps overhead out of noise: {ratio:.3f}"
PY

python tools/obs_report.py "$RUN" --strict -o "$RUN/report.md"
grep -q "Throughput / loss trajectory" "$RUN/report.md"
echo "OK: report rendered ($RUN/report.md)"

RUN2=$(mktemp -d)
HB=$(mktemp -d)
echo "== obs smoke 3/5: watchdog trip via BIGDL_FAULTS ($RUN2) =="
python - "$RUN2" "$HB" <<'PY'
import os, socket, subprocess, sys

run2, hb = sys.argv[1], sys.argv[2]
s = socket.socket(); s.bind(("localhost", 0))
port = s.getsockname()[1]; s.close()
env = dict(os.environ)   # the workers inherit JAX_PLATFORMS=cpu
env["PYTHONPATH"] = os.getcwd() + os.pathsep + env.get("PYTHONPATH", "")
worker = os.path.join("tests", "helpers", "multiproc_worker.py")
procs = [subprocess.Popen(
    [sys.executable, worker, str(i), "2", str(port),
     "--watchdog", hb, "--obs", run2,
     "--faults", "proc_kill@at=3,proc=1"],
    env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL)
    for i in range(2)]
assert procs[1].wait(timeout=600) == 1, "victim should die with code 1"
rc0 = procs[0].wait(timeout=600)
assert rc0 == 43, f"survivor should exit 43 (watchdog), got {rc0}"
bundles = [f for f in os.listdir(run2) if f.startswith("crash-watchdog")]
assert bundles, os.listdir(run2)
files = set(os.listdir(os.path.join(run2, bundles[0])))
assert {"reason.txt", "events.jsonl", "threads.txt",
        "config.json", "memory.json"} <= files, files
print(f"OK: watchdog trip left crash bundle {bundles[0]}")
PY
python tools/obs_report.py "$RUN2" -o "$RUN2/report.md"
grep -q "Crash bundles" "$RUN2/report.md"

RUN3=$(mktemp -d)
echo "== obs smoke 4/5: performance observatory drill ($RUN3) =="
BIGDL_OBS_DIR="$RUN3" python - <<'PY'
import math
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")

import bigdl_tpu.nn as nn
from bigdl_tpu.dataset import DataSet, Sample
from bigdl_tpu.dataset.transformer import SampleToBatch
from bigdl_tpu.models.lenet import LeNet5
from bigdl_tpu.obs import alerts as obs_alerts
from bigdl_tpu.obs import events as obs_events
from bigdl_tpu.obs import ledger as obs_ledger
from bigdl_tpu.obs import metrics as obs_metrics
from bigdl_tpu.obs.events import read_events, validate_event
from bigdl_tpu.optim import LocalOptimizer, max_iteration
from bigdl_tpu.utils.random import set_seed
from bigdl_tpu.utils.table import T

rng = np.random.RandomState(0)
samples = [Sample(rng.rand(28, 28).astype(np.float32),
                  np.asarray([float(rng.randint(1, 11))]))
           for _ in range(64)]
ds = DataSet.array(samples) >> SampleToBatch(8)


def step_wall_after(steps):
    set_seed(1)
    opt = LocalOptimizer(LeNet5(10), ds, nn.ClassNLLCriterion())
    opt.set_state(T(learningRate=0.05))
    opt.set_end_when(max_iteration(steps))
    opt.optimize()
    snap = obs_metrics.get().snapshot()
    # this drill runs on the CPU, which has no datasheet peak: the
    # utilization gauge must be ABSENT, not computed against a chip's
    assert not snap.get("train_mfu", {}).get("series"), snap["train_mfu"]
    return obs_metrics.family_total(snap, "train_step_wall_seconds",
                                    optimizer="local")


# ledger + windowed gauges: the capture rides the compile, the gauges
# the flushes
mfu1 = step_wall_after(5)
assert math.isfinite(mfu1) and mfu1 > 0, mfu1
led = obs_ledger.get().stats()
assert led["captures"] >= 1, led
mfu2 = step_wall_after(5)   # warm re-run: finite and same order (stable)
assert math.isfinite(mfu2) and mfu2 > 0, mfu2
assert 0.05 < mfu2 / mfu1 < 20.0, (mfu1, mfu2)
events = read_events(obs_events.get().path)
for e in events:
    validate_event(e)
execs = [e for e in events if e["type"] == "ledger"
         and e["kind"] == "exec"]
assert execs, "ledger/exec events must ride the JSONL stream"
print(f"OK: {len(execs)} ledger capture(s); step wall {mfu1:.2e} s "
      f"(re-run {mfu2:.2e} s); no MFU on the CPU")

# alert drill: inject a queue-depth spike, watch it fire then resolve
reg = obs_metrics.get()
engine = obs_alerts.AlertEngine(
    reg.snapshot, [r for r in obs_alerts.default_rules()
                   if r.name == "queue_depth"])
assert engine.evaluate_once() == []
spike = reg.gauge("serve_queue_depth", "drill", engine="drill")
spike.set(999)
assert engine.evaluate_once() == [("queue_depth", "firing", 999.0)]
spike.set(0)
assert engine.evaluate_once() == [("queue_depth", "resolved", 0.0)]
kinds = [e["kind"] for e in obs_events.get().ring_events()
         if e["type"] == "alert"]
assert kinds == ["firing", "resolved"], kinds
print("OK: queue-depth spike fired and resolved")
PY
python tools/obs_report.py "$RUN3" --strict -o "$RUN3/report.md"
grep -q "Performance ledger" "$RUN3/report.md"
grep -q "Alert timeline" "$RUN3/report.md"
echo "OK: observatory report rendered ($RUN3/report.md)"

RUN4=$(mktemp -d)
echo "== obs smoke 5/5: request-forensics drill ($RUN4) =="
python -m pytest tests/test_recorder.py tests/test_remote.py -q \
    -m "forensic and not slow" -p no:cacheprovider -p no:randomly
BIGDL_OBS_DIR="$RUN4" python - "$RUN4" <<'PY'
import json, os, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")

import bigdl_tpu.nn as nn
from bigdl_tpu.models.transformer import TransformerLM
from bigdl_tpu.obs import events as obs_events
from bigdl_tpu.obs import recorder
from bigdl_tpu.obs.trace import Trace
from bigdl_tpu.serve import (LocalReplica, ProcessReplica, Router,
                             ServeEngine, WeightStore)
from bigdl_tpu.serve.decode import ContinuousDecoder
from bigdl_tpu.utils.random import set_seed

run_dir = sys.argv[1]
set_seed(1)
model = nn.Sequential(nn.Linear(4, 3), nn.LogSoftMax())

# -- 2-replica fleet under load, one replica chaos-killed mid-burst,
#    head sampling at 0 (the production default) ---------------------------
eng = ServeEngine(model, max_batch=4, max_wait_ms=2, input_shape=(4,))
victim = ProcessReplica(model, name="victim",
                        env={"BIGDL_FAULTS": "serve_kill@at=4"},
                        max_batch=4, max_wait_ms=2, input_shape=(4,))
rng = np.random.RandomState(0)
failed = 0
try:
    with Router([LocalReplica(eng, name="healthy"), victim],
                shed=False, trace_sample=0.0) as router:
        futs = [router.submit(rng.randn(4).astype(np.float32))
                for _ in range(24)]
        # one deliberately chaos-slowed request: a 1 ms deadline no
        # batched engine can make -> slo_miss forensics
        slow = router.submit(rng.randn(4).astype(np.float32), slo_ms=1)
        for f in futs + [slow]:
            try:
                f.result(timeout=120)
            except Exception:
                failed += 1
finally:
    victim.close()
    eng.close()

recs = [r for r in recorder.get().records() if r.get("outcome")]
anom = [r for r in recs if r.get("anomaly")]
assert len(recs) == 25, len(recs)
assert anom, "the serve_kill drill must produce anomalies"
assert any(r["anomaly"] == "slo_miss" for r in anom), \
    [r["anomaly"] for r in anom]
# 100% of anomalous requests keep a complete, monotone timeline
for r in anom:
    phases = [h[0] for h in r["hops"]]
    stamps = [h[1] for h in r["hops"]]
    assert phases[0] == "admit", phases
    assert stamps == sorted(stamps), r
ring = obs_events.get().ring_events()
traces = [e for e in ring if e["type"] == "trace"]
forensics = [e for e in ring if e["type"] == "forensic"]
# tail retention: at sample=0 the ONLY emitted traces are the anomalies
assert len(traces) == len(anom) == len(forensics), \
    (len(traces), len(anom), len(forensics))
print(f"OK: {len(anom)} anomalous / {len(recs) - len(anom)} healthy "
      f"records; every anomaly bundled, zero healthy trace events")

# -- record one greedy decode for the offline replay check -----------------
set_seed(1)
lm = TransformerLM(vocab_size=11, d_model=16, n_heads=2, n_layers=2,
                   hidden=32)
store = WeightStore()
dec = ContinuousDecoder(lm, max_slots=2, n_pos=16, page_size=4,
                        sync_interval=2)
dec.weights_version = store.put_model(lm)
tr = Trace()
fut = dec.submit([1, 2, 3, 4], 5, trace=tr)
dec.run()
row = fut.result()
rec = recorder.get().get(tr.trace_id)
assert rec["tokens"] == row and rec["seed_len"] == 4
with open(os.path.join(run_dir, "records.jsonl"), "w") as fh:
    fh.write(json.dumps(rec) + "\n")
with open(os.path.join(run_dir, "replay_model.py"), "w") as fh:
    fh.write(
        "from bigdl_tpu.models.transformer import TransformerLM\n"
        "from bigdl_tpu.utils.random import set_seed\n\n\n"
        "def model():\n"
        "    set_seed(1)\n"
        "    return TransformerLM(vocab_size=11, d_model=16,\n"
        "                         n_heads=2, n_layers=2, hidden=32)\n")
print("OK: recorded a greedy decode for replay")
PY
PYTHONPATH="$RUN4:${PYTHONPATH:-}" \
python tools/request_replay.py "$RUN4/records.jsonl" \
    --model replay_model:model | grep MATCH
python tools/obs_report.py "$RUN4" --strict -o "$RUN4/report.md"
grep -q "## Forensics" "$RUN4/report.md"
echo "OK: forensics drill green (replay MATCH, report rendered)"
echo "obs smoke: all green"
