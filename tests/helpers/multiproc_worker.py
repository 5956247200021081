"""Worker process for the multi-process CPU CI test
(tests/test_multiprocess.py) — the reference's local-cluster simulation
pattern (DistriOptimizerSpec.scala:40-42,104-116 runs Engine.init(4,4)
against a local SparkContext; here each OS process is one "host" with 2
virtual CPU devices, joined via jax.distributed).

Usage: python multiproc_worker.py <process_id> <num_processes> <port>
           [ckpt_dir] [--die-at N] [--resume]
Prints one JSON line:
  {"process_id": i, "losses": [...], "psum": float,
   "ckpt_files": [...], "resumed_loss": float}

``--die-at N``: this worker calls os._exit(1) once neval reaches N — the
mid-training failure of the drill (the reference's fail-fast story:
spark.task.maxFailures=1, lenet Train.scala:46 — a failed task kills the
job; restart resumes from the checkpoint).
``--resume``: load the newest model.N/state.N from ckpt_dir before
training, so the run continues from the recorded neval.

Resilience drills (tests/test_resilience.py):
``--faults SPEC``: install a FaultInjector plan (BIGDL_FAULTS syntax;
per-process targeting via the spec's own ``proc=`` filter).
``--watchdog DIR``: run under the heartbeat watchdog; a silent peer makes
this worker exit with resilience.watchdog.EXIT_CODE instead of hanging
in the dead collective.
``--preempt``: arm Engine.install_preemption_handler (pass to EVERY
process — the merged stop flag is a collective).
``--preempt-at N``: this worker SIGTERMs itself once neval reaches N.

Elastic drills (tests/test_multiprocess.py, docs/resilience.md):
``--elastic``: recover-in-place mode — the launcher must export
``BIGDL_ELASTIC=1`` (so Engine.init_distributed routes through the
elastic bring-up) and pass ``--watchdog DIR`` (the heartbeat dir doubles
as the reform dir); the watchdog runs the ``recover`` policy, training
uses a 24-sample dataset with ``SampleToBatch(global_batch_size=24)``
(full-batch at ANY world size, so a post-recovery trajectory is oracle-
comparable) and zero1 so optimizer state is genuinely sharded across
processes.  The JSON adds ``recovered``/``generation``/``world``/
``ckpt_loads`` and survivors exit through ``elastic.finalize`` (ordered:
the leaked pre-recovery coordination service on process 0 must outlive
every other survivor).

Observability drills (tests/test_obs.py):
``--obs DIR``: enable the structured event log (JSONL per process under
DIR, docs/observability.md).  Process 0 additionally renders the
per-host span breakdown AFTER training (from the collect_per_node cache
— the deadlock-safety claim the 4-process obs drill asserts) and ships
it in the JSON as ``span_report``.
"""
import json
import os as _os
import sys


def main():
    argv = list(sys.argv[1:])
    die_at = None
    if "--die-at" in argv:
        i = argv.index("--die-at")
        die_at = int(argv[i + 1])
        del argv[i:i + 2]
    faults_spec = None
    if "--faults" in argv:
        i = argv.index("--faults")
        faults_spec = argv[i + 1]
        del argv[i:i + 2]
    watchdog_dir = None
    if "--watchdog" in argv:
        i = argv.index("--watchdog")
        watchdog_dir = argv[i + 1]
        del argv[i:i + 2]
    preempt = "--preempt" in argv
    if preempt:
        argv.remove("--preempt")
    elastic_mode = "--elastic" in argv
    if elastic_mode:
        argv.remove("--elastic")
    preempt_at = None
    if "--preempt-at" in argv:
        i = argv.index("--preempt-at")
        preempt_at = int(argv[i + 1])
        del argv[i:i + 2]
    obs_dir = None
    if "--obs" in argv:
        i = argv.index("--obs")
        obs_dir = argv[i + 1]
        del argv[i:i + 2]
    resume = "--resume" in argv
    if resume:
        argv.remove("--resume")
    straggler = "--straggler" in argv
    if straggler:
        argv.remove("--straggler")
    pipeline = "--pipeline" in argv
    if pipeline:
        argv.remove("--pipeline")
    pipeline_hybrid = "--pipeline-hybrid" in argv
    if pipeline_hybrid:
        argv.remove("--pipeline-hybrid")
        pipeline = True
    pid, nproc, port = int(argv[0]), int(argv[1]), argv[2]
    ckpt_dir = argv[3] if len(argv) > 3 else None

    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 2)
    jax.config.update("jax_default_matmul_precision", "highest")

    import os
    os.environ["BIGDL_CHECK_SINGLETON"] = "0"

    from bigdl_tpu.utils.engine import Engine
    if nproc > 1:
        Engine.init_distributed(coordinator_address="localhost:%s" % port,
                                num_processes=nproc, process_id=pid)
    assert jax.process_count() == nproc
    assert jax.device_count() == 2 * nproc

    if obs_dir:
        from bigdl_tpu.obs import events as obs_events
        obs_events.configure(obs_dir, process_index=pid)
    watchdog = None
    if watchdog_dir:
        from bigdl_tpu.resilience import Watchdog
        watchdog = Watchdog(
            watchdog_dir, pid, nproc, interval=0.3, timeout=6.0,
            on_peer_death="recover" if elastic_mode else "exit").start()
    if faults_spec:
        from bigdl_tpu.resilience import faults as _faults
        _faults.configure(faults_spec, process_index=pid)
    if preempt:
        Engine.install_preemption_handler()

    import numpy as np
    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import DataSet, Sample
    from bigdl_tpu.dataset.transformer import SampleToBatch
    from bigdl_tpu.optim import DistriOptimizer, max_iteration
    from bigdl_tpu.utils.table import T
    from bigdl_tpu.utils.random import set_seed

    # identical model init + data in every process
    set_seed(5)
    rng = np.random.RandomState(0)
    n, d, classes = 16, 6, 3
    w = rng.randn(d, classes) * 2
    xs = rng.randn(n, d).astype(np.float32)
    ys = (xs @ w).argmax(1) + 1.0
    samples = [Sample(x, np.asarray([y])) for x, y in zip(xs, ys)]

    # full-batch: every step sees the whole dataset regardless of process
    # count, so the loss trajectory must match the single-process oracle
    local_batch = n // nproc
    ds = (DataSet.array(samples, distributed=(nproc > 1))
          >> SampleToBatch(local_batch))

    from bigdl_tpu.optim import several_iteration
    from bigdl_tpu.optim.trigger import Trigger
    from bigdl_tpu.utils import file as File

    if pipeline:
        # multi-host PIPELINE: stages span processes (DCN in production,
        # loopback here); every process feeds the identical global batch
        # through a replicated dataset — the contract
        # _build_step_pipeline enforces
        from bigdl_tpu.parallel.mesh import make_mesh
        if pipeline_hybrid:
            # hybrid dp x pp SPANNING processes: stage rows replicate
            # over the data axis, exercising the replica-dedup stage
            # gather in checkpoints
            n_stage = nproc
            mesh = make_mesh({"data": 2, "pipe": n_stage})
        else:
            n_stage = 2 * nproc
            mesh = make_mesh({"pipe": n_stage})
        ds_p = DataSet.array(samples) >> SampleToBatch(n)
        model_p = nn.Sequential(nn.Linear(d, 16), nn.ReLU(True),
                                nn.Linear(16, 16), nn.Tanh(),
                                nn.Linear(16, 8), nn.ReLU(True),
                                nn.Linear(8, classes), nn.LogSoftMax())
        opt = DistriOptimizer(model_p, ds_p, nn.ClassNLLCriterion(),
                              mesh=mesh, pipeline_stages=n_stage,
                              pipeline_microbatches=4)
        opt.set_state(T(learningRate=0.5, momentum=0.9))
        opt.set_end_when(max_iteration(6))
        if ckpt_dir:
            opt.set_checkpoint(ckpt_dir, several_iteration(3))
        opt.optimize()
        psum = float(sum(np.abs(np.asarray(p)).sum()
                         for p in jax.tree_util.tree_leaves(
                             model_p.params())))
        out = {"process_id": pid, "losses": [float(opt.state["loss"])],
               "psum": psum}
        if ckpt_dir:
            out["ckpt_files"] = sorted(_os.listdir(ckpt_dir))
        print(json.dumps(out))
        return

    if elastic_mode:
        # elastic drill: 24 records (divisible by 4- and 3-process
        # worlds), global-batch SampleToBatch (full batch at any world
        # size -> trajectory comparable to a smaller-world oracle),
        # zero1 (optimizer state genuinely sharded across processes, so
        # recovery must reshard it) and momentum (stale velocity would
        # visibly diverge)
        from bigdl_tpu.resilience import elastic
        import bigdl_tpu.optim.optimizer as optmod
        ckpt_loads = []
        orig_load = optmod.load_latest_checkpoint

        def counted_load(*a, **k):
            # the happy recovery path must never read a checkpoint
            ckpt_loads.append(1)
            return orig_load(*a, **k)

        optmod.load_latest_checkpoint = counted_load
        n_e = 24
        rng_e = np.random.RandomState(0)
        w_e = rng_e.randn(d, classes) * 2
        xs_e = rng_e.randn(n_e, d).astype(np.float32)
        ys_e = (xs_e @ w_e).argmax(1) + 1.0
        set_seed(5)
        samples_e = [Sample(x, np.asarray([y]))
                     for x, y in zip(xs_e, ys_e)]
        ds_e = (DataSet.array(samples_e, distributed=(nproc > 1))
                >> SampleToBatch(global_batch_size=n_e))
        # hidden width 24: divisible by BOTH the 8-device (4-proc) and
        # 6-device (3-proc) data axes, so zero1 state stays genuinely
        # cross-process sharded before AND after the re-form (the shard
        # writer keeps writing shard files at the reduced world)
        model_e = nn.Sequential(nn.Linear(d, 24), nn.Tanh(),
                                nn.Linear(24, classes), nn.LogSoftMax())
        opt = DistriOptimizer(model_e, ds_e, nn.ClassNLLCriterion(),
                              zero1=(nproc > 1))
        opt.set_state(T(learningRate=0.5, momentum=0.9))
        opt.set_end_when(max_iteration(6))
        if ckpt_dir:
            opt.set_checkpoint(ckpt_dir, several_iteration(2))
        opt.optimize()
        if watchdog is not None:
            watchdog.stop()
        psum = float(sum(np.abs(np.asarray(p)).sum()
                         for p in jax.tree_util.tree_leaves(
                             model_e.params())))
        out = {"process_id": pid, "losses": [float(opt.state["loss"])],
               "psum": psum, "final_neval": int(opt.state["neval"]),
               "recovered": bool(elastic.runtime().recovered),
               "generation": int(elastic.runtime().generation),
               "world": int(jax.process_count()),
               "ckpt_loads": len(ckpt_loads)}
        if ckpt_dir:
            out["ckpt_files"] = sorted(_os.listdir(ckpt_dir))
        print(json.dumps(out))
        sys.stdout.flush()
        # ordered exit: after a recovery the pre-recovery coordination
        # service (leaked on process 0) must outlive every other
        # survivor's exit; a no-op when nothing ever tripped
        elastic.finalize(0)
        return

    model = nn.Sequential(nn.Linear(d, 8), nn.Tanh(),
                          nn.Linear(8, classes), nn.LogSoftMax())

    # momentum makes the drill honest: resuming without the optimizer
    # velocity would visibly diverge from the uninterrupted oracle
    start_state = T(learningRate=0.5, momentum=0.9)
    resume_opt = None
    if resume:
        # continue from the newest snapshot pair (model.N + state.N):
        # state carries neval, so max_iteration(6) resumes mid-count
        nevals = sorted(int(f.split(".")[-1])
                        for f in _os.listdir(ckpt_dir)
                        if f.startswith("model.")
                        and f.split(".")[-1].isdigit())
        latest = nevals[-1]
        model = File.load_module(_os.path.join(ckpt_dir,
                                               "model.%d" % latest))
        st = File.load(_os.path.join(ckpt_dir, "state.%d" % latest))
        start_state.update(st["state"])
        resume_opt = st.get("opt_state")

    opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion())
    if straggler:
        # multi-host straggler drill: only process 0 OBSERVES the last
        # replica as slow; the allgather+max merge must give every
        # process the identical policy state (divergent masks would
        # deadlock the collective).  k = int(0.375*2*4) = 3 -> threshold
        # lands at the fast cohort -> replica 3 masked from iteration 3
        n_tasks = 2 * nproc
        def observed(wall):
            t = np.ones(n_tasks)
            if pid == 0:
                t[-1] = 9.0
            return t
        opt.set_drop_module_property(0.375, 0.5, batch_size=2,
                                     warmup_iteration=0,
                                     time_source=observed)
    opt.set_state(start_state)
    if resume_opt is not None:
        opt.set_optim_state(resume_opt)
    if die_at is not None:
        def die_or_end(s):
            if s.get("neval", 0) >= die_at:
                sys.stdout.flush()
                _os._exit(1)   # simulated mid-training crash
            return s.get("neval", 0) > 6
        opt.set_end_when(Trigger(die_or_end, "die-at-%d" % die_at))
    elif preempt_at is not None:
        import signal as _signal

        def sigterm_or_end(s):
            # the scheduler's eviction notice, self-inflicted: the armed
            # handler flips the flag, the loop's merged check stops every
            # process at the same iteration with a final checkpoint
            if s.get("neval", 0) >= preempt_at and not Engine.preempted():
                _os.kill(_os.getpid(), _signal.SIGTERM)
            return s.get("neval", 0) > 6
        opt.set_end_when(Trigger(sigterm_or_end,
                                 "preempt-at-%d" % preempt_at))
    else:
        opt.set_end_when(max_iteration(6))
    if ckpt_dir and not resume:
        opt.set_checkpoint(ckpt_dir, several_iteration(3))

    try:
        opt.optimize()
    except Exception as e:
        if watchdog is not None:
            # a dead peer can surface as an immediate collective error
            # (TCP reset) before the heartbeat timeout: hold for the
            # watchdog's verdict so survivors deliver the uniform
            # exit-43 contract instead of an arbitrary unwind
            watchdog.arbitrate(e)
        raise
    if watchdog is not None:
        # training survived; peers exit at slightly different times from
        # here on, which must not read as peer death
        watchdog.stop()
    losses = [float(opt.state["loss"])]

    psum = float(sum(np.abs(np.asarray(p)).sum()
                     for p in jax.tree_util.tree_leaves(model.params())))

    out = {"process_id": pid, "losses": losses, "psum": psum,
           "preempted": bool(opt.state.get("preempted", False)),
           "final_neval": int(opt.state.get("neval", 0)),
           "nonfinite_skips": int(opt.state.get("nonFiniteSkips", 0)),
           # per-node metric breakdown (ref Metrics.scala "computing time
           # for each node"): one entry per process
           "compute_per_node": opt.metrics.per_node(
               "computing time average")}
    if straggler:
        out["drop_mask"] = [float(v) for v in opt._straggler.mask()]
    if obs_dir and pid == 0:
        # ONLY process 0 renders — proving the epoch-end span gather in
        # optimize() cached everything and this is collective-free
        out["span_report"] = opt.spans.per_host_report()
        out["dispatch_per_node"] = opt.metrics.per_node("span: dispatch")

    # cross-process validation merge (ref DistriValidator.scala:32): each
    # process sees its shard; merged counts must cover the GLOBAL set
    from bigdl_tpu.optim import Top1Accuracy
    from bigdl_tpu.optim.local_optimizer import distri_validate
    val_ds = (DataSet.array(samples, distributed=(nproc > 1))
              >> SampleToBatch(local_batch))
    res = distri_validate(model, model.params(), model.state(),
                          val_ds, [Top1Accuracy()])
    acc = res[0][1]
    out["val_count"] = int(acc.count)
    out["val_correct"] = int(acc.correct)
    if ckpt_dir:
        out["ckpt_files"] = sorted(_os.listdir(ckpt_dir))
    if ckpt_dir and not resume:
        # resume: fresh model from the newest checkpoint, 2 more steps —
        # every process reads the same files process 0 wrote
        from bigdl_tpu.utils import file as File
        nevals = sorted(int(f.split(".")[-1]) for f in out["ckpt_files"]
                        if f.startswith("model.")
                        and f.split(".")[-1].isdigit())
        m2 = File.load_module(_os.path.join(ckpt_dir,
                                            "model.%d" % nevals[-1]))
        opt2 = DistriOptimizer(m2, ds, nn.ClassNLLCriterion())
        opt2.set_state(T(learningRate=0.5))
        opt2.set_end_when(max_iteration(2))
        opt2.optimize()
        out["resumed_loss"] = float(opt2.state["loss"])
    print(json.dumps(out))


if __name__ == "__main__":
    main()
