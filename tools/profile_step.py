"""Per-op device profile of a training step (VERDICT round-1 item 1).

Builds the same jitted train step as ``bench.py`` for a chosen model,
captures a ``jax.profiler`` device trace, and joins the per-op device
timings against the optimized HLO module's **metadata** (op_name +
source_file, attached by XLA to every instruction) to attribute every
microsecond of device time to (a) an op kind (conv fwd/bwd, pool fwd/bwd,
matmul, rng, eltwise...) and (b) the framework module that emitted it
(conv.py, pooling.py, normalization.py, ...).

The reference's profiling analogue is per-module wall timers
(AbstractModule.scala:125-136) and conv im2col/col2im counters
(SpatialConvolution.scala:73-78); on TPU the per-op device trace is the
honest equivalent because XLA fuses across module boundaries.

Usage:  python tools/profile_step.py \
            [inception|vgg16|lenet|resnet50|bilstm|transformer] [batch]
Writes ``PROFILE_<model>.md`` at the repo root and prints the table.
"""
from __future__ import annotations

import os as _os
import sys as _sys

_REPO_ROOT = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
if _REPO_ROOT not in _sys.path:
    _sys.path.insert(0, _REPO_ROOT)  # run without an installed package

import collections
import glob
import gzip
import json
import re
import sys
import tempfile


# --------------------------------------------------------------- HLO parsing

_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE_RE = re.compile(r"(?:^|[\s)])([a-z][a-z0-9\-]*)\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")


class Instr:
    __slots__ = ("name", "comp", "opcode", "shape", "operands", "op_name",
                 "src", "line")


def parse_hlo_module(hlo_text: str):
    """Parse optimized HLO text into {instr_name: Instr} + entry name.

    Handles tuple-typed instructions; opcode = first bare lowercase word
    followed by '(' after the '=' (type annotations like T(8,128) are
    uppercase; tuple-open parens are not preceded by letters).
    """
    instrs = {}
    entry = None
    cur = None
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped.endswith("{") and "=" not in stripped.split("(")[0]:
            mc = re.match(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(", stripped)
            if mc:
                cur = mc.group(2)
                if mc.group(1):
                    entry = cur
                continue
        md = _DEF_RE.match(line)
        if not md or "=" not in line:
            continue
        name, rest = md.groups()
        mo = _OPCODE_RE.search(rest)
        if not mo:
            continue
        it = Instr()
        it.name, it.comp, it.opcode = name, cur, mo.group(1)
        ms = _SHAPE_RE.search(rest)
        it.shape = [int(s) for s in ms.group(2).split(",") if s] if ms else []
        # operand names: first (...) group after the opcode
        ops = rest[mo.end():]
        depth, buf = 1, []
        for ch in ops:
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    break
            buf.append(ch)
        it.operands = re.findall(r"%([\w.\-]+)", "".join(buf))
        mm = re.search(r'op_name="([^"]*)"', rest)
        it.op_name = mm.group(1) if mm else ""
        mm = re.search(r'source_file="([^"]*)"', rest)
        it.src = mm.group(1).split("/")[-1] if mm else ""
        it.line = line
        instrs[(cur, name)] = it
    return instrs, entry


def build_indexes(instrs):
    """name -> Instr within each computation + global last-wins name map."""
    by_comp = collections.defaultdict(dict)
    for (comp, name), it in instrs.items():
        by_comp[comp][name] = it
    return by_comp


def _window_params(line, nspatial):
    """Parse window={size=.. stride=.. pad=.. lhs_dilate=.. rhs_dilate=..}
    into per-spatial-dim tuples (defaults: stride 1, pad 0, dilation 1)."""
    win = re.search(r"window=\{([^}]*)\}", line)
    fields = {"size": None, "stride": None, "pad": None,
              "lhs_dilate": None, "rhs_dilate": None}
    if win:
        for part in win.group(1).split():
            if "=" in part:
                k, v = part.split("=", 1)
                if k in fields:
                    fields[k] = v.split("x")
    size = [int(s) for s in fields["size"]] if fields["size"] else [1] * nspatial
    stride = [int(s) for s in fields["stride"]] if fields["stride"] else [1] * nspatial
    ldil = [int(s) for s in fields["lhs_dilate"]] if fields["lhs_dilate"] else [1] * nspatial
    rdil = [int(s) for s in fields["rhs_dilate"]] if fields["rhs_dilate"] else [1] * nspatial
    if fields["pad"]:
        pad = [tuple(int(p) for p in s.split("_")) for s in fields["pad"]]
    else:
        pad = [(0, 0)] * nspatial
    return size, stride, pad, ldil, rdil


def _valid_pairs(o_size, k_size, stride, pad_low, l_size, lhs_dil, rhs_dil):
    """Count (output position, kernel position) pairs along one spatial
    dim whose lhs index lands on a real element — excluding zero padding
    and lhs-dilation zeros, which contribute no useful multiply.  This is
    XLA cost-analysis semantics."""
    l_span = (l_size - 1) * lhs_dil  # highest real lhs coordinate
    total = 0
    for o in range(o_size):
        base = o * stride - pad_low
        for k in range(k_size):
            l = base + k * rhs_dil
            if 0 <= l <= l_span and l % lhs_dil == 0:
                total += 1
    return total


def conv_flops(it, comp_map) -> float:
    """Useful FLOPs of a convolution Instr, directly from its own HLO
    signature — valid for ANY conv form XLA emits (forward
    ``bf01_oi01->bf01``, data-grad incl. the transposed big-window
    ``fb01_oi01->fb01`` formulation with pad K-1, filter-grad
    ``fb01_io01->fb01``): MACs = prod(out non-spatial) * (rhs 'i' dim) *
    prod over spatial dims of valid (output, kernel) index pairs.
    Padded and lhs-dilation-zero positions are excluded, so all three
    grad forms of one layer count the same FLOPs as its forward — which
    is what makes >100%%-of-roofline rows impossible by construction
    (the round-2 table's 242%% rows came from shape-matching
    heuristics).  Validated against XLA cost_analysis."""
    if not it.shape or len(it.operands) < 2:
        return 0.0
    lhs_it = comp_map.get(it.operands[0])
    rhs_it = comp_map.get(it.operands[1])
    if (lhs_it is None or rhs_it is None or not rhs_it.shape
            or not lhs_it.shape):
        return 0.0
    dl = re.search(r"dim_labels=([\w]+)_([\w]+)->([\w]+)", it.line)
    if not dl:
        return 0.0
    lhs_l, rhs_l, out_l = dl.groups()
    spatial = [c for c in out_l if c.isdigit()]
    nsp = len(spatial)
    lhs_sp = {lab: dim for dim, lab in zip(lhs_it.shape, lhs_l)}
    out_nonspatial = 1
    for dim, lab in zip(it.shape, out_l):
        if not lab.isdigit():
            out_nonspatial *= dim
    cin = 1
    for dim, lab in zip(rhs_it.shape, rhs_l):
        if lab == "i":
            cin = dim
    size, stride, pad, ldil, rdil = _window_params(it.line, nsp)
    out_sp = [dim for dim, lab in zip(it.shape, out_l) if lab.isdigit()]
    pairs = 1
    for d, lab in enumerate(spatial):
        pairs *= _valid_pairs(out_sp[d], size[d], stride[d], pad[d][0],
                              lhs_sp.get(lab, 1), ldil[d], rdil[d])
    # grouped convs need no correction: out 'f' spans all groups while
    # cin (rhs 'i') is already the per-group fan-in
    return 2.0 * out_nonspatial * cin * pairs


def conv_sig(it, comp_map) -> str:
    lhs_it = comp_map.get(it.operands[0]) if it.operands else None
    rhs_it = comp_map.get(it.operands[1]) if len(it.operands) > 1 else None
    win = re.search(r"window=\{([^}]*)\}", it.line)
    dl = re.search(r"dim_labels=(\S+?)[, ]", it.line)
    fmt = lambda s: ",".join(map(str, s)) if s else "?"
    return "out[%s]<-lhs[%s]*rhs[%s] %s %s" % (
        fmt(it.shape), fmt(lhs_it.shape if lhs_it else None),
        fmt(rhs_it.shape if rhs_it else None),
        win.group(1).split(" ")[0] if win else "",
        dl.group(1) if dl else "")


def categorize(opcode: str, op_name: str, src: str) -> str:
    o = op_name
    if (opcode == "custom-call" and "tpu_custom_call" in o) \
            or "pallas" in o or "mosaic" in o.lower():
        # Pallas kernels compile to tpu_custom_call; attribute them to
        # their own bucket so a pool/LRN/recurrence kernel adoption
        # shows up as PALLAS time, not ELTWISE/OTHER (round 6)
        return "PALLAS-KERNEL"
    if opcode == "select-and-scatter" or "select_and_scatter" in o:
        return "POOL-BWD"
    if "conv_general_dilated" in o or opcode == "convolution":
        if "transpose(" in o:
            return "CONV-BWD"
        return "CONV-FWD"
    if opcode == "reduce-window" or "reduce_window" in o:
        return "POOL-FWD(reduce_window)"
    if opcode == "dot" or "dot_general" in o:
        return "MATMUL"
    if "threefry" in o or "random" in o or "_uniform" in o or "bernoulli" in o:
        return "RNG"
    if opcode in ("copy", "copy-start", "copy-done", "transpose", "bitcast"):
        return "LAYOUT"
    if opcode in ("all-reduce", "all-gather", "reduce-scatter"):
        return "COLLECTIVE"
    return "ELTWISE/OTHER"


# ----------------------------------------------------------------- the step


def build_step(model_name: str, batch: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import bigdl_tpu.nn as nn
    from bigdl_tpu import tensor as bt
    from bigdl_tpu.nn.module import Context
    from bigdl_tpu.optim.optim_method import SGD
    from bigdl_tpu.utils.random import RNG, set_device_prng, set_seed

    set_seed(1)
    # match the bench's device-PRNG selection (rbg) unless overridden:
    # dropout-mask generation is part of the step being profiled
    set_device_prng(_os.environ.get("BIGDL_PRNG", "rbg") or None)
    pol = _os.environ.get("BIGDL_POLICY", "BF16_COMPUTE")
    if pol not in ("FP32", "BF16_COMPUTE", "BF16_ACT"):
        raise SystemExit("BIGDL_POLICY must be one of FP32/BF16_COMPUTE/"
                         "BF16_ACT, got %r" % pol)
    bt.set_policy(getattr(bt, pol))

    if model_name == "inception":
        from bigdl_tpu.models.inception import Inception_v1
        model = Inception_v1(class_num=1000)
        xshape, nclass = (batch, 3, 224, 224), 1000
    elif model_name == "vgg16":
        from bigdl_tpu.models.vgg import Vgg_16
        model = Vgg_16(class_num=1000)
        xshape, nclass = (batch, 3, 224, 224), 1000
    elif model_name == "vgg_cifar":
        # the bench config (VGG-16 bs128 CIFAR-10)
        from bigdl_tpu.models.vgg import VggForCifar10
        model = VggForCifar10(class_num=10)
        xshape, nclass = (batch, 3, 32, 32), 10
    elif model_name == "resnet50":
        from bigdl_tpu.models.resnet import ResNet
        model = ResNet(depth=50, class_num=1000)
        xshape, nclass = (batch, 3, 224, 224), 1000
    elif model_name == "lenet":
        from bigdl_tpu.models.lenet import LeNet5
        model = LeNet5(class_num=10)
        xshape, nclass = (batch, 1, 28, 28), 10
    elif model_name == "bilstm":
        from bigdl_tpu.models.textclassifier import TextClassifierBiLSTM
        model = TextClassifierBiLSTM(20, 200, hidden_size=128)
        xshape, nclass = (batch, 500, 200), 20
    elif model_name == "transformer":
        # the bench flagship geometry (bench.py configs): d_model 1024,
        # 4 heads (d_head 256 — K<=128 batched gemms are emitter-bound,
        # PERF_NOTES), ffn 4096, L6
        from bigdl_tpu.models.transformer import TransformerClassifier
        model = TransformerClassifier(class_num=20, d_model=1024,
                                      n_heads=4, n_layers=6, hidden=4096)
        xshape, nclass = (batch, 512, 1024), 20
    else:
        raise SystemExit("unknown model %s" % model_name)

    criterion = nn.ClassNLLCriterion()
    method = SGD()
    params, net_state = model.params(), model.state()
    opt_state = method.init_state(params)
    hyper = {"lr": 0.01, "momentum": 0.9, "dampening": 0.0,
             "weight_decay": 0.0001, "nesterov": False}

    def train_step(params, net_state, opt_state, x, y, key):
        def loss_fn(p):
            out, ns = model.apply(p, x, net_state, Context(training=True, key=key))
            return criterion.apply_loss(out, y), ns
        (loss, ns), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        new_params, new_opt = method.update(grads, opt_state, params, hyper)
        return new_params, ns, new_opt, loss

    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(*xshape), jnp.float32)
    y = jnp.asarray(rs.randint(1, nclass + 1, (batch,)))
    key = RNG.next_key()  # honors the device-PRNG selection above
    step = jax.jit(train_step, donate_argnums=(0, 1, 2))
    return step, (params, net_state, opt_state, x, y, key)


def _trace_device_ops(thunk, sync):
    """Run ``thunk`` under a jax.profiler trace; return
    Counter{op_name: total device us} from the TPU 'XLA Ops' rows."""
    import jax

    tmpdir = tempfile.mkdtemp(prefix="bigdl_prof_")
    jax.profiler.start_trace(tmpdir)
    sync(thunk())
    jax.profiler.stop_trace()
    fn = sorted(glob.glob(tmpdir + "/plugins/profile/*/*.trace.json.gz"))[-1]
    with gzip.open(fn) as f:
        tr = json.load(f)
    ev = tr["traceEvents"]
    pids = {e["pid"]: e["args"]["name"] for e in ev
            if e.get("ph") == "M" and e.get("name") == "process_name"}
    tids = {(e["pid"], e["tid"]): e["args"]["name"] for e in ev
            if e.get("ph") == "M" and e.get("name") == "thread_name"}
    dev_pid = [p for p, n in pids.items() if "TPU" in n][0]
    per_op = collections.Counter()
    for e in ev:
        if (e.get("ph") == "X" and e.get("pid") == dev_pid
                and tids.get((e["pid"], e["tid"])) == "XLA Ops"):
            per_op[e["name"]] += e.get("dur", 0)
    return per_op, tmpdir


def measure_matmul_roofline(iters: int = 10) -> float:
    """Achievable bf16 matmul TF/s from DEVICE-CLOCK kernel durations
    (own jax.profiler trace), not host wall time: a wall-clock roofline
    carries host dispatch noise, which is how the round-2 profile
    paired a fast trace with a slow roofline and reported conv rows
    above 100%%.  Kernel durations and the per-op table share one clock
    domain."""
    import jax
    import jax.numpy as jnp

    a = (jax.random.normal(jax.random.PRNGKey(1), (8192, 8192),
                           jnp.bfloat16) * 0.01)
    mm = jax.jit(lambda v: (v @ a).astype(jnp.bfloat16) * 0.001)
    z = mm(a)
    float(jnp.sum(z).astype(jnp.float32))  # warm

    def thunk():
        w = z
        for _ in range(iters):
            w = mm(w)
        return w

    per_op, tmpdir = _trace_device_ops(
        thunk, lambda w: float(jnp.sum(w).astype(jnp.float32)))
    import shutil
    shutil.rmtree(tmpdir, ignore_errors=True)  # roofline trace is transient
    # the dominant device op is the matmul kernel itself; everything else
    # (scale fusion, transfers) is excluded from the roofline division
    mm_us = max(per_op.values())
    return 2 * 8192 ** 3 * iters / (mm_us / 1e6) / 1e12


def profile(model_name="inception", batch=128, nsteps=5, step=None, args=None):
    import jax

    if step is None:
        step, args = build_step(model_name, batch)
    compiled = step.lower(*args).compile()
    hlo_text = compiled.as_text()
    instrs, entry = parse_hlo_module(hlo_text)
    by_comp = build_indexes(instrs)

    def comp_conv_info(comp_name, seen=None):
        """(flops, sigs, op_names, srcs) of convs in a computation,
        recursing into nested fusions."""
        seen = seen or set()
        if comp_name in seen:
            return 0.0, [], [], []
        seen.add(comp_name)
        fl, sigs, onames, srcs = 0.0, [], [], []
        cmap = by_comp.get(comp_name, {})
        for it in cmap.values():
            if it.opcode == "convolution":
                fl += conv_flops(it, cmap)
                sigs.append(conv_sig(it, cmap))
                onames.append(it.op_name)
            if it.src:
                srcs.append(it.src)
            if it.opcode == "fusion":
                mc = _CALLS_RE.search(it.line)
                if mc:
                    f2, s2, o2, r2 = comp_conv_info(mc.group(1), seen)
                    fl += f2
                    sigs += s2
                    onames += o2
                    srcs += r2
        return fl, sigs, onames, srcs

    # one cost code path (obs/ledger.py): the ledger normalizes the
    # dict/list cost_analysis forms and records the entry next to the
    # runtime captures, so this probe and bench.py report ONE number
    from bigdl_tpu.obs import ledger as cost_ledger
    _entry = cost_ledger.get().capture_compiled(("profile_step",),
                                                compiled)
    total_flops = _entry.flops if _entry is not None else float("nan")

    params, net_state, opt_state, x, y, key = args
    state = {"a": (params, net_state, opt_state)}
    for _ in range(3):
        p, n, o = state["a"]
        p, n, o, loss = step(p, n, o, x, y, key)
        state["a"] = (p, n, o)
    float(loss)

    def thunk():
        loss = None
        for _ in range(nsteps):
            p, n, o = state["a"]
            p, n, o, loss = step(p, n, o, x, y, key)
            state["a"] = (p, n, o)
        return loss

    per_op, tmpdir = _trace_device_ops(thunk, lambda l: float(l))
    roofline = measure_matmul_roofline()
    entry_map = by_comp.get(entry, {})
    rows = []
    for name, us in per_op.items():
        ms = us / 1e3 / nsteps
        it = entry_map.get(name)
        opcode = it.opcode if it else "?"
        op_name = it.op_name if it else ""
        src = it.src if it else ""
        fl, sigs = 0.0, []
        if it is not None and it.opcode == "fusion":
            mc = _CALLS_RE.search(it.line)
            if mc:
                fl, sigs, conv_onames, srcs = comp_conv_info(mc.group(1))
                if not op_name and conv_onames:
                    op_name = conv_onames[0]
                if not src and srcs:
                    src = collections.Counter(srcs).most_common(1)[0][0]
        elif it is not None and it.opcode == "convolution":
            fl = conv_flops(it, entry_map)
            sigs = [conv_sig(it, entry_map)]
        cat = categorize(opcode, op_name, src)
        if fl and cat not in ("CONV-FWD", "CONV-BWD"):
            cat = "CONV-BWD" if "transpose(" in op_name else "CONV-FWD"
        tfs = fl / (ms / 1e3) / 1e12 if ms > 0 and fl else 0.0
        rows.append({
            "name": name, "category": cat, "ms": ms, "gflop": fl / 1e9,
            "tflops": tfs,
            "pct_roofline": 100.0 * tfs / roofline if tfs else 0.0,
            "src": src, "op_name": op_name.replace("jit(train_step)/", ""),
            "sigs": sigs,
        })
    rows.sort(key=lambda r: -r["ms"])
    return rows, total_flops, roofline, tmpdir


def report(rows, total_flops, roofline, model_name, batch, path=None):
    total_ms = sum(r["ms"] for r in rows)
    by_cat = collections.defaultdict(lambda: [0.0, 0.0])
    by_src = collections.defaultdict(float)
    for r in rows:
        by_cat[r["category"]][0] += r["ms"]
        by_cat[r["category"]][1] += r["gflop"]
        by_src[r["src"] or "?"] += r["ms"]

    lines = []
    lines.append("# Per-op device profile — %s bs%d train step" % (model_name, batch))
    lines.append("")
    lines.append("Same-run matmul roofline: **%.1f TF/s**; XLA step FLOPs %.1f G; "
                 "device-busy %.2f ms/step; device-busy TF/s %.1f."
                 % (roofline, total_flops / 1e9, total_ms,
                    total_flops / total_ms / 1e9))
    lines.append("")
    lines.append("## By op kind")
    lines.append("")
    lines.append("| kind | ms/step | % busy | GFLOP | achieved TF/s | % roofline |")
    lines.append("|---|---|---|---|---|---|")
    overs = []
    for cat, (ms, gf) in sorted(by_cat.items(), key=lambda kv: -kv[1][0]):
        tfs = gf / ms if ms else 0.0          # GFLOP/ms == TF/s
        if tfs > roofline:
            overs.append(cat)
        lines.append("| %s | %.2f | %.1f%% | %.1f | %.1f | %.0f%% |"
                     % (cat, ms, 100 * ms / total_ms, gf, tfs,
                        100 * tfs / roofline))
    if overs:
        lines.append("")
        lines.append("**WARNING: %s exceed the same-run roofline — the FLOP "
                     "attribution or roofline measurement is broken; do not "
                     "trust this table.**" % ", ".join(overs))
    lines.append("")
    lines.append("## By emitting module (source_file of the fusion root)")
    lines.append("")
    lines.append("| source | ms/step | % busy |")
    lines.append("|---|---|---|")
    for src, ms in sorted(by_src.items(), key=lambda kv: -kv[1]):
        lines.append("| %s | %.2f | %.1f%% |" % (src, ms, 100 * ms / total_ms))
    lines.append("")
    lines.append("## Top ops")
    lines.append("")
    lines.append("| op | kind | ms/step | GFLOP | TF/s | %roof | source | op_name / conv |")
    lines.append("|---|---|---|---|---|---|---|---|")
    for r in rows[:45]:
        what = r["sigs"][0] if r["sigs"] else r["op_name"]
        lines.append("| %s | %s | %.3f | %.1f | %.1f | %.0f%% | %s | %s |" % (
            r["name"], r["category"], r["ms"], r["gflop"], r["tflops"],
            r["pct_roofline"], r["src"], what[:70]))
    out = "\n".join(lines) + "\n"
    if path:
        with open(path, "w") as f:
            f.write(out)
    return out


def main():
    from bigdl_tpu.utils.engine import enable_compile_cache
    enable_compile_cache()
    model_name = sys.argv[1] if len(sys.argv) > 1 else "inception"
    # per-model default batch = the bench.py config geometry (a bs128
    # transformer would be 8x the benchmarked flagship and overrun HBM)
    default_batch = {"transformer": 16, "resnet50": 64, "lenet": 256}
    batch = (int(sys.argv[2]) if len(sys.argv) > 2
             else default_batch.get(model_name, 128))
    rows, total_flops, roofline, tmpdir = profile(model_name, batch)
    path = "PROFILE_%s.md" % model_name
    print(report(rows, total_flops, roofline, model_name, batch, path))
    print("written:", path, " trace:", tmpdir)


if __name__ == "__main__":
    main()
