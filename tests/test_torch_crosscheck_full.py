"""Full-inventory independent-oracle checks against CPU torch.

The reference golden-tests 112 layers against live Torch7
(dl/src/test/scala/com/intel/analytics/bigdl/torch/, TH.scala:35); torch
is the same lineage oracle available here.  Every layer/criterion in
SURVEY.md §2.3 with a torch equivalent is checked for FORWARD and
GRADIENTS (input-grad + every weight-grad) through one parametrized
harness; layers without a torch equivalent are covered by tests/golden.

Complements test_torch_crosscheck.py (hand-written spot checks with
extra semantics, e.g. BatchNorm running-stat updates).
"""
import numpy as np
import pytest

torch = pytest.importorskip("torch")
import torch.nn.functional as F  # noqa: E402

import bigdl_tpu.nn as nn  # noqa: E402
from bigdl_tpu.utils.table import T  # noqa: E402

RS = np.random.RandomState(7)
TOL = dict(rtol=1e-4, atol=1e-5)


def t(x):
    return torch.from_numpy(np.array(x, np.float32))


def run_layer(mod, xs, torch_fwd, *, train=False, input_grad=True,
              param_grad=True, tol=None, grad_scale=1.0):
    """Forward + input-grad + param-grad crosscheck of one module.

    xs: list of np input arrays (len>1 => Table input).
    torch_fwd(txs, P) -> torch tensor, where txs are torch leaf tensors
    and P maps our param names to torch leaf tensors.
    """
    tol = tol or TOL
    (mod.training() if train else mod.evaluate())
    inp = T(*xs) if len(xs) > 1 else xs[0]
    y = np.asarray(mod.forward(inp))

    txs = [t(x).requires_grad_(True) for x in xs]
    P = {k: t(np.asarray(v)).requires_grad_(True)
         for k, v in mod._params.items()}
    ty = torch_fwd(txs, P)
    np.testing.assert_allclose(y, ty.detach().numpy(), **tol)

    g = (RS.randn(*y.shape) * grad_scale).astype(np.float32)
    mod.zero_grad_parameters()
    gin = mod.backward(inp, g if len(xs) == 1 else T(
        *np.split(g, 1)) if False else g)
    ty.backward(t(g))
    if input_grad:
        gins = list(gin) if len(xs) > 1 else [gin]
        for gi, txi in zip(gins, txs):
            if txi.grad is None:
                continue
            np.testing.assert_allclose(np.asarray(gi), txi.grad.numpy(),
                                       **tol)
    if param_grad:
        for k, tp in P.items():
            np.testing.assert_allclose(np.asarray(mod._grads[k]),
                                       tp.grad.numpy(), **tol)


def run_criterion(crit, x, target, torch_loss, *, tol=None, input_grad=True):
    tol = tol or TOL
    loss = float(crit.forward(x, target))
    tx = t(x).requires_grad_(True)
    tl = torch_loss(tx)
    np.testing.assert_allclose(loss, float(tl), **tol)
    if input_grad:
        gin = crit.backward(x, target)
        tl.backward()
        np.testing.assert_allclose(np.asarray(gin), tx.grad.numpy(), **tol)


def x4(c=5, h=6, w=6, n=2, positive=False):
    a = RS.randn(n, c, h, w).astype(np.float32)
    return np.abs(a) + 0.5 if positive else a


def x2(d=7, n=4, positive=False):
    a = RS.randn(n, d).astype(np.float32)
    return np.abs(a) + 0.5 if positive else a


# ------------------------------------------------------------- layer cases
# name -> () -> (mod, [inputs], torch_fwd, kwargs)

def _act(mod, fn, positive=False, **kw):
    return lambda: (mod(), [x4(positive=positive)],
                    lambda txs, P: fn(txs[0], P), kw)


LAYER_CASES = {
    # activations (§2.3 "Activations (24)")
    "ReLU": _act(nn.ReLU, lambda x, P: F.relu(x)),
    "ReLU6": _act(nn.ReLU6, lambda x, P: F.relu6(x)),
    "Tanh": _act(nn.Tanh, lambda x, P: torch.tanh(x)),
    "TanhShrink": _act(nn.TanhShrink, lambda x, P: x - torch.tanh(x)),
    "Sigmoid": _act(nn.Sigmoid, lambda x, P: torch.sigmoid(x)),
    "LogSigmoid": _act(nn.LogSigmoid, lambda x, P: F.logsigmoid(x)),
    "SoftPlus": _act(lambda: nn.SoftPlus(1.7),
                     lambda x, P: F.softplus(x, beta=1.7)),
    "SoftSign": _act(nn.SoftSign, lambda x, P: F.softsign(x)),
    "SoftShrink": _act(lambda: nn.SoftShrink(0.4),
                       lambda x, P: F.softshrink(x, 0.4)),
    "HardShrink": _act(lambda: nn.HardShrink(0.4),
                       lambda x, P: F.hardshrink(x, 0.4)),
    "HardTanh": _act(lambda: nn.HardTanh(-0.7, 0.8),
                     lambda x, P: F.hardtanh(x, -0.7, 0.8)),
    "Clamp": _act(lambda: nn.Clamp(-1, 1),
                  lambda x, P: torch.clamp(x, -1, 1)),
    "Threshold": _act(lambda: nn.Threshold(0.3, -2.0),
                      lambda x, P: F.threshold(x, 0.3, -2.0)),
    "LeakyReLU": _act(lambda: nn.LeakyReLU(0.07),
                      lambda x, P: F.leaky_relu(x, 0.07)),
    "ELU": _act(lambda: nn.ELU(0.9), lambda x, P: F.elu(x, 0.9)),
    "Abs": _act(nn.Abs, lambda x, P: torch.abs(x)),
    "Sqrt": _act(nn.Sqrt, lambda x, P: torch.sqrt(x), positive=True),
    "Square": _act(nn.Square, lambda x, P: x * x),
    "Power": _act(lambda: nn.Power(2.0, 1.5, 0.3),
                  lambda x, P: (0.3 + 1.5 * x) ** 2.0, positive=True),
    "Exp": _act(nn.Exp, lambda x, P: torch.exp(x)),
    "Log": _act(nn.Log, lambda x, P: torch.log(x), positive=True),
    "LogSoftMax": lambda: (nn.LogSoftMax(), [x2()],
                           lambda txs, P: F.log_softmax(txs[0], 1), {}),
    "SoftMax": lambda: (nn.SoftMax(), [x2()],
                        lambda txs, P: F.softmax(txs[0], 1), {}),
    "SoftMin": lambda: (nn.SoftMin(), [x2()],
                        lambda txs, P: F.softmin(txs[0], 1), {}),
    "PReLU": lambda: (nn.PReLU(5), [x4(c=5)],
                      lambda txs, P: F.prelu(txs[0], P["weight"]), {}),
    "RReLU(eval)": _act(lambda: nn.RReLU(1 / 8.0, 1 / 3.0),
                        lambda x, P: F.rrelu(x, 1 / 8.0, 1 / 3.0,
                                             training=False)),
    "GradientReversal": lambda: (
        nn.GradientReversal(0.5), [x2()],
        # forward identity, gradient scaled by -lam = -0.5
        lambda txs, P: txs[0] * (-0.5) + (txs[0] * 1.5).detach(), {}),

    # linear-algebra family (§2.3 "Linear-algebra layers (10)")
    "Linear": lambda: (nn.Linear(7, 4), [x2(7)],
                       lambda txs, P: F.linear(txs[0], P["weight"],
                                               P["bias"]), {}),
    "Linear(no-bias)": lambda: (nn.Linear(7, 4, with_bias=False), [x2(7)],
                                lambda txs, P: F.linear(txs[0], P["weight"]),
                                {}),
    "Bilinear": lambda: (
        nn.Bilinear(5, 4, 3), [x2(5), x2(4)],
        lambda txs, P: F.bilinear(txs[0], txs[1], P["weight"], P["bias"]),
        {}),
    "CMul": lambda: (nn.CMul((1, 6)), [x2(6)],
                     lambda txs, P: txs[0] * P["weight"], {}),
    "CAdd": lambda: (nn.CAdd((1, 6)), [x2(6)],
                     lambda txs, P: txs[0] + P["bias"], {}),
    "Mul": lambda: (nn.Mul(), [x2()],
                    lambda txs, P: txs[0] * P["weight"], {}),
    "MulConstant": _act(lambda: nn.MulConstant(2.5),
                        lambda x, P: x * 2.5),
    "AddConstant": _act(lambda: nn.AddConstant(1.2),
                        lambda x, P: x + 1.2),
    "MM": lambda: (nn.MM(), [RS.randn(3, 4, 5).astype(np.float32),
                             RS.randn(3, 5, 6).astype(np.float32)],
                   lambda txs, P: torch.bmm(txs[0], txs[1]), {}),
    "MM(transA)": lambda: (nn.MM(trans_a=True),
                           [RS.randn(3, 5, 4).astype(np.float32),
                            RS.randn(3, 5, 6).astype(np.float32)],
                           lambda txs, P: torch.bmm(
                               txs[0].transpose(1, 2), txs[1]), {}),
    "MV": lambda: (nn.MV(), [RS.randn(3, 4, 5).astype(np.float32),
                             RS.randn(3, 5).astype(np.float32)],
                   lambda txs, P: torch.bmm(
                       txs[0], txs[1].unsqueeze(-1)).squeeze(-1), {}),
    "Cosine": lambda: (
        nn.Cosine(6, 4), [x2(6)],
        lambda txs, P: F.linear(F.normalize(txs[0], dim=-1, eps=1e-12),
                                F.normalize(P["weight"], dim=-1, eps=1e-12)),
        dict(tol=dict(rtol=1e-3, atol=1e-4))),
    "Euclidean": lambda: (
        nn.Euclidean(6, 4), [x2(6)],
        lambda txs, P: torch.cdist(txs[0], P["weight"].T),
        dict(tol=dict(rtol=1e-3, atol=1e-4))),
    "LookupTable": lambda: (
        nn.LookupTable(10, 6),
        [np.asarray([[1, 4, 9], [2, 10, 3]], np.float32)],
        lambda txs, P: F.embedding(txs[0].long() - 1, P["weight"]),
        dict(input_grad=False)),

    # reductions / indexing
    "Mean": lambda: (nn.Mean(2, n_input_dims=2), [x2()],
                     lambda txs, P: txs[0].mean(dim=1), {}),
    "Sum": lambda: (nn.Sum(2, n_input_dims=2), [x2()],
                    lambda txs, P: txs[0].sum(dim=1), {}),
    "Max": lambda: (nn.Max(2, num_input_dims=1), [x2()],
                    lambda txs, P: txs[0].max(dim=1).values, {}),
    "Min": lambda: (nn.Min(2, num_input_dims=1), [x2()],
                    lambda txs, P: txs[0].min(dim=1).values, {}),
    "Select": lambda: (nn.Select(2, 3), [x2()],
                       lambda txs, P: txs[0][:, 2], {}),
    "Narrow": lambda: (nn.Narrow(2, 2, 3), [x2()],
                       lambda txs, P: txs[0][:, 1:4], {}),

    # conv/spatial family
    "SpatialConvolution": lambda: (
        nn.SpatialConvolution(3, 8, 3, 3, 1, 1, 1, 1), [x4(3, 7, 7)],
        lambda txs, P: F.conv2d(txs[0], P["weight"], P["bias"], padding=1),
        {}),
    "SpatialConvolution(s2g2)": lambda: (
        nn.SpatialConvolution(4, 6, 3, 3, 2, 2, 1, 1, n_group=2),
        [x4(4, 9, 9)],
        lambda txs, P: F.conv2d(txs[0], P["weight"], P["bias"], stride=2,
                                padding=1, groups=2), {}),
    "SpatialConvolution(stem7x7s2)": lambda: (
        # exercises the space-to-depth stem (conv.py _space_to_depth_conv)
        nn.SpatialConvolution(3, 8, 7, 7, 2, 2, 3, 3), [x4(3, 16, 16)],
        lambda txs, P: F.conv2d(txs[0], P["weight"], P["bias"], stride=2,
                                padding=3),
        dict(tol=dict(rtol=1e-3, atol=1e-4))),
    "SpatialDilatedConvolution": lambda: (
        nn.SpatialDilatedConvolution(3, 5, 3, 3, 1, 1, 2, 2, 2, 2),
        [x4(3, 8, 8)],
        lambda txs, P: F.conv2d(txs[0], P["weight"], P["bias"], padding=2,
                                dilation=2), {}),
    "SpatialFullConvolution": lambda: (
        nn.SpatialFullConvolution(3, 5, 3, 3, 2, 2, 1, 1, 1, 1),
        [x4(3, 5, 5)],
        lambda txs, P: F.conv_transpose2d(txs[0], P["weight"], P["bias"],
                                          stride=2, padding=1,
                                          output_padding=1), {}),
    "SpatialMaxPooling": lambda: (
        nn.SpatialMaxPooling(3, 3, 2, 2, 1, 1), [x4(3, 8, 8)],
        lambda txs, P: F.max_pool2d(txs[0], 3, 2, 1), {}),
    "SpatialMaxPooling(k2s2)": lambda: (
        nn.SpatialMaxPooling(2, 2, 2, 2), [x4(3, 8, 8)],
        lambda txs, P: F.max_pool2d(txs[0], 2), {}),
    "SpatialAveragePooling": lambda: (
        nn.SpatialAveragePooling(3, 3, 2, 2, 1, 1,
                                 count_include_pad=False), [x4(3, 8, 8)],
        lambda txs, P: F.avg_pool2d(txs[0], 3, 2, 1,
                                    count_include_pad=False), {}),
    "SpatialBatchNormalization(train)": lambda: (
        nn.SpatialBatchNormalization(4), [x4(4)],
        lambda txs, P: F.batch_norm(
            txs[0], torch.zeros(4), torch.ones(4), P["weight"], P["bias"],
            training=True),
        dict(train=True, tol=dict(rtol=1e-3, atol=1e-4))),
    "BatchNormalization(train)": lambda: (
        nn.BatchNormalization(6), [x2(6, n=8)],
        lambda txs, P: F.batch_norm(
            txs[0], torch.zeros(6), torch.ones(6), P["weight"], P["bias"],
            training=True),
        dict(train=True, tol=dict(rtol=1e-3, atol=1e-4))),
    "SpatialCrossMapLRN": lambda: (
        nn.SpatialCrossMapLRN(5, 1e-4, 0.75, 1.0), [x4(7, 4, 4) * 3],
        lambda txs, P: F.local_response_norm(txs[0], 5, alpha=1e-4,
                                             beta=0.75, k=1.0), {}),
    "SpatialZeroPadding": lambda: (
        nn.SpatialZeroPadding(1, 2, 3, 0), [x4(3)],
        lambda txs, P: F.pad(txs[0], (1, 2, 3, 0)), {}),

    # table ops
    "CAddTable": lambda: (nn.CAddTable(), [x2(), x2()],
                          lambda txs, P: txs[0] + txs[1], {}),
    "CSubTable": lambda: (nn.CSubTable(), [x2(), x2()],
                          lambda txs, P: txs[0] - txs[1], {}),
    "CMulTable": lambda: (nn.CMulTable(), [x2(), x2()],
                          lambda txs, P: txs[0] * txs[1], {}),
    "CDivTable": lambda: (nn.CDivTable(), [x2(), x2(positive=True)],
                          lambda txs, P: txs[0] / txs[1], {}),
    "CMaxTable": lambda: (nn.CMaxTable(), [x2(), x2()],
                          lambda txs, P: torch.maximum(txs[0], txs[1]), {}),
    "CMinTable": lambda: (nn.CMinTable(), [x2(), x2()],
                          lambda txs, P: torch.minimum(txs[0], txs[1]), {}),
    "JoinTable": lambda: (nn.JoinTable(1, 1), [x2(), x2()],
                          lambda txs, P: torch.cat([txs[0], txs[1]], 1), {}),
    "DotProduct": lambda: (nn.DotProduct(), [x2(), x2()],
                           lambda txs, P: (txs[0] * txs[1]).sum(-1), {}),
    "PairwiseDistance": lambda: (
        nn.PairwiseDistance(2), [x2(), x2()],
        lambda txs, P: F.pairwise_distance(txs[0], txs[1], p=2, eps=0.0),
        dict(tol=dict(rtol=1e-3, atol=1e-4))),
    "CosineDistance": lambda: (
        nn.CosineDistance(), [x2(), x2()],
        lambda txs, P: F.cosine_similarity(txs[0], txs[1], dim=-1),
        dict(tol=dict(rtol=1e-3, atol=1e-4))),

    # shape ops
    "Reshape": lambda: (nn.Reshape([3, 14]), [x4(6, 7, 1)],
                        lambda txs, P: txs[0].reshape(2, 3, 14), {}),
    "View": lambda: (nn.View(42), [x4(6, 7, 1)],
                     lambda txs, P: txs[0].reshape(2, 42), {}),
    "Transpose": lambda: (nn.Transpose([(2, 3)]), [x4()],
                          lambda txs, P: txs[0].transpose(1, 2), {}),
    "Replicate": lambda: (nn.Replicate(3, 2), [x2()],
                          lambda txs, P: txs[0].unsqueeze(1).expand(
                              4, 3, 7), {}),
    "Squeeze": lambda: (nn.Squeeze(2, num_input_dims=3), [x4(1, 5, 5)],
                        lambda txs, P: txs[0].squeeze(1), {}),
    "Unsqueeze": lambda: (nn.Unsqueeze(2), [x2()],
                          lambda txs, P: txs[0].unsqueeze(1), {}),
    "Contiguous": lambda: (nn.Contiguous(), [x2()],
                           lambda txs, P: txs[0] * 1.0, {}),
    "Copy": lambda: (nn.Copy(), [x2()], lambda txs, P: txs[0] * 1.0, {}),
    "Identity": lambda: (nn.Identity(), [x2()],
                         lambda txs, P: txs[0] * 1.0, {}),

    # recurrent cells (no LSTM/GRU in the reference — SURVEY §2.3; torch
    # cells are the natural oracle for the capability extension)
    "LSTMCell": lambda: _lstm_cell_case(),
    "GRUCell": lambda: _gru_cell_case(),
}


def _lstm_cell_case():
    d, h, n = 5, 4, 3
    cell = nn.LSTMCell(d, h)
    x = x2(d, n)
    hx = RS.randn(n, h).astype(np.float32)
    cx = RS.randn(n, h).astype(np.float32)

    class Wrap(nn.Module):
        def __init__(self):
            super().__init__()
            self._params = cell._params
            self._grads = cell._grads

        def _forward(self, P, xx, S, ctx):
            out, _ = cell._step(P, xx[1], (xx[2], xx[3]), ctx)
            return out, None

    def torch_fwd(txs, P):
        w, b = P["w"], P["bias"]
        hh, _ = torch.nn.functional.linear(
            torch.cat([txs[0], txs[1]], dim=-1), w, b).chunk(1, 0)[0], None
        i, f, g, o = hh.chunk(4, -1)
        c2 = torch.sigmoid(f) * txs[2] + torch.sigmoid(i) * torch.tanh(g)
        return torch.sigmoid(o) * torch.tanh(c2)

    return Wrap(), [x, hx, cx], torch_fwd, {}


def _gru_cell_case():
    d, h, n = 5, 4, 3
    cell = nn.GRUCell(d, h)
    x = x2(d, n)
    hx = RS.randn(n, h).astype(np.float32)

    class Wrap(nn.Module):
        def __init__(self):
            super().__init__()
            self._params = cell._params
            self._grads = cell._grads

        def _forward(self, P, xx, S, ctx):
            out, _ = cell._step(P, xx[1], xx[2], ctx)
            return out, None

    def torch_fwd(txs, P):
        xh = torch.cat([txs[0], txs[1]], dim=-1)
        rz = torch.sigmoid(F.linear(xh, P["w_rz"], P["b_rz"]))
        r, z = rz.chunk(2, -1)
        xrh = torch.cat([txs[0], r * txs[1]], dim=-1)
        nn_ = torch.tanh(F.linear(xrh, P["w_h"], P["b_h"]))
        return (1 - z) * nn_ + z * txs[1]

    return Wrap(), [x, hx], torch_fwd, {}


@pytest.mark.parametrize("name", sorted(LAYER_CASES))
def test_layer_vs_torch(name):
    case = LAYER_CASES[name]()
    if isinstance(case, tuple) and len(case) == 4:
        mod, xs, torch_fwd, kw = case
    else:  # cell cases return the tuple directly
        mod, xs, torch_fwd, kw = case
    run_layer(mod, xs, torch_fwd, **kw)


# --------------------------------------------------------- criterion cases

def crit_cases():
    x = x2(6)
    y = x2(6)
    logp = np.asarray(nn.LogSoftMax().forward(x2(6)))
    labels = np.asarray([1, 3, 6, 2], np.float32)
    tgt01 = (RS.rand(4, 6) > 0.5).astype(np.float32)
    tgt_pm = np.sign(RS.randn(4, 6)).astype(np.float32)
    p = 1 / (1 + np.exp(-x))
    cases = {
        "ClassNLL": (nn.ClassNLLCriterion(), logp, labels,
                     lambda tx: F.nll_loss(
                         tx, torch.tensor(labels.astype(int) - 1)), {}),
        "CrossEntropy": (nn.CrossEntropyCriterion(), x, labels,
                         lambda tx: F.cross_entropy(
                             tx, torch.tensor(labels.astype(int) - 1)), {}),
        "MSE": (nn.MSECriterion(), x, y,
                lambda tx: F.mse_loss(tx, t(y)), {}),
        "Abs": (nn.AbsCriterion(), x, y,
                lambda tx: F.l1_loss(tx, t(y)), {}),
        "SmoothL1": (nn.SmoothL1Criterion(), x, y,
                     lambda tx: F.smooth_l1_loss(tx, t(y)), {}),
        "BCE": (nn.BCECriterion(), p, tgt01,
                lambda tx: F.binary_cross_entropy(tx, t(tgt01)),
                dict(tol=dict(rtol=1e-3, atol=1e-4))),
        "DistKLDiv": (nn.DistKLDivCriterion(), logp, np.abs(y) / 10,
                      lambda tx: F.kl_div(tx, t(np.abs(y) / 10),
                                          reduction="batchmean") * 1.0,
                      dict(tol=dict(rtol=1e-3, atol=1e-3))),
        "SoftMargin": (nn.SoftMarginCriterion(), x, tgt_pm,
                       lambda tx: F.soft_margin_loss(tx, t(tgt_pm)), {}),
        "MultiLabelSoftMargin": (
            nn.MultiLabelSoftMarginCriterion(), x, tgt01,
            lambda tx: F.multilabel_soft_margin_loss(tx, t(tgt01)),
            dict(tol=dict(rtol=1e-3, atol=1e-4))),
        "MultiMargin": (
            nn.MultiMarginCriterion(), x, labels,
            lambda tx: F.multi_margin_loss(
                tx, torch.tensor(labels.astype(int) - 1)), {}),
        "MultiLabelMargin": (
            nn.MultiLabelMarginCriterion(), x,
            np.asarray([[2, 4, 0, 0, 0, 0]] * 4, np.float32),
            lambda tx: F.multilabel_margin_loss(
                tx, torch.tensor([[1, 3, -1, -1, -1, -1]] * 4)), {}),
        "L1Cost": (nn.L1Cost(), x, x,
                   lambda tx: tx.abs().sum(), {}),
        "HingeEmbedding": (
            nn.HingeEmbeddingCriterion(1.0), x2(1, n=6).ravel(),
            np.sign(RS.randn(6)).astype(np.float32), None, {}),
        "MarginRanking": (nn.MarginRankingCriterion(0.5), None, None, None,
                          {}),
        "CosineEmbedding": (nn.CosineEmbeddingCriterion(0.3), None, None,
                            None, {}),
    }
    return cases


@pytest.mark.parametrize("name", [
    "ClassNLL", "CrossEntropy", "MSE", "Abs", "SmoothL1", "BCE",
    "DistKLDiv", "SoftMargin", "MultiLabelSoftMargin", "MultiMargin",
    "MultiLabelMargin", "L1Cost"])
def test_criterion_vs_torch(name):
    crit, x, target, torch_loss, kw = crit_cases()[name]
    run_criterion(crit, x, target, torch_loss, **kw)


def test_hinge_embedding_vs_torch():
    x = np.abs(RS.randn(6).astype(np.float32)) + 0.1
    yy = np.sign(RS.randn(6)).astype(np.float32)
    crit = nn.HingeEmbeddingCriterion(1.0)
    run_criterion(crit, x, yy,
                  lambda tx: F.hinge_embedding_loss(tx, t(yy), margin=1.0))


def test_margin_ranking_vs_torch():
    a = x2(1, n=5).ravel()
    b = x2(1, n=5).ravel()
    yy = np.sign(RS.randn(5)).astype(np.float32)
    crit = nn.MarginRankingCriterion(0.5)
    loss = float(crit.forward(T(a, b), yy))
    ta, tb = t(a).requires_grad_(True), t(b).requires_grad_(True)
    tl = F.margin_ranking_loss(ta, tb, t(yy), margin=0.5)
    np.testing.assert_allclose(loss, float(tl), **TOL)
    gin = crit.backward(T(a, b), yy)
    tl.backward()
    np.testing.assert_allclose(np.asarray(gin[1]), ta.grad.numpy(), **TOL)
    np.testing.assert_allclose(np.asarray(gin[2]), tb.grad.numpy(), **TOL)


def test_cosine_embedding_vs_torch():
    a, b = x2(6, n=5), x2(6, n=5)
    yy = np.sign(RS.randn(5)).astype(np.float32)
    crit = nn.CosineEmbeddingCriterion(0.3)
    loss = float(crit.forward(T(a, b), yy))
    ta, tb = t(a).requires_grad_(True), t(b).requires_grad_(True)
    tl = F.cosine_embedding_loss(ta, tb, t(yy), margin=0.3)
    np.testing.assert_allclose(loss, float(tl), rtol=1e-3, atol=1e-4)
    gin = crit.backward(T(a, b), yy)
    tl.backward()
    np.testing.assert_allclose(np.asarray(gin[1]), ta.grad.numpy(),
                               rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gin[2]), tb.grad.numpy(),
                               rtol=1e-3, atol=1e-4)


def test_recurrent_lstm_sequence_vs_torch():
    """Full scan over time vs torch.nn.LSTM (single layer, batch_first)."""
    d, h, n, steps = 5, 4, 3, 7
    rec = nn.Recurrent().add(nn.LSTMCell(d, h))
    rec.evaluate()
    x = RS.randn(n, steps, d).astype(np.float32)
    y = np.asarray(rec.forward(x))

    cellp = rec.cell._params
    w = np.asarray(cellp["w"])          # (4H, D+H), gate order i,f,g,o
    bias = np.asarray(cellp["bias"])
    tl = torch.nn.LSTM(d, h, batch_first=True)
    with torch.no_grad():
        tl.weight_ih_l0.copy_(t(w[:, :d]))
        tl.weight_hh_l0.copy_(t(w[:, d:]))
        tl.bias_ih_l0.copy_(t(bias))
        tl.bias_hh_l0.zero_()
    ty, _ = tl(t(x))
    np.testing.assert_allclose(y, ty.detach().numpy(), rtol=1e-4,
                               atol=1e-4)


def test_birecurrent_lstm_vs_torch_bidirectional():
    d, h, n, steps = 5, 4, 3, 6
    bi = nn.BiRecurrent(nn.LSTMCell(d, h), nn.LSTMCell(d, h))
    bi.evaluate()
    x = RS.randn(n, steps, d).astype(np.float32)
    y = np.asarray(bi.forward(x))

    fw = bi.modules[0].cell._params
    bw = bi.modules[1].cell._params
    tl = torch.nn.LSTM(d, h, batch_first=True, bidirectional=True)
    with torch.no_grad():
        tl.weight_ih_l0.copy_(t(np.asarray(fw["w"])[:, :d]))
        tl.weight_hh_l0.copy_(t(np.asarray(fw["w"])[:, d:]))
        tl.bias_ih_l0.copy_(t(np.asarray(fw["bias"])))
        tl.bias_hh_l0.zero_()
        tl.weight_ih_l0_reverse.copy_(t(np.asarray(bw["w"])[:, :d]))
        tl.weight_hh_l0_reverse.copy_(t(np.asarray(bw["w"])[:, d:]))
        tl.bias_ih_l0_reverse.copy_(t(np.asarray(bw["bias"])))
        tl.bias_hh_l0_reverse.zero_()
    ty, _ = tl(t(x))
    np.testing.assert_allclose(y, ty.detach().numpy(), rtol=1e-4,
                               atol=1e-4)
