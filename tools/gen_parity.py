"""Regenerate PARITY.md — the SURVEY.md §2 inventory → `file:line` map.

  python tools/gen_parity.py        # rewrites PARITY.md in place

Checked by tests/test_parity_doc.py (references must resolve).
"""
import inspect
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

NN_NAMES = """Sequential Concat ConcatTable ParallelTable MapTable Bottle Recurrent TimeDistributed
SpatialConvolution SpatialShareConvolution SpatialFullConvolution SpatialDilatedConvolution SpatialConvolutionMap
SpatialMaxPooling SpatialAveragePooling SpatialBatchNormalization BatchNormalization SpatialCrossMapLRN
SpatialContrastiveNormalization SpatialDivisiveNormalization SpatialSubtractiveNormalization SpatialZeroPadding RoiPooling Nms
Linear Bilinear CMul CAdd Mul Add MulConstant AddConstant MM MV Cosine Euclidean LookupTable
Mean Sum Max Min Index Select Narrow MaskedSelect
ReLU ReLU6 PReLU RReLU LeakyReLU ELU Tanh TanhShrink Sigmoid LogSigmoid LogSoftMax SoftMax SoftMin SoftPlus
SoftShrink SoftSign HardTanh HardShrink Threshold Clamp Abs Sqrt Square Power Exp Log GradientReversal
CAddTable CSubTable CMulTable CDivTable CMaxTable CMinTable JoinTable SelectTable NarrowTable FlattenTable
MixtureTable CriterionTable DotProduct PairwiseDistance CosineDistance
Reshape InferReshape View Transpose Replicate Squeeze Unsqueeze Padding Contiguous Copy Identity Echo
RnnCell LSTMCell GRUCell BiRecurrent TimeDistributedCriterion Dropout L1Penalty
ClassNLLCriterion CrossEntropyCriterion MSECriterion AbsCriterion BCECriterion DistKLDivCriterion
ClassSimplexCriterion CosineEmbeddingCriterion HingeEmbeddingCriterion L1HingeEmbeddingCriterion
MarginCriterion MarginRankingCriterion MultiCriterion ParallelCriterion MultiLabelMarginCriterion
MultiLabelSoftMarginCriterion MultiMarginCriterion SmoothL1Criterion SmoothL1CriterionWithWeights
SoftMarginCriterion SoftmaxWithCriterion L1Cost""".split()

OPTIM_NAMES = ("Optimizer DistriOptimizer LocalOptimizer SGD Adagrad LBFGS "
               "OptimMethod Trigger Top1Accuracy Top5Accuracy Loss "
               "EvaluateMethods Metrics Validator LocalValidator "
               "DistriValidator Predictor DLClassifier save_model "
               "save_state").split()

DATASET_NAMES = ("DataSet LocalDataSet DistributedDataSet ShardedDataSet "
                 "Transformer ChainedTransformer SampleToBatch PreFetch "
                 "Sample MiniBatch ByteRecord BytesToBGRImg BytesToGreyImg "
                 "BGRImgNormalizer BGRImgPixelNormalizer BGRImgCropper "
                 "BGRImgRdmCropper HFlip ColoJitter Lighting BGRImgToBatch "
                 "MTLabeledBGRImgToBatch BGRImgToImageVector LabeledSentence "
                 "LabeledSentenceToSample Dictionary WordTokenizer").split()

UTILS_NAMES = ("Engine Table T File TorchFile CaffeLoader RandomGenerator "
               "kth_largest ModelBroadcast").split()

MODEL_NAMES = ("LeNet5 VggForCifar10 Vgg_16 Vgg_19 Inception_v1 "
               "Inception_v1_NoAuxClassifier Inception_v2 ResNet ResNetCifar "
               "Autoencoder SimpleRNN AlexNet AlexNet_OWT "
               "TextClassifierConv TextClassifierBiLSTM").split()


def loc(obj):
    if isinstance(obj, types.ModuleType):
        return f"`{obj.__file__.split(ROOT + '/')[-1]}`"
    try:
        f = inspect.getsourcefile(obj).split(ROOT + "/")[-1]
        return f"`{f}:{inspect.getsourcelines(obj)[1]}`"
    except TypeError:
        return "(builtin/alias)"


def table(mod, names):
    rows = []
    for n in names:
        obj = getattr(mod, n)
        where = loc(obj)
        if n == "Engine":
            where = "`bigdl_tpu/utils/engine.py:20` (`_Engine` singleton instance)"
        rows.append(f"| {n} | {where} |")
    return "\n".join(rows)


def main():
    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as o
    import bigdl_tpu.dataset as d
    import bigdl_tpu.utils as u
    import bigdl_tpu.models as m

    doc = f"""# PARITY — SURVEY.md §2 component inventory → implementation

Machine-generated name→`file:line` map (regenerate with
``python tools/gen_parity.py``) so the reference's component inventory can
be checked line by line.  Every name resolves from the package namespaces
exactly as listed.  Reference citations live in each implementation's
docstring.

## §2.2 Tensor package

The reference's 6.5k-LoC tensor layer dissolves into jnp + XLA by design
(SURVEY.md §7 item 1).  What remains: `bigdl_tpu/tensor/__init__.py` —
`DTypePolicy` (the TensorNumeric dtype role), `narrow`/`select`
Torch-shape helpers.  Tensor *capabilities* (views, elementwise, BLAS) are
jnp; the MKL-fallback seam maps to `bigdl_tpu/native/` (C++ hostops with
numpy fallback, the MKL.java discovery/fallback role).

## §2.3 NN package (nn/ — containers, layers, activations, criterions)

| Component | Implementation |
|---|---|
{table(nn, NN_NAMES)}

## §2.4 Dataset package

| Component | Implementation |
|---|---|
{table(d, DATASET_NAMES)}

Shard streaming (SeqFileFolder/ImageNetSeqFileGenerator roles):
`bigdl_tpu/dataset/shardfile.py`, `bigdl_tpu/dataset/imagenet_tools.py`,
`DataSet.seq_file_folder` — which, as of round 5, also ingests ACTUAL
Hadoop SequenceFiles in the reference's wire format
(`bigdl_tpu/dataset/seqfile.py`: version-6 reader/writer,
BGRImgToLocalSeqFile/LocalSeqFileToBytes/SeqBytesToBGRImg transformers,
readLabel/readName key semantics, class_num filter — ref
DataSet.scala:384-455, BGRImgToLocalSeqFile.scala,
LocalSeqFileToBytes.scala).  20-newsgroups + GloVe ingestion (the Python
news20.py role): `bigdl_tpu/dataset/news20.py` (offline, pre-extracted
trees).  Built-in readers: `bigdl_tpu/dataset/mnist.py`,
`bigdl_tpu/dataset/cifar.py`.

## §2.5 Parameters package (communication backend)

| Reference component | TPU-native equivalent |
|---|---|
| AllReduceParameter reduce-scatter/all-gather | XLA all-reduce emitted by the jit train step (`bigdl_tpu/optim/distri_optimizer.py` `_core_step`); explicit collectives in `bigdl_tpu/parallel/collectives.py` |
| FP16CompressedTensor / FP16SplitsCompressedTensor | `DistriOptimizer(gradient_compression="bf16")` — `bigdl_tpu/optim/distri_optimizer.py` `_build_step_compressed` (bf16 gradient all-reduce over the wire) |
| per-partition weight update (owner slice) | `DistriOptimizer(zero1=True)` — `bigdl_tpu/parallel/sharding.py` `zero1_rule` |
| syncPool / parallel fp16 add | XLA collective scheduling (no user-facing equivalent needed) |

## §2.6 Optim package

| Component | Implementation |
|---|---|
{table(o, OPTIM_NAMES)}

## §2.7 Utils package

| Component | Implementation |
|---|---|
{table(u, UTILS_NAMES)}

Also: `bigdl_tpu/utils/log.py` (log4j.properties role),
`bigdl_tpu/utils/profiler.py` (per-module times + jax.profiler traces),
`Engine.check_singleton` (race-detection role, §5.2).

## §2.8 Models & examples

| Component | Implementation |
|---|---|
{table(m, MODEL_NAMES)}

Train/Test mains: `examples/train_*.py`, `examples/model_validator.py`,
`examples/image_classification.py`, `examples/text_classifier.py`.
Perf CLIs: `bigdl_tpu/models/utils/perf.py` +
`local_optimizer_perf.py` / `distri_optimizer_perf.py`.

## §2.9 Parallelism strategies

| Strategy | Status | Where |
|---|---|---|
| Data parallelism (inter+intra node) | YES | `DistriOptimizer` (mesh `data` axis; intra-node splitting dissolves into XLA, SURVEY §2.9) |
| Parameter sharding all-reduce | YES | jit-emitted reduce-scatter/all-gather; `parallel/collectives.py` |
| Gradient compression | YES | `gradient_compression="bf16"` |
| Straggler mitigation | YES (as gradient masking) | `set_drop_module_property` / `drop_percentage=` — kth-largest time threshold, masked `psum(w*g)/sum(w)`, max-drop rejection (`optim/straggler.py`; ref DistriOptimizer.scala:154-172,:245-278) |
| Intra-op threading | YES (free) | XLA fusion |
| Tensor parallelism | YES (beyond ref) | `parallel/sharding.py` + `tensor_parallel=True` |
| Pipeline parallelism | YES (beyond ref) | `parallel/pipeline.py` |
| Sequence/context parallelism | YES (beyond ref) | `parallel/ring_attention.py` |
| Expert parallelism (MoE) | YES (beyond ref) | `parallel/moe.py` |
| ZeRO-1 | YES (beyond ref) | `zero1=True` |
| Per-param learning rates | YES | `T(learningRates=...)` in the jit SGD path |

## Documented intentional divergences

Deliberate behavior differences from the reference (not bugs; parity
audits should not flag these):

- `Lighting` (`bigdl_tpu/dataset/image.py`): alpha drawn from
  `normal(0, alphastd)` per fb.resnet.torch, where Lighting.scala:41 draws
  `uniform(0, alphastd)`; the RGB-ordered eigen rows are flipped for
  BGR-decoded images, where the reference applies them unflipped.
- `BGRImgCropper` defaults to random crop (reference default CropRandom);
  the framework-native `ImgCropper` spelling defaults to center crop for
  validation pipelines.
- Straggler dropping masks gradients instead of cancelling tasks: an XLA
  dispatch cannot be cancelled mid-flight, so a replica whose measured time
  exceeded the threshold is masked out of the NEXT iteration's aggregation
  (one-dispatch lag vs the reference's in-flight `invokeAndWait2` timeout);
  threshold arithmetic, finished-count division, and the max-drop rejection
  follow the reference exactly (`optim/straggler.py`).
- Maxpool gradient tie rule (`bigdl_tpu/nn/pooling.py _max_pool2d`):
  exact non-overlapping pools (kernel == stride, unpadded, windows tiling
  the input — the VGG/LeNet shape) use a reshape+max formulation whose backward splits the gradient
  EVENLY among tied in-window maxima; the reference/Torch routes the full
  gradient to the FIRST maximum in row-major order (overlapping/padded
  pools here use XLA select-and-scatter: one winner, possibly a different
  tie).  Ties are common with byte-quantized image inputs, so gradients
  diverge from the reference there while per-window gradient mass is
  identical (porting guide #6).
- RNG: seeded determinism is preserved, but streams are JAX counter-based
  PRNG, not Torch's Mersenne-Twister (SURVEY §7 hard parts).
- RNN generation (`models/rnn.generate`) samples the standard inverse-CDF
  index `(cumsum < rand).sum()`; the reference's
  `cumsum.filter(_ < rand).length - 1` (rnn/Test.scala:70-77) is off by
  one against its own cumulative array and can yield -1.
"""
    out = os.path.join(ROOT, "PARITY.md")
    with open(out, "w") as f:
        f.write(doc)
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
