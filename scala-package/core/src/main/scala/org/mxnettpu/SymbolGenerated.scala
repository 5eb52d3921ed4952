package org.mxnettpu

// Generated by tools/gen_scala_ops.py from the live operator registry —
// DO NOT EDIT. Reference counterpart: the SymbolMacro compile-time macro
// expansion in the reference scala-package. One method per registered
// operator; tensor inputs ride positionally, attributes stringify.

object SymbolGenerated {
  /** relu/sigmoid/tanh/softrelu (src/operator/activation-inl.h), */
  def Activation(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("Activation", args, params, name)

  /** Normalize over all axes but channel (axis 1). In training, use batch */
  def BatchNorm(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("BatchNorm", args, params, name)

  /** (src/operator/bilinear_sampler-inl.h) — gather-based bilinear warp. */
  def BilinearSampler(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("BilinearSampler", args, params, name)

  /** Identity forward, zero gradient (src/operator/tensor/elemwise_unary_op.cc */
  def BlockGrad(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("BlockGrad", args, params, name)

  /** data (T, N, C) activations (softmax applied internally), */
  def CTCLoss(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("CTCLoss", args, params, name)

  /** Cast to a new dtype (src/operator/tensor/elemwise_unary_op.cc Cast). */
  def Cast(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("Cast", args, params, name)

  /** Depthwise convolution along each sequence of ``seq_len`` rows, */
  def CausalConv1D(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("CausalConv1D", args, params, name)

  /** Concat */
  def Concat(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("Concat", args, params, name)

  /** Convolution */
  def Convolution(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("Convolution", args, params, name)

  /** Convolution_v1 */
  def Convolution_v1(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("Convolution_v1", args, params, name)

  /** Displacement correlation (correlation-inl.h). kernel_size=1 path: */
  def Correlation(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("Correlation", args, params, name)

  /** Spatial crop (src/operator/crop-inl.h). */
  def Crop(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("Crop", args, params, name)

  /** Normalize over all axes but channel (axis 1). In training, use batch */
  def CuDNNBatchNorm(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("CuDNNBatchNorm", args, params, name)

  /** Transposed convolution = conv with lhs dilation */
  def Deconvolution(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("Deconvolution", args, params, name)

  /** Dropout */
  def Dropout(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("Dropout", args, params, name)

  /** Sum of N arrays in one fused op (src/ndarray/ndarray.cc:290 */
  def ElementWiseSum(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("ElementWiseSum", args, params, name)

  /** Embedding lookup — gather from the weight table; backward is XLA */
  def Embedding(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("Embedding", args, params, name)

  /** Flatten */
  def Flatten(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("Flatten", args, params, name)

  /** Y = X·Wᵀ + b. Flattens input to 2-D like the reference; the matmul is */
  def FullyConnected(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("FullyConnected", args, params, name)

  /** The gated delta rule (Kimi Delta Attention) over rows cut into */
  def GatedDeltaRule(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("GatedDeltaRule", args, params, name)

  /** RMSNorm of ``data * silu(gate)`` over each of ``groups`` equal */
  def GatedRMSNorm(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("GatedRMSNorm", args, params, name)

  /** Affine/warp grid generation (src/operator/grid_generator-inl.h). */
  def GridGenerator(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("GridGenerator", args, params, name)

  /** Causal attention of rows cut into sequences of ``seq_len``: */
  def GroupedQueryAttention(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("GroupedQueryAttention", args, params, name)

  /** IdentityAttachKLSparseReg */
  def IdentityAttachKLSparseReg(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("IdentityAttachKLSparseReg", args, params, name)

  /** InstanceNorm */
  def InstanceNorm(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("InstanceNorm", args, params, name)

  /** L2Normalization */
  def L2Normalization(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("L2Normalization", args, params, name)

  /** Local response norm across channels (src/operator/lrn-inl.h). */
  def LRN(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("LRN", args, params, name)

  /** The per-head keys and values of latent attention from the */
  def LatentExpand(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("LatentExpand", args, params, name)

  /** leaky/prelu/elu/rrelu (src/operator/leaky_relu-inl.h). */
  def LeakyReLU(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("LeakyReLU", args, params, name)

  /** LinearRegressionOutput */
  def LinearRegressionOutput(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("LinearRegressionOutput", args, params, name)

  /** LogisticRegressionOutput */
  def LogisticRegressionOutput(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("LogisticRegressionOutput", args, params, name)

  /** MAERegressionOutput */
  def MAERegressionOutput(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("MAERegressionOutput", args, params, name)

  /** Forward identity; backward seeds grad_scale (src/operator/make_loss-inl.h). */
  def MakeLoss(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("MakeLoss", args, params, name)

  /** Mixture-of-experts block, two layers on one router */
  def MoE(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("MoE", args, params, name)

  /** Pad */
  def Pad(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("Pad", args, params, name)

  /** max/avg/sum pooling via lax.reduce_window (src/operator/pooling-inl.h, */
  def Pooling(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("Pooling", args, params, name)

  /** max/avg/sum pooling via lax.reduce_window (src/operator/pooling-inl.h, */
  def Pooling_v1(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("Pooling_v1", args, params, name)

  /** RPN proposal generation (src/operator/contrib/proposal-inl.h): */
  def Proposal(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("Proposal", args, params, name)

  /** y = x / sqrt(mean(x^2) + eps) * gamma over the last axis, or, */
  def RMSNorm(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("RMSNorm", args, params, name)

  /** RNN */
  def RNN(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("RNN", args, params, name)

  /** ROI max pooling (src/operator/roi_pooling-inl.h). Computed with a */
  def ROIPooling(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("ROIPooling", args, params, name)

  /** Reshape */
  def Reshape(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("Reshape", args, params, name)

  /** Sequence-parallel self-attention over (B, H, T, D) inputs. */
  def RingAttention(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("RingAttention", args, params, name)

  /** Rotary position embedding over whole heads (the two halves of a */
  def RoPE(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("RoPE", args, params, name)

  /** The Mamba-2 selective scan over rows cut into sequences of */
  def SSD(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("SSD", args, params, name)

  /** Hinge-loss output layer (src/operator/svm_output-inl.h). */
  def SVMOutput(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("SVMOutput", args, params, name)

  /** SequenceLast */
  def SequenceLast(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("SequenceLast", args, params, name)

  /** SequenceMask */
  def SequenceMask(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("SequenceMask", args, params, name)

  /** SequenceReverse */
  def SequenceReverse(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("SequenceReverse", args, params, name)

  /** SliceChannel */
  def SliceChannel(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("SliceChannel", args, params, name)

  /** Softmax forward; backward = (p - onehot(label)) * grad_scale */
  def Softmax(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("Softmax", args, params, name)

  /** SoftmaxActivation */
  def SoftmaxActivation(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("SoftmaxActivation", args, params, name)

  /** Softmax forward; backward = (p - onehot(label)) * grad_scale */
  def SoftmaxOutput(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("SoftmaxOutput", args, params, name)

  /** Affine spatial transformer (src/operator/spatial_transformer-inl.h). */
  def SpatialTransformer(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("SpatialTransformer", args, params, name)

  /** SwapAxis */
  def SwapAxis(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("SwapAxis", args, params, name)

  /** TorchCriterion */
  def TorchCriterion(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("TorchCriterion", args, params, name)

  /** Nearest/bilinear upsampling (src/operator/upsampling-inl.h). */
  def UpSampling(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("UpSampling", args, params, name)

  /** WarpCTC */
  def WarpCTC(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("WarpCTC", args, params, name)

  /** Elementwise abs. */
  def abs(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("abs", args, params, name)

  /** adam_update */
  def adam_update(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("adam_update", args, params, name)

  /** Sum of N arrays in one fused op (src/ndarray/ndarray.cc:290 */
  def add_n(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("add_n", args, params, name)

  /** arange_op */
  def arange_op(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("arange_op", args, params, name)

  /** Elementwise arccos. */
  def arccos(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("arccos", args, params, name)

  /** Elementwise arccosh. */
  def arccosh(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("arccosh", args, params, name)

  /** Elementwise arcsin. */
  def arcsin(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("arcsin", args, params, name)

  /** Elementwise arcsinh. */
  def arcsinh(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("arcsinh", args, params, name)

  /** Elementwise arctan. */
  def arctan(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("arctan", args, params, name)

  /** Elementwise arctanh. */
  def arctanh(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("arctanh", args, params, name)

  /** argmax */
  def argmax(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("argmax", args, params, name)

  /** argmax over axis 1 returning same dtype (used by Accuracy metric; */
  def argmax_channel(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("argmax_channel", args, params, name)

  /** argmin */
  def argmin(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("argmin", args, params, name)

  /** argsort */
  def argsort(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("argsort", args, params, name)

  /** batch_dot */
  def batch_dot(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("batch_dot", args, params, name)

  /** batch_take */
  def batch_take(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("batch_take", args, params, name)

  /** broadcast_add */
  def broadcast_add(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("broadcast_add", args, params, name)

  /** broadcast_axes */
  def broadcast_axes(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("broadcast_axes", args, params, name)

  /** broadcast_axis */
  def broadcast_axis(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("broadcast_axis", args, params, name)

  /** broadcast_div */
  def broadcast_div(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("broadcast_div", args, params, name)

  /** broadcast_equal */
  def broadcast_equal(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("broadcast_equal", args, params, name)

  /** broadcast_greater */
  def broadcast_greater(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("broadcast_greater", args, params, name)

  /** broadcast_greater_equal */
  def broadcast_greater_equal(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("broadcast_greater_equal", args, params, name)

  /** broadcast_hypot */
  def broadcast_hypot(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("broadcast_hypot", args, params, name)

  /** broadcast_lesser */
  def broadcast_lesser(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("broadcast_lesser", args, params, name)

  /** broadcast_lesser_equal */
  def broadcast_lesser_equal(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("broadcast_lesser_equal", args, params, name)

  /** broadcast_maximum */
  def broadcast_maximum(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("broadcast_maximum", args, params, name)

  /** broadcast_minimum */
  def broadcast_minimum(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("broadcast_minimum", args, params, name)

  /** broadcast_minus */
  def broadcast_minus(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("broadcast_minus", args, params, name)

  /** broadcast_mod */
  def broadcast_mod(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("broadcast_mod", args, params, name)

  /** broadcast_mul */
  def broadcast_mul(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("broadcast_mul", args, params, name)

  /** broadcast_not_equal */
  def broadcast_not_equal(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("broadcast_not_equal", args, params, name)

  /** broadcast_plus */
  def broadcast_plus(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("broadcast_plus", args, params, name)

  /** broadcast_power */
  def broadcast_power(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("broadcast_power", args, params, name)

  /** broadcast_sub */
  def broadcast_sub(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("broadcast_sub", args, params, name)

  /** broadcast_to */
  def broadcast_to(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("broadcast_to", args, params, name)

  /** Cast to a new dtype (src/operator/tensor/elemwise_unary_op.cc Cast). */
  def cast(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("cast", args, params, name)

  /** Elementwise ceil. */
  def ceil(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("ceil", args, params, name)

  /** out[i] = lhs[i, rhs[i]] (src/ndarray/ndarray.cc:765 */
  def choose_element_0index(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("choose_element_0index", args, params, name)

  /** Clip values to [a_min, a_max] (src/operator/tensor/matrix_op.cc clip). */
  def clip(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("clip", args, params, name)

  /** concat */
  def concat(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("concat", args, params, name)

  /** Elementwise cos. */
  def cos(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("cos", args, params, name)

  /** Elementwise cosh. */
  def cosh(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("cosh", args, params, name)

  /** crop */
  def crop(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("crop", args, params, name)

  /** data (T, N, C) activations (softmax applied internally), */
  def ctc_loss(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("ctc_loss", args, params, name)

  /** Elementwise degrees. */
  def degrees(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("degrees", args, params, name)

  /** Quantized int -> float32 (src/operator/contrib/dequantize-inl.h); */
  def dequantize(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("dequantize", args, params, name)

  /** Matrix product (MXU path). Mirrors tensor/matrix_op dot incl. the */
  def dot(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("dot", args, params, name)

  /** elemwise_add */
  def elemwise_add(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("elemwise_add", args, params, name)

  /** elemwise_div */
  def elemwise_div(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("elemwise_div", args, params, name)

  /** elemwise_mul */
  def elemwise_mul(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("elemwise_mul", args, params, name)

  /** elemwise_sub */
  def elemwise_sub(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("elemwise_sub", args, params, name)

  /** Elementwise erf. */
  def erf(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("erf", args, params, name)

  /** Elementwise exp. */
  def exp(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("exp", args, params, name)

  /** expand_dims */
  def expand_dims(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("expand_dims", args, params, name)

  /** Elementwise expm1. */
  def expm1(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("expm1", args, params, name)

  /** FFT over the last dim; complex output interleaved [re, im] pairs */
  def fft(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("fft", args, params, name)

  /** lhs with lhs[i, rhs[i]] = mhs[i] (src/ndarray/ndarray.cc:771 */
  def fill_element_0index(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("fill_element_0index", args, params, name)

  /** Elementwise fix. */
  def fix(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("fix", args, params, name)

  /** flatten */
  def flatten(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("flatten", args, params, name)

  /** flip */
  def flip(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("flip", args, params, name)

  /** Elementwise floor. */
  def floor(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("floor", args, params, name)

  /** Elementwise gamma. */
  def gamma(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("gamma", args, params, name)

  /** Elementwise gammaln. */
  def gammaln(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("gammaln", args, params, name)

  /** gather_nd */
  def gather_nd(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("gather_nd", args, params, name)

  /** Elementwise identity. */
  def identity(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("identity", args, params, name)

  /** ifft */
  def ifft(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("ifft", args, params, name)

  /** linalg_gemm2 */
  def linalg_gemm2(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("linalg_gemm2", args, params, name)

  /** Elementwise log. */
  def log(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("log", args, params, name)

  /** Elementwise log10. */
  def log10(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("log10", args, params, name)

  /** Elementwise log1p. */
  def log1p(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("log1p", args, params, name)

  /** Elementwise log2. */
  def log2(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("log2", args, params, name)

  /** log_softmax */
  def log_softmax(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("log_softmax", args, params, name)

  /** Forward identity; backward seeds grad_scale (src/operator/make_loss-inl.h). */
  def make_loss(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("make_loss", args, params, name)

  /** max */
  def max(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("max", args, params, name)

  /** max_axis */
  def max_axis(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("max_axis", args, params, name)

  /** mean */
  def mean(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("mean", args, params, name)

  /** min */
  def min(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("min", args, params, name)

  /** min_axis */
  def min_axis(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("min_axis", args, params, name)

  /** nanprod */
  def nanprod(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("nanprod", args, params, name)

  /** nansum */
  def nansum(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("nansum", args, params, name)

  /** Elementwise negative. */
  def negative(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("negative", args, params, name)

  /** norm */
  def norm(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("norm", args, params, name)

  /** normal */
  def normal(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("normal", args, params, name)

  /** one_hot */
  def one_hot(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("one_hot", args, params, name)

  /** ones */
  def ones(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("ones", args, params, name)

  /** ones_like */
  def ones_like(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("ones_like", args, params, name)

  /** pad */
  def pad(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("pad", args, params, name)

  /** Pick elements along ``axis`` by per-position indices, clip mode */
  def pick(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("pick", args, params, name)

  /** pow */
  def pow(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("pow", args, params, name)

  /** prod */
  def prod(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("prod", args, params, name)

  /** Affine quantization (src/operator/contrib/quantize-inl.h:29 */
  def quantize(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("quantize", args, params, name)

  /** Elementwise radians. */
  def radians(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("radians", args, params, name)

  /** random_exponential */
  def random_exponential(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("random_exponential", args, params, name)

  /** random_gamma */
  def random_gamma(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("random_gamma", args, params, name)

  /** random_generalized_negative_binomial */
  def random_generalized_negative_binomial(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("random_generalized_negative_binomial", args, params, name)

  /** random_negative_binomial */
  def random_negative_binomial(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("random_negative_binomial", args, params, name)

  /** random_normal */
  def random_normal(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("random_normal", args, params, name)

  /** random_poisson */
  def random_poisson(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("random_poisson", args, params, name)

  /** random_randint */
  def random_randint(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("random_randint", args, params, name)

  /** random_uniform */
  def random_uniform(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("random_uniform", args, params, name)

  /** Elementwise reciprocal. */
  def reciprocal(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("reciprocal", args, params, name)

  /** Elementwise relu. */
  def relu(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("relu", args, params, name)

  /** repeat */
  def repeat(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("repeat", args, params, name)

  /** reshape */
  def reshape(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("reshape", args, params, name)

  /** reverse */
  def reverse(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("reverse", args, params, name)

  /** Elementwise rint. */
  def rint(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("rint", args, params, name)

  /** rmsprop_update */
  def rmsprop_update(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("rmsprop_update", args, params, name)

  /** Graves-form RMSProp (optimizer_op.cc rmspropalex_update). */
  def rmspropalex_update(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("rmspropalex_update", args, params, name)

  /** Elementwise round. */
  def round(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("round", args, params, name)

  /** Elementwise rsqrt. */
  def rsqrt(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("rsqrt", args, params, name)

  /** sgd_mom_update */
  def sgd_mom_update(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("sgd_mom_update", args, params, name)

  /** sgd_update */
  def sgd_update(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("sgd_update", args, params, name)

  /** Elementwise sigmoid. */
  def sigmoid(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("sigmoid", args, params, name)

  /** Elementwise sign. */
  def sign(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("sign", args, params, name)

  /** Elementwise sin. */
  def sin(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("sin", args, params, name)

  /** Elementwise sinh. */
  def sinh(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("sinh", args, params, name)

  /** slice */
  def slice(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("slice", args, params, name)

  /** slice_axis */
  def slice_axis(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("slice_axis", args, params, name)

  /** smooth_l1 */
  def smooth_l1(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("smooth_l1", args, params, name)

  /** softmax */
  def softmax(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("softmax", args, params, name)

  /** Scalar -sum(log softmax(data)[i, label_i]) */
  def softmax_cross_entropy(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("softmax_cross_entropy", args, params, name)

  /** Elementwise softsign. */
  def softsign(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("softsign", args, params, name)

  /** sort */
  def sort(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("sort", args, params, name)

  /** split */
  def split(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("split", args, params, name)

  /** Elementwise sqrt. */
  def sqrt(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("sqrt", args, params, name)

  /** Elementwise square. */
  def square(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("square", args, params, name)

  /** Identity forward, zero gradient (src/operator/tensor/elemwise_unary_op.cc */
  def stop_gradient(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("stop_gradient", args, params, name)

  /** sum */
  def sum(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("sum", args, params, name)

  /** sum_axis */
  def sum_axis(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("sum_axis", args, params, name)

  /** swapaxes */
  def swapaxes(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("swapaxes", args, params, name)

  /** take */
  def take(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("take", args, params, name)

  /** Elementwise tan. */
  def tan(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("tan", args, params, name)

  /** Elementwise tanh. */
  def tanh(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("tanh", args, params, name)

  /** tile */
  def tile(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("tile", args, params, name)

  /** topk */
  def topk(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("topk", args, params, name)

  /** transpose */
  def transpose(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("transpose", args, params, name)

  /** uniform */
  def uniform(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("uniform", args, params, name)

  /** where */
  def where(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("where", args, params, name)

  /** zeros */
  def zeros(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("zeros", args, params, name)

  /** zeros_like */
  def zeros_like(args: Map[String, Symbol],
      params: Map[String, String] = Map.empty,
      name: String = null): Symbol =
    Symbol.create("zeros_like", args, params, name)

}
