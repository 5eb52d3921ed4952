package org.mxnettpu

// Generated by tools/gen_scala_ops.py from the live operator registry —
// DO NOT EDIT. Reference counterpart: the NDArrayMacro compile-time macro
// expansion in the reference scala-package. One method per registered
// operator; tensor inputs ride positionally, attributes stringify.

object NDArrayGenerated {
  /** relu/sigmoid/tanh/softrelu (src/operator/activation-inl.h),
    * args: data: NDArray-or-Symbol; act_type: string, optional
    */
  def Activation(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("Activation", inputs, params, outputs)

  /** Normalize over all axes but channel (axis 1). In training, use batch
    * args: data: NDArray-or-Symbol; gamma: NDArray-or-Symbol; beta: NDArray-or-Symbol; eps: float, optional; fix_gamma: boolean, optional; momentum: float, optional; output_mean_var: boolean, optional; use_global_stats: boolean, optional
    */
  def BatchNorm(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("BatchNorm", inputs, params, outputs)

  /** (src/operator/bilinear_sampler-inl.h) — gather-based bilinear warp.
    * args: data: NDArray-or-Symbol; grid: NDArray-or-Symbol
    */
  def BilinearSampler(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("BilinearSampler", inputs, params, outputs)

  /** Identity forward, zero gradient (src/operator/tensor/elemwise_unary_op.cc
    * args: data: NDArray-or-Symbol
    */
  def BlockGrad(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("BlockGrad", inputs, params, outputs)

  /** data (T, N, C) activations (softmax applied internally),
    * args: data: NDArray-or-Symbol; label: NDArray-or-Symbol; blank_label: string, optional; use_data_lengths: boolean, optional; use_label_lengths: boolean, optional
    */
  def CTCLoss(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("CTCLoss", inputs, params, outputs)

  /** Cast to a new dtype (src/operator/tensor/elemwise_unary_op.cc Cast).
    * args: data: NDArray-or-Symbol; dtype: string, optional
    */
  def Cast(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("Cast", inputs, params, outputs)

  /** Depthwise convolution along each sequence of ``seq_len`` rows,
    * args: data: NDArray-or-Symbol; weight: NDArray-or-Symbol; bias: NDArray-or-Symbol; kernel: int, required; no_bias: boolean, optional; seq_len: int, required
    */
  def CausalConv1D(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("CausalConv1D", inputs, params, outputs)

  /** Concat
    * args: arg0: NDArray-or-Symbol; dim: int, optional; num_args: int, required
    */
  def Concat(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("Concat", inputs, params, outputs)

  /** Convolution
    * args: data: NDArray-or-Symbol; weight: NDArray-or-Symbol; bias: NDArray-or-Symbol; cudnn_off: boolean, optional; cudnn_tune: string, optional; dilate: Shape(tuple), optional; kernel: Shape(tuple), required; layout: string, optional; no_bias: boolean, optional; num_filter: int, required; num_group: int, optional; pad: Shape(tuple), optional; stride: Shape(tuple), optional; workspace: int, optional
    */
  def Convolution(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("Convolution", inputs, params, outputs)

  /** Convolution_v1
    * args: data: NDArray-or-Symbol; weight: NDArray-or-Symbol; bias: NDArray-or-Symbol; cudnn_off: boolean, optional; cudnn_tune: string, optional; dilate: Shape(tuple), optional; kernel: Shape(tuple), required; layout: string, optional; no_bias: boolean, optional; num_filter: int, required; num_group: int, optional; pad: Shape(tuple), optional; stride: Shape(tuple), optional; workspace: int, optional
    */
  def Convolution_v1(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("Convolution_v1", inputs, params, outputs)

  /** Displacement correlation (correlation-inl.h). kernel_size=1 path:
    * args: data1: NDArray-or-Symbol; data2: NDArray-or-Symbol; is_multiply: boolean, optional; kernel_size: int, optional; max_displacement: int, optional; pad_size: int, optional; stride1: int, optional; stride2: int, optional
    */
  def Correlation(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("Correlation", inputs, params, outputs)

  /** Spatial crop (src/operator/crop-inl.h).
    * args: data: NDArray-or-Symbol; center_crop: boolean, optional; h_w: Shape(tuple), optional; num_args: int, optional; offset: Shape(tuple), optional
    */
  def Crop(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("Crop", inputs, params, outputs)

  /** Normalize over all axes but channel (axis 1). In training, use batch
    * args: data: NDArray-or-Symbol; gamma: NDArray-or-Symbol; beta: NDArray-or-Symbol; eps: float, optional; fix_gamma: boolean, optional; momentum: float, optional; output_mean_var: boolean, optional; use_global_stats: boolean, optional
    */
  def CuDNNBatchNorm(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("CuDNNBatchNorm", inputs, params, outputs)

  /** Transposed convolution = conv with lhs dilation
    * args: data: NDArray-or-Symbol; weight: NDArray-or-Symbol; adj: Shape(tuple), optional; kernel: Shape(tuple), required; no_bias: boolean, optional; num_filter: int, required; num_group: int, optional; pad: Shape(tuple), optional; stride: Shape(tuple), optional; target_shape: Shape(tuple), optional; workspace: int, optional
    */
  def Deconvolution(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("Deconvolution", inputs, params, outputs)

  /** Dropout
    * args: data: NDArray-or-Symbol; p: float, optional
    */
  def Dropout(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("Dropout", inputs, params, outputs)

  /** Sum of N arrays in one fused op (src/ndarray/ndarray.cc:290
    * args: arg0: NDArray-or-Symbol; num_args: int, required
    */
  def ElementWiseSum(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("ElementWiseSum", inputs, params, outputs)

  /** Embedding lookup — gather from the weight table; backward is XLA
    * args: data: NDArray-or-Symbol; weight: NDArray-or-Symbol; input_dim: int, required; output_dim: int, required
    */
  def Embedding(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("Embedding", inputs, params, outputs)

  /** Flatten
    * args: data: NDArray-or-Symbol
    */
  def Flatten(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("Flatten", inputs, params, outputs)

  /** Y = X·Wᵀ + b. Flattens input to 2-D like the reference; the matmul is
    * args: data: NDArray-or-Symbol; weight: NDArray-or-Symbol; bias: NDArray-or-Symbol; no_bias: boolean, optional; num_hidden: int, required
    */
  def FullyConnected(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("FullyConnected", inputs, params, outputs)

  /** The gated delta rule (Kimi Delta Attention) over rows cut into
    * args: query: NDArray-or-Symbol; key: NDArray-or-Symbol; value: NDArray-or-Symbol; gate: NDArray-or-Symbol; beta: NDArray-or-Symbol; A_log: NDArray-or-Symbol; dt_bias: NDArray-or-Symbol; chunk: int, optional; head_dim: int, required; heads: int, required; seq_len: int, required
    */
  def GatedDeltaRule(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("GatedDeltaRule", inputs, params, outputs)

  /** RMSNorm of ``data * silu(gate)`` over each of ``groups`` equal
    * args: data: NDArray-or-Symbol; gate: NDArray-or-Symbol; gamma: NDArray-or-Symbol; eps: float, optional; groups: int, optional
    */
  def GatedRMSNorm(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("GatedRMSNorm", inputs, params, outputs)

  /** Affine/warp grid generation (src/operator/grid_generator-inl.h).
    * args: data: NDArray-or-Symbol; target_shape: Shape(tuple), optional; transform_type: string, optional
    */
  def GridGenerator(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("GridGenerator", inputs, params, outputs)

  /** Causal attention of rows cut into sequences of ``seq_len``:
    * args: query: NDArray-or-Symbol; key: NDArray-or-Symbol; value: NDArray-or-Symbol; causal: boolean, optional; gated: boolean, optional; head_dim: int, required; num_heads: int, required; num_kv_heads: int, required; scale: float, optional; seq_len: int, required; v_head_dim: int, optional; window: int, optional
    */
  def GroupedQueryAttention(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("GroupedQueryAttention", inputs, params, outputs)

  /** IdentityAttachKLSparseReg
    * args: data: NDArray-or-Symbol; momentum: float, optional; penalty: float, optional; sparseness_target: float, optional
    */
  def IdentityAttachKLSparseReg(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("IdentityAttachKLSparseReg", inputs, params, outputs)

  /** InstanceNorm
    * args: data: NDArray-or-Symbol; gamma: NDArray-or-Symbol; beta: NDArray-or-Symbol; eps: float, optional
    */
  def InstanceNorm(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("InstanceNorm", inputs, params, outputs)

  /** L2Normalization
    * args: data: NDArray-or-Symbol; eps: float, optional; mode: string, optional
    */
  def L2Normalization(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("L2Normalization", inputs, params, outputs)

  /** Local response norm across channels (src/operator/lrn-inl.h).
    * args: data: NDArray-or-Symbol; alpha: float, optional; beta: float, optional; knorm: float, optional; nsize: int, optional
    */
  def LRN(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("LRN", inputs, params, outputs)

  /** The per-head keys and values of latent attention from the
    * args: data: NDArray-or-Symbol; shared_key: NDArray-or-Symbol; key_dim: int, required; num_heads: int, required; value_dim: int, required
    */
  def LatentExpand(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("LatentExpand", inputs, params, outputs)

  /** leaky/prelu/elu/rrelu (src/operator/leaky_relu-inl.h).
    * args: data: NDArray-or-Symbol; act_type: string, optional; lower_bound: float, optional; slope: float, optional; upper_bound: float, optional
    */
  def LeakyReLU(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("LeakyReLU", inputs, params, outputs)

  /** LinearRegressionOutput
    * args: data: NDArray-or-Symbol; label: NDArray-or-Symbol; grad_scale: float, optional
    */
  def LinearRegressionOutput(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("LinearRegressionOutput", inputs, params, outputs)

  /** LogisticRegressionOutput
    * args: data: NDArray-or-Symbol; label: NDArray-or-Symbol; grad_scale: float, optional
    */
  def LogisticRegressionOutput(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("LogisticRegressionOutput", inputs, params, outputs)

  /** MAERegressionOutput
    * args: data: NDArray-or-Symbol; label: NDArray-or-Symbol; grad_scale: float, optional
    */
  def MAERegressionOutput(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("MAERegressionOutput", inputs, params, outputs)

  /** Forward identity; backward seeds grad_scale (src/operator/make_loss-inl.h).
    * args: data: NDArray-or-Symbol; grad_scale: float, optional; normalization: string, optional; valid_thresh: float, optional
    */
  def MakeLoss(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("MakeLoss", inputs, params, outputs)

  /** Mixture-of-experts block, two layers on one router
    * args: data: NDArray-or-Symbol; gate_weight: NDArray-or-Symbol; expert1_weight: NDArray-or-Symbol; expert1_bias: NDArray-or-Symbol; expert2_weight: NDArray-or-Symbol; expert2_bias: NDArray-or-Symbol; capacity_factor: float, optional; expert_act: string, optional; experts_held: Shape(tuple), optional; gated: boolean, optional; hidden_size: int, required; load_balance_coeff: float, optional; num_experts: int, required; num_experts_per_tok: int, optional; route_norm: boolean, optional; route_scale: float, optional; score_func: string, optional
    */
  def MoE(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("MoE", inputs, params, outputs)

  /** Pad
    * args: data: NDArray-or-Symbol; constant_value: float, optional; mode: string, optional; pad_width: Shape(tuple), optional
    */
  def Pad(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("Pad", inputs, params, outputs)

  /** max/avg/sum pooling via lax.reduce_window (src/operator/pooling-inl.h,
    * args: data: NDArray-or-Symbol; cudnn_off: boolean, optional; global_pool: boolean, optional; kernel: Shape(tuple), optional; pad: Shape(tuple), optional; pool_type: string, optional; pooling_convention: string, optional; stride: Shape(tuple), optional
    */
  def Pooling(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("Pooling", inputs, params, outputs)

  /** max/avg/sum pooling via lax.reduce_window (src/operator/pooling-inl.h,
    * args: data: NDArray-or-Symbol; cudnn_off: boolean, optional; global_pool: boolean, optional; kernel: Shape(tuple), optional; pad: Shape(tuple), optional; pool_type: string, optional; pooling_convention: string, optional; stride: Shape(tuple), optional
    */
  def Pooling_v1(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("Pooling_v1", inputs, params, outputs)

  /** RPN proposal generation (src/operator/contrib/proposal-inl.h):
    * args: cls_prob: NDArray-or-Symbol; bbox_pred: NDArray-or-Symbol; im_info: NDArray-or-Symbol; feature_stride: int, optional; iou_loss: boolean, optional; output_score: boolean, optional; ratios: Shape(tuple), optional; rpn_min_size: int, optional; rpn_post_nms_top_n: int, optional; rpn_pre_nms_top_n: int, optional; scales: Shape(tuple), optional; threshold: float, optional
    */
  def Proposal(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("Proposal", inputs, params, outputs)

  /** y = x / sqrt(mean(x^2) + eps) * gamma over the last axis, or,
    * args: data: NDArray-or-Symbol; gamma: NDArray-or-Symbol; eps: float, optional; width: int, optional
    */
  def RMSNorm(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("RMSNorm", inputs, params, outputs)

  /** RNN
    * args: data: NDArray-or-Symbol; parameters: NDArray-or-Symbol; state: NDArray-or-Symbol; state_cell: NDArray-or-Symbol; bidirectional: boolean, optional; lstm_state_clip_max: float, optional; lstm_state_clip_min: float, optional; mode: string, required; num_layers: int, required; p: float, optional; state_outputs: boolean, optional; state_size: int, required
    */
  def RNN(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("RNN", inputs, params, outputs)

  /** ROI max pooling (src/operator/roi_pooling-inl.h). Computed with a
    * args: data: NDArray-or-Symbol; rois: NDArray-or-Symbol; pooled_size: Shape(tuple), optional; spatial_scale: float, optional
    */
  def ROIPooling(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("ROIPooling", inputs, params, outputs)

  /** Reshape
    * args: data: NDArray-or-Symbol; reverse: boolean, optional; shape: Shape(tuple), optional
    */
  def Reshape(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("Reshape", inputs, params, outputs)

  /** Sequence-parallel self-attention over (B, H, T, D) inputs.
    * args: query: NDArray-or-Symbol; key: NDArray-or-Symbol; value: NDArray-or-Symbol; causal: boolean, optional; scale: float, optional
    */
  def RingAttention(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("RingAttention", inputs, params, outputs)

  /** Rotary position embedding over whole heads (the two halves of a
    * args: data: NDArray-or-Symbol; head_dim: int, required; seq_len: int, required; theta: float, optional
    */
  def RoPE(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("RoPE", inputs, params, outputs)

  /** The Mamba-2 selective scan over rows cut into sequences of
    * args: data: NDArray-or-Symbol; dt: NDArray-or-Symbol; B: NDArray-or-Symbol; C: NDArray-or-Symbol; A_log: NDArray-or-Symbol; dt_bias: NDArray-or-Symbol; D: NDArray-or-Symbol; chunk: int, optional; groups: int, required; head_dim: int, required; heads: int, required; seq_len: int, required; state: int, required
    */
  def SSD(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("SSD", inputs, params, outputs)

  /** Hinge-loss output layer (src/operator/svm_output-inl.h).
    * args: data: NDArray-or-Symbol; label: NDArray-or-Symbol; margin: float, optional; regularization_coefficient: float, optional; use_linear: boolean, optional
    */
  def SVMOutput(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("SVMOutput", inputs, params, outputs)

  /** SequenceLast
    * args: data: NDArray-or-Symbol; use_sequence_length: boolean, optional
    */
  def SequenceLast(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("SequenceLast", inputs, params, outputs)

  /** SequenceMask
    * args: data: NDArray-or-Symbol; use_sequence_length: boolean, optional; value: float, optional
    */
  def SequenceMask(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("SequenceMask", inputs, params, outputs)

  /** SequenceReverse
    * args: data: NDArray-or-Symbol; use_sequence_length: boolean, optional
    */
  def SequenceReverse(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("SequenceReverse", inputs, params, outputs)

  /** SliceChannel
    * args: data: NDArray-or-Symbol; axis: int, optional; num_outputs: int, optional; squeeze_axis: boolean, optional
    */
  def SliceChannel(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("SliceChannel", inputs, params, outputs)

  /** Softmax forward; backward = (p - onehot(label)) * grad_scale
    * args: data: NDArray-or-Symbol; label: NDArray-or-Symbol; grad_scale: float, optional; ignore_label: float, optional; multi_output: boolean, optional; normalization: string, optional; out_grad: boolean, optional; preserve_shape: boolean, optional; smooth_alpha: float, optional; use_ignore: boolean, optional
    */
  def Softmax(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("Softmax", inputs, params, outputs)

  /** SoftmaxActivation
    * args: data: NDArray-or-Symbol; mode: string, optional
    */
  def SoftmaxActivation(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("SoftmaxActivation", inputs, params, outputs)

  /** Softmax forward; backward = (p - onehot(label)) * grad_scale
    * args: data: NDArray-or-Symbol; label: NDArray-or-Symbol; grad_scale: float, optional; ignore_label: float, optional; multi_output: boolean, optional; normalization: string, optional; out_grad: boolean, optional; preserve_shape: boolean, optional; smooth_alpha: float, optional; use_ignore: boolean, optional
    */
  def SoftmaxOutput(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("SoftmaxOutput", inputs, params, outputs)

  /** Affine spatial transformer (src/operator/spatial_transformer-inl.h).
    * args: data: NDArray-or-Symbol; loc: NDArray-or-Symbol; sampler_type: string, optional; target_shape: Shape(tuple), optional; transform_type: string, optional
    */
  def SpatialTransformer(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("SpatialTransformer", inputs, params, outputs)

  /** SwapAxis
    * args: data: NDArray-or-Symbol; dim1: int, optional; dim2: int, optional
    */
  def SwapAxis(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("SwapAxis", inputs, params, outputs)

  /** TorchCriterion
    * args: data: NDArray-or-Symbol; label: NDArray-or-Symbol; grad_scale: float, optional; label_shape: Shape(tuple), optional; lua_string: string, required
    */
  def TorchCriterion(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("TorchCriterion", inputs, params, outputs)

  /** Nearest/bilinear upsampling (src/operator/upsampling-inl.h).
    * args: arg0: NDArray-or-Symbol; multi_input_mode: string, optional; num_args: int, required; num_filter: int, optional; sample_type: string, optional; scale: int, optional
    */
  def UpSampling(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("UpSampling", inputs, params, outputs)

  /** WarpCTC
    * args: data: NDArray-or-Symbol; label: NDArray-or-Symbol; input_length: int, optional; label_length: int, optional
    */
  def WarpCTC(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("WarpCTC", inputs, params, outputs)

  /** Elementwise abs.
    * args: data: NDArray-or-Symbol
    */
  def abs(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("abs", inputs, params, outputs)

  /** adam_update
    * args: weight: NDArray-or-Symbol; grad: NDArray-or-Symbol; mean: NDArray-or-Symbol; var: NDArray-or-Symbol; beta1: float, optional; beta2: float, optional; clip_gradient: float, optional; epsilon: float, optional; lr: float, optional; rescale_grad: float, optional; wd: float, optional
    */
  def adam_update(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("adam_update", inputs, params, outputs)

  /** Sum of N arrays in one fused op (src/ndarray/ndarray.cc:290
    * args: arg0: NDArray-or-Symbol; num_args: int, required
    */
  def add_n(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("add_n", inputs, params, outputs)

  /** arange_op
    * args: dtype: string, optional; repeat: int, optional; start: float, optional; step: float, optional; stop: float, optional
    */
  def arange_op(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("arange_op", inputs, params, outputs)

  /** Elementwise arccos.
    * args: data: NDArray-or-Symbol
    */
  def arccos(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("arccos", inputs, params, outputs)

  /** Elementwise arccosh.
    * args: data: NDArray-or-Symbol
    */
  def arccosh(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("arccosh", inputs, params, outputs)

  /** Elementwise arcsin.
    * args: data: NDArray-or-Symbol
    */
  def arcsin(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("arcsin", inputs, params, outputs)

  /** Elementwise arcsinh.
    * args: data: NDArray-or-Symbol
    */
  def arcsinh(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("arcsinh", inputs, params, outputs)

  /** Elementwise arctan.
    * args: data: NDArray-or-Symbol
    */
  def arctan(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("arctan", inputs, params, outputs)

  /** Elementwise arctanh.
    * args: data: NDArray-or-Symbol
    */
  def arctanh(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("arctanh", inputs, params, outputs)

  /** argmax
    * args: data: NDArray-or-Symbol; axis: int, optional; keepdims: boolean, optional
    */
  def argmax(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("argmax", inputs, params, outputs)

  /** argmax over axis 1 returning same dtype (used by Accuracy metric;
    * args: data: NDArray-or-Symbol
    */
  def argmax_channel(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("argmax_channel", inputs, params, outputs)

  /** argmin
    * args: data: NDArray-or-Symbol; axis: int, optional; keepdims: boolean, optional
    */
  def argmin(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("argmin", inputs, params, outputs)

  /** argsort
    * args: data: NDArray-or-Symbol; axis: int, optional; is_ascend: boolean, optional
    */
  def argsort(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("argsort", inputs, params, outputs)

  /** batch_dot
    * args: lhs: NDArray-or-Symbol; rhs: NDArray-or-Symbol; transpose_a: boolean, optional; transpose_b: boolean, optional
    */
  def batch_dot(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("batch_dot", inputs, params, outputs)

  /** batch_take
    * args: a: NDArray-or-Symbol; indices: NDArray-or-Symbol
    */
  def batch_take(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("batch_take", inputs, params, outputs)

  /** broadcast_add
    * args: lhs: NDArray-or-Symbol; rhs: NDArray-or-Symbol
    */
  def broadcast_add(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("broadcast_add", inputs, params, outputs)

  /** broadcast_axes
    * args: data: NDArray-or-Symbol; axis: Shape(tuple), optional; size: Shape(tuple), optional
    */
  def broadcast_axes(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("broadcast_axes", inputs, params, outputs)

  /** broadcast_axis
    * args: data: NDArray-or-Symbol; axis: Shape(tuple), optional; size: Shape(tuple), optional
    */
  def broadcast_axis(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("broadcast_axis", inputs, params, outputs)

  /** broadcast_div
    * args: lhs: NDArray-or-Symbol; rhs: NDArray-or-Symbol
    */
  def broadcast_div(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("broadcast_div", inputs, params, outputs)

  /** broadcast_equal
    * args: lhs: NDArray-or-Symbol; rhs: NDArray-or-Symbol
    */
  def broadcast_equal(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("broadcast_equal", inputs, params, outputs)

  /** broadcast_greater
    * args: lhs: NDArray-or-Symbol; rhs: NDArray-or-Symbol
    */
  def broadcast_greater(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("broadcast_greater", inputs, params, outputs)

  /** broadcast_greater_equal
    * args: lhs: NDArray-or-Symbol; rhs: NDArray-or-Symbol
    */
  def broadcast_greater_equal(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("broadcast_greater_equal", inputs, params, outputs)

  /** broadcast_hypot
    * args: lhs: NDArray-or-Symbol; rhs: NDArray-or-Symbol
    */
  def broadcast_hypot(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("broadcast_hypot", inputs, params, outputs)

  /** broadcast_lesser
    * args: lhs: NDArray-or-Symbol; rhs: NDArray-or-Symbol
    */
  def broadcast_lesser(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("broadcast_lesser", inputs, params, outputs)

  /** broadcast_lesser_equal
    * args: lhs: NDArray-or-Symbol; rhs: NDArray-or-Symbol
    */
  def broadcast_lesser_equal(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("broadcast_lesser_equal", inputs, params, outputs)

  /** broadcast_maximum
    * args: lhs: NDArray-or-Symbol; rhs: NDArray-or-Symbol
    */
  def broadcast_maximum(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("broadcast_maximum", inputs, params, outputs)

  /** broadcast_minimum
    * args: lhs: NDArray-or-Symbol; rhs: NDArray-or-Symbol
    */
  def broadcast_minimum(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("broadcast_minimum", inputs, params, outputs)

  /** broadcast_minus
    * args: lhs: NDArray-or-Symbol; rhs: NDArray-or-Symbol
    */
  def broadcast_minus(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("broadcast_minus", inputs, params, outputs)

  /** broadcast_mod
    * args: lhs: NDArray-or-Symbol; rhs: NDArray-or-Symbol
    */
  def broadcast_mod(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("broadcast_mod", inputs, params, outputs)

  /** broadcast_mul
    * args: lhs: NDArray-or-Symbol; rhs: NDArray-or-Symbol
    */
  def broadcast_mul(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("broadcast_mul", inputs, params, outputs)

  /** broadcast_not_equal
    * args: lhs: NDArray-or-Symbol; rhs: NDArray-or-Symbol
    */
  def broadcast_not_equal(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("broadcast_not_equal", inputs, params, outputs)

  /** broadcast_plus
    * args: lhs: NDArray-or-Symbol; rhs: NDArray-or-Symbol
    */
  def broadcast_plus(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("broadcast_plus", inputs, params, outputs)

  /** broadcast_power
    * args: lhs: NDArray-or-Symbol; rhs: NDArray-or-Symbol
    */
  def broadcast_power(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("broadcast_power", inputs, params, outputs)

  /** broadcast_sub
    * args: lhs: NDArray-or-Symbol; rhs: NDArray-or-Symbol
    */
  def broadcast_sub(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("broadcast_sub", inputs, params, outputs)

  /** broadcast_to
    * args: data: NDArray-or-Symbol; shape: Shape(tuple), optional
    */
  def broadcast_to(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("broadcast_to", inputs, params, outputs)

  /** Cast to a new dtype (src/operator/tensor/elemwise_unary_op.cc Cast).
    * args: data: NDArray-or-Symbol; dtype: string, optional
    */
  def cast(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("cast", inputs, params, outputs)

  /** Elementwise ceil.
    * args: data: NDArray-or-Symbol
    */
  def ceil(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("ceil", inputs, params, outputs)

  /** out[i] = lhs[i, rhs[i]] (src/ndarray/ndarray.cc:765
    * args: lhs: NDArray-or-Symbol; rhs: NDArray-or-Symbol
    */
  def choose_element_0index(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("choose_element_0index", inputs, params, outputs)

  /** Clip values to [a_min, a_max] (src/operator/tensor/matrix_op.cc clip).
    * args: data: NDArray-or-Symbol; a_max: float, optional; a_min: float, optional
    */
  def clip(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("clip", inputs, params, outputs)

  /** concat
    * args: arg0: NDArray-or-Symbol; dim: int, optional; num_args: int, required
    */
  def concat(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("concat", inputs, params, outputs)

  /** Elementwise cos.
    * args: data: NDArray-or-Symbol
    */
  def cos(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("cos", inputs, params, outputs)

  /** Elementwise cosh.
    * args: data: NDArray-or-Symbol
    */
  def cosh(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("cosh", inputs, params, outputs)

  /** crop
    * args: data: NDArray-or-Symbol; begin: Shape(tuple), optional; end: Shape(tuple), optional
    */
  def crop(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("crop", inputs, params, outputs)

  /** data (T, N, C) activations (softmax applied internally),
    * args: data: NDArray-or-Symbol; label: NDArray-or-Symbol; blank_label: string, optional; use_data_lengths: boolean, optional; use_label_lengths: boolean, optional
    */
  def ctc_loss(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("ctc_loss", inputs, params, outputs)

  /** Elementwise degrees.
    * args: data: NDArray-or-Symbol
    */
  def degrees(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("degrees", inputs, params, outputs)

  /** Quantized int -> float32 (src/operator/contrib/dequantize-inl.h);
    * args: data: NDArray-or-Symbol; min_range: NDArray-or-Symbol; max_range: NDArray-or-Symbol; out_type: string, optional
    */
  def dequantize(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("dequantize", inputs, params, outputs)

  /** Matrix product (MXU path). Mirrors tensor/matrix_op dot incl. the
    * args: lhs: NDArray-or-Symbol; rhs: NDArray-or-Symbol; transpose_a: boolean, optional; transpose_b: boolean, optional
    */
  def dot(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("dot", inputs, params, outputs)

  /** elemwise_add
    * args: lhs: NDArray-or-Symbol; rhs: NDArray-or-Symbol
    */
  def elemwise_add(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("elemwise_add", inputs, params, outputs)

  /** elemwise_div
    * args: lhs: NDArray-or-Symbol; rhs: NDArray-or-Symbol
    */
  def elemwise_div(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("elemwise_div", inputs, params, outputs)

  /** elemwise_mul
    * args: lhs: NDArray-or-Symbol; rhs: NDArray-or-Symbol
    */
  def elemwise_mul(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("elemwise_mul", inputs, params, outputs)

  /** elemwise_sub
    * args: lhs: NDArray-or-Symbol; rhs: NDArray-or-Symbol
    */
  def elemwise_sub(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("elemwise_sub", inputs, params, outputs)

  /** Elementwise erf.
    * args: data: NDArray-or-Symbol
    */
  def erf(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("erf", inputs, params, outputs)

  /** Elementwise exp.
    * args: data: NDArray-or-Symbol
    */
  def exp(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("exp", inputs, params, outputs)

  /** expand_dims
    * args: data: NDArray-or-Symbol; axis: int, optional
    */
  def expand_dims(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("expand_dims", inputs, params, outputs)

  /** Elementwise expm1.
    * args: data: NDArray-or-Symbol
    */
  def expm1(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("expm1", inputs, params, outputs)

  /** FFT over the last dim; complex output interleaved [re, im] pairs
    * args: data: NDArray-or-Symbol; compute_size: int, optional
    */
  def fft(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("fft", inputs, params, outputs)

  /** lhs with lhs[i, rhs[i]] = mhs[i] (src/ndarray/ndarray.cc:771
    * args: lhs: NDArray-or-Symbol; mhs: NDArray-or-Symbol; rhs: NDArray-or-Symbol
    */
  def fill_element_0index(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("fill_element_0index", inputs, params, outputs)

  /** Elementwise fix.
    * args: data: NDArray-or-Symbol
    */
  def fix(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("fix", inputs, params, outputs)

  /** flatten
    * args: data: NDArray-or-Symbol
    */
  def flatten(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("flatten", inputs, params, outputs)

  /** flip
    * args: data: NDArray-or-Symbol; axis: Shape(tuple), optional
    */
  def flip(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("flip", inputs, params, outputs)

  /** Elementwise floor.
    * args: data: NDArray-or-Symbol
    */
  def floor(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("floor", inputs, params, outputs)

  /** Elementwise gamma.
    * args: data: NDArray-or-Symbol
    */
  def gamma(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("gamma", inputs, params, outputs)

  /** Elementwise gammaln.
    * args: data: NDArray-or-Symbol
    */
  def gammaln(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("gammaln", inputs, params, outputs)

  /** gather_nd
    * args: data: NDArray-or-Symbol; indices: NDArray-or-Symbol
    */
  def gather_nd(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("gather_nd", inputs, params, outputs)

  /** Elementwise identity.
    * args: data: NDArray-or-Symbol
    */
  def identity(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("identity", inputs, params, outputs)

  /** ifft
    * args: data: NDArray-or-Symbol; compute_size: int, optional
    */
  def ifft(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("ifft", inputs, params, outputs)

  /** linalg_gemm2
    * args: A: NDArray-or-Symbol; B: NDArray-or-Symbol; alpha: float, optional; transpose_a: boolean, optional; transpose_b: boolean, optional
    */
  def linalg_gemm2(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("linalg_gemm2", inputs, params, outputs)

  /** Elementwise log.
    * args: data: NDArray-or-Symbol
    */
  def log(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("log", inputs, params, outputs)

  /** Elementwise log10.
    * args: data: NDArray-or-Symbol
    */
  def log10(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("log10", inputs, params, outputs)

  /** Elementwise log1p.
    * args: data: NDArray-or-Symbol
    */
  def log1p(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("log1p", inputs, params, outputs)

  /** Elementwise log2.
    * args: data: NDArray-or-Symbol
    */
  def log2(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("log2", inputs, params, outputs)

  /** log_softmax
    * args: data: NDArray-or-Symbol; axis: int, optional
    */
  def log_softmax(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("log_softmax", inputs, params, outputs)

  /** Forward identity; backward seeds grad_scale (src/operator/make_loss-inl.h).
    * args: data: NDArray-or-Symbol; grad_scale: float, optional; normalization: string, optional; valid_thresh: float, optional
    */
  def make_loss(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("make_loss", inputs, params, outputs)

  /** max
    * args: data: NDArray-or-Symbol; axis: Shape(tuple), optional; keepdims: boolean, optional
    */
  def max(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("max", inputs, params, outputs)

  /** max_axis
    * args: data: NDArray-or-Symbol; axis: Shape(tuple), optional; keepdims: boolean, optional
    */
  def max_axis(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("max_axis", inputs, params, outputs)

  /** mean
    * args: data: NDArray-or-Symbol; axis: Shape(tuple), optional; keepdims: boolean, optional
    */
  def mean(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("mean", inputs, params, outputs)

  /** min
    * args: data: NDArray-or-Symbol; axis: Shape(tuple), optional; keepdims: boolean, optional
    */
  def min(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("min", inputs, params, outputs)

  /** min_axis
    * args: data: NDArray-or-Symbol; axis: Shape(tuple), optional; keepdims: boolean, optional
    */
  def min_axis(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("min_axis", inputs, params, outputs)

  /** nanprod
    * args: data: NDArray-or-Symbol; axis: Shape(tuple), optional; keepdims: boolean, optional
    */
  def nanprod(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("nanprod", inputs, params, outputs)

  /** nansum
    * args: data: NDArray-or-Symbol; axis: Shape(tuple), optional; keepdims: boolean, optional
    */
  def nansum(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("nansum", inputs, params, outputs)

  /** Elementwise negative.
    * args: data: NDArray-or-Symbol
    */
  def negative(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("negative", inputs, params, outputs)

  /** norm
    * args: data: NDArray-or-Symbol
    */
  def norm(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("norm", inputs, params, outputs)

  /** normal
    * args: dtype: string, optional; loc: float, optional; scale: float, optional; shape: Shape(tuple), optional
    */
  def normal(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("normal", inputs, params, outputs)

  /** one_hot
    * args: data: NDArray-or-Symbol; depth: int, optional; dtype: string, optional; off_value: float, optional; on_value: float, optional
    */
  def one_hot(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("one_hot", inputs, params, outputs)

  /** ones
    * args: dtype: string, optional; shape: Shape(tuple), optional
    */
  def ones(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("ones", inputs, params, outputs)

  /** ones_like
    * args: data: NDArray-or-Symbol
    */
  def ones_like(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("ones_like", inputs, params, outputs)

  /** pad
    * args: data: NDArray-or-Symbol; constant_value: float, optional; mode: string, optional; pad_width: Shape(tuple), optional
    */
  def pad(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("pad", inputs, params, outputs)

  /** Pick elements along ``axis`` by per-position indices, clip mode
    * args: data: NDArray-or-Symbol; index: NDArray-or-Symbol; axis: int, optional; keepdims: boolean, optional
    */
  def pick(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("pick", inputs, params, outputs)

  /** pow
    * args: lhs: NDArray-or-Symbol; rhs: NDArray-or-Symbol
    */
  def pow(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("pow", inputs, params, outputs)

  /** prod
    * args: data: NDArray-or-Symbol; axis: Shape(tuple), optional; keepdims: boolean, optional
    */
  def prod(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("prod", inputs, params, outputs)

  /** Affine quantization (src/operator/contrib/quantize-inl.h:29
    * args: data: NDArray-or-Symbol; min_range: NDArray-or-Symbol; max_range: NDArray-or-Symbol; out_type: string, optional
    */
  def quantize(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("quantize", inputs, params, outputs)

  /** Elementwise radians.
    * args: data: NDArray-or-Symbol
    */
  def radians(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("radians", inputs, params, outputs)

  /** random_exponential
    * args: dtype: string, optional; lam: float, optional; shape: Shape(tuple), optional
    */
  def random_exponential(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("random_exponential", inputs, params, outputs)

  /** random_gamma
    * args: alpha: float, optional; beta: float, optional; dtype: string, optional; shape: Shape(tuple), optional
    */
  def random_gamma(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("random_gamma", inputs, params, outputs)

  /** random_generalized_negative_binomial
    * args: alpha: float, optional; dtype: string, optional; mu: float, optional; shape: Shape(tuple), optional
    */
  def random_generalized_negative_binomial(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("random_generalized_negative_binomial", inputs, params, outputs)

  /** random_negative_binomial
    * args: dtype: string, optional; k: int, optional; p: float, optional; shape: Shape(tuple), optional
    */
  def random_negative_binomial(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("random_negative_binomial", inputs, params, outputs)

  /** random_normal
    * args: dtype: string, optional; loc: float, optional; scale: float, optional; shape: Shape(tuple), optional
    */
  def random_normal(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("random_normal", inputs, params, outputs)

  /** random_poisson
    * args: dtype: string, optional; lam: float, optional; shape: Shape(tuple), optional
    */
  def random_poisson(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("random_poisson", inputs, params, outputs)

  /** random_randint
    * args: dtype: string, optional; high: int, optional; low: int, optional; shape: Shape(tuple), optional
    */
  def random_randint(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("random_randint", inputs, params, outputs)

  /** random_uniform
    * args: dtype: string, optional; high: float, optional; low: float, optional; shape: Shape(tuple), optional
    */
  def random_uniform(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("random_uniform", inputs, params, outputs)

  /** Elementwise reciprocal.
    * args: data: NDArray-or-Symbol
    */
  def reciprocal(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("reciprocal", inputs, params, outputs)

  /** Elementwise relu.
    * args: data: NDArray-or-Symbol
    */
  def relu(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("relu", inputs, params, outputs)

  /** repeat
    * args: data: NDArray-or-Symbol; axis: int, optional; repeats: int, optional
    */
  def repeat(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("repeat", inputs, params, outputs)

  /** reshape
    * args: data: NDArray-or-Symbol; reverse: boolean, optional; shape: Shape(tuple), optional
    */
  def reshape(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("reshape", inputs, params, outputs)

  /** reverse
    * args: data: NDArray-or-Symbol; axis: Shape(tuple), optional
    */
  def reverse(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("reverse", inputs, params, outputs)

  /** Elementwise rint.
    * args: data: NDArray-or-Symbol
    */
  def rint(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("rint", inputs, params, outputs)

  /** rmsprop_update
    * args: weight: NDArray-or-Symbol; grad: NDArray-or-Symbol; n: NDArray-or-Symbol; clip_gradient: float, optional; clip_weights: float, optional; epsilon: float, optional; gamma1: float, optional; lr: float, optional; rescale_grad: float, optional; wd: float, optional
    */
  def rmsprop_update(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("rmsprop_update", inputs, params, outputs)

  /** Graves-form RMSProp (optimizer_op.cc rmspropalex_update).
    * args: weight: NDArray-or-Symbol; grad: NDArray-or-Symbol; n: NDArray-or-Symbol; g: NDArray-or-Symbol; delta: NDArray-or-Symbol; clip_gradient: float, optional; clip_weights: float, optional; epsilon: float, optional; gamma1: float, optional; gamma2: float, optional; lr: float, optional; rescale_grad: float, optional; wd: float, optional
    */
  def rmspropalex_update(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("rmspropalex_update", inputs, params, outputs)

  /** Elementwise round.
    * args: data: NDArray-or-Symbol
    */
  def round(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("round", inputs, params, outputs)

  /** Elementwise rsqrt.
    * args: data: NDArray-or-Symbol
    */
  def rsqrt(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("rsqrt", inputs, params, outputs)

  /** sgd_mom_update
    * args: weight: NDArray-or-Symbol; grad: NDArray-or-Symbol; mom: NDArray-or-Symbol; clip_gradient: float, optional; lr: float, optional; momentum: float, optional; rescale_grad: float, optional; wd: float, optional
    */
  def sgd_mom_update(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("sgd_mom_update", inputs, params, outputs)

  /** sgd_update
    * args: weight: NDArray-or-Symbol; grad: NDArray-or-Symbol; clip_gradient: float, optional; lr: float, optional; momentum: float, optional; rescale_grad: float, optional; wd: float, optional
    */
  def sgd_update(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("sgd_update", inputs, params, outputs)

  /** Elementwise sigmoid.
    * args: data: NDArray-or-Symbol
    */
  def sigmoid(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("sigmoid", inputs, params, outputs)

  /** Elementwise sign.
    * args: data: NDArray-or-Symbol
    */
  def sign(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("sign", inputs, params, outputs)

  /** Elementwise sin.
    * args: data: NDArray-or-Symbol
    */
  def sin(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("sin", inputs, params, outputs)

  /** Elementwise sinh.
    * args: data: NDArray-or-Symbol
    */
  def sinh(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("sinh", inputs, params, outputs)

  /** slice
    * args: data: NDArray-or-Symbol; begin: Shape(tuple), optional; end: Shape(tuple), optional
    */
  def slice(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("slice", inputs, params, outputs)

  /** slice_axis
    * args: data: NDArray-or-Symbol; axis: int, optional; begin: int, optional; end: int, optional
    */
  def slice_axis(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("slice_axis", inputs, params, outputs)

  /** smooth_l1
    * args: data: NDArray-or-Symbol; scalar: float, optional
    */
  def smooth_l1(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("smooth_l1", inputs, params, outputs)

  /** softmax
    * args: data: NDArray-or-Symbol; axis: int, optional; temperature: float, optional
    */
  def softmax(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("softmax", inputs, params, outputs)

  /** Scalar -sum(log softmax(data)[i, label_i])
    * args: data: NDArray-or-Symbol; label: NDArray-or-Symbol
    */
  def softmax_cross_entropy(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("softmax_cross_entropy", inputs, params, outputs)

  /** Elementwise softsign.
    * args: data: NDArray-or-Symbol
    */
  def softsign(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("softsign", inputs, params, outputs)

  /** sort
    * args: data: NDArray-or-Symbol; axis: int, optional; is_ascend: boolean, optional
    */
  def sort(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("sort", inputs, params, outputs)

  /** split
    * args: data: NDArray-or-Symbol; axis: int, optional; num_outputs: int, optional; squeeze_axis: boolean, optional
    */
  def split(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("split", inputs, params, outputs)

  /** Elementwise sqrt.
    * args: data: NDArray-or-Symbol
    */
  def sqrt(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("sqrt", inputs, params, outputs)

  /** Elementwise square.
    * args: data: NDArray-or-Symbol
    */
  def square(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("square", inputs, params, outputs)

  /** Identity forward, zero gradient (src/operator/tensor/elemwise_unary_op.cc
    * args: data: NDArray-or-Symbol
    */
  def stop_gradient(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("stop_gradient", inputs, params, outputs)

  /** sum
    * args: data: NDArray-or-Symbol; axis: Shape(tuple), optional; keepdims: boolean, optional
    */
  def sum(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("sum", inputs, params, outputs)

  /** sum_axis
    * args: data: NDArray-or-Symbol; axis: Shape(tuple), optional; keepdims: boolean, optional
    */
  def sum_axis(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("sum_axis", inputs, params, outputs)

  /** swapaxes
    * args: data: NDArray-or-Symbol; dim1: int, optional; dim2: int, optional
    */
  def swapaxes(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("swapaxes", inputs, params, outputs)

  /** take
    * args: a: NDArray-or-Symbol; indices: NDArray-or-Symbol; axis: int, optional; mode: string, optional
    */
  def take(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("take", inputs, params, outputs)

  /** Elementwise tan.
    * args: data: NDArray-or-Symbol
    */
  def tan(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("tan", inputs, params, outputs)

  /** Elementwise tanh.
    * args: data: NDArray-or-Symbol
    */
  def tanh(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("tanh", inputs, params, outputs)

  /** tile
    * args: data: NDArray-or-Symbol; reps: Shape(tuple), optional
    */
  def tile(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("tile", inputs, params, outputs)

  /** topk
    * args: data: NDArray-or-Symbol; axis: int, optional; is_ascend: boolean, optional; k: int, optional; ret_typ: string, optional
    */
  def topk(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("topk", inputs, params, outputs)

  /** transpose
    * args: data: NDArray-or-Symbol; axes: Shape(tuple), optional
    */
  def transpose(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("transpose", inputs, params, outputs)

  /** uniform
    * args: dtype: string, optional; high: float, optional; low: float, optional; shape: Shape(tuple), optional
    */
  def uniform(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("uniform", inputs, params, outputs)

  /** where
    * args: condition: NDArray-or-Symbol; x: NDArray-or-Symbol; y: NDArray-or-Symbol
    */
  def where(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("where", inputs, params, outputs)

  /** zeros
    * args: dtype: string, optional; shape: Shape(tuple), optional
    */
  def zeros(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("zeros", inputs, params, outputs)

  /** zeros_like
    * args: data: NDArray-or-Symbol
    */
  def zeros_like(inputs: Seq[NDArray],
      params: Map[String, String] = Map.empty,
      outputs: Seq[NDArray] = null)
      : IndexedSeq[NDArray] =
    NDArray.invoke("zeros_like", inputs, params, outputs)

}
