# Generated by tools/gen_r_ops.py from the live operator
# registry — DO NOT EDIT (reference counterpart:
# R-package/R/mxnet_generated.R). One mx.nd.<op> and one
# mx.symbol.<op> per registered operator; tensor arguments are
# positional or named, scalar attributes are named.

# shared builders ------------------------------------------------
mx.internal.gen.nd <- function(op, args, out) {
  is.nd <- vapply(args, inherits, logical(1), "MXNDArray")
  res <- mx.nd.internal.invoke(op, args[is.nd], args[!is.nd],
                               out = out)
  if (length(res) == 1) res[[1]] else res
}

mx.internal.gen.sym <- function(op, args, name) {
  do.call(mx.symbol.create, c(list(op), args, list(name = name)))
}

# relu/sigmoid/tanh/softrelu (src/operator/activation-inl.h),
#   data : NDArray-or-Symbol
#   act_type : string, optional
mx.nd.Activation <- function(..., out = NULL) mx.internal.gen.nd("Activation", list(...), out)

# Normalize over all axes but channel (axis 1). In training, use batch
#   data : NDArray-or-Symbol
#   gamma : NDArray-or-Symbol
#   beta : NDArray-or-Symbol
#   eps : float, optional
#   fix_gamma : boolean, optional
#   momentum : float, optional
#   output_mean_var : boolean, optional
#   use_global_stats : boolean, optional
mx.nd.BatchNorm <- function(..., out = NULL) mx.internal.gen.nd("BatchNorm", list(...), out)

# (src/operator/bilinear_sampler-inl.h) — gather-based bilinear warp.
#   data : NDArray-or-Symbol
#   grid : NDArray-or-Symbol
mx.nd.BilinearSampler <- function(..., out = NULL) mx.internal.gen.nd("BilinearSampler", list(...), out)
mx.symbol.BilinearSampler <- function(..., name = NULL) mx.internal.gen.sym("BilinearSampler", list(...), name)

# Identity forward, zero gradient (src/operator/tensor/elemwise_unary_op.cc
#   data : NDArray-or-Symbol
mx.nd.BlockGrad <- function(..., out = NULL) mx.internal.gen.nd("BlockGrad", list(...), out)
mx.symbol.BlockGrad <- function(..., name = NULL) mx.internal.gen.sym("BlockGrad", list(...), name)

# data (T, N, C) activations (softmax applied internally),
#   data : NDArray-or-Symbol
#   label : NDArray-or-Symbol
#   blank_label : string, optional
#   use_data_lengths : boolean, optional
#   use_label_lengths : boolean, optional
mx.nd.CTCLoss <- function(..., out = NULL) mx.internal.gen.nd("CTCLoss", list(...), out)
mx.symbol.CTCLoss <- function(..., name = NULL) mx.internal.gen.sym("CTCLoss", list(...), name)

# Cast to a new dtype (src/operator/tensor/elemwise_unary_op.cc Cast).
#   data : NDArray-or-Symbol
#   dtype : string, optional
mx.nd.Cast <- function(..., out = NULL) mx.internal.gen.nd("Cast", list(...), out)
mx.symbol.Cast <- function(..., name = NULL) mx.internal.gen.sym("Cast", list(...), name)

# Depthwise convolution along each sequence of ``seq_len`` rows,
#   data : NDArray-or-Symbol
#   weight : NDArray-or-Symbol
#   bias : NDArray-or-Symbol
#   kernel : int, required
#   no_bias : boolean, optional
#   seq_len : int, required
mx.nd.CausalConv1D <- function(..., out = NULL) mx.internal.gen.nd("CausalConv1D", list(...), out)
mx.symbol.CausalConv1D <- function(..., name = NULL) mx.internal.gen.sym("CausalConv1D", list(...), name)

# Concat
#   arg0 : NDArray-or-Symbol
#   dim : int, optional
#   num_args : int, required
mx.nd.Concat <- function(..., out = NULL) mx.internal.gen.nd("Concat", list(...), out)

# Convolution
#   data : NDArray-or-Symbol
#   weight : NDArray-or-Symbol
#   bias : NDArray-or-Symbol
#   cudnn_off : boolean, optional
#   cudnn_tune : string, optional
#   dilate : Shape(tuple), optional
#   kernel : Shape(tuple), required
#   layout : string, optional
#   no_bias : boolean, optional
#   num_filter : int, required
#   num_group : int, optional
#   pad : Shape(tuple), optional
#   stride : Shape(tuple), optional
#   workspace : int, optional
mx.nd.Convolution <- function(..., out = NULL) mx.internal.gen.nd("Convolution", list(...), out)

# Convolution_v1
#   data : NDArray-or-Symbol
#   weight : NDArray-or-Symbol
#   bias : NDArray-or-Symbol
#   cudnn_off : boolean, optional
#   cudnn_tune : string, optional
#   dilate : Shape(tuple), optional
#   kernel : Shape(tuple), required
#   layout : string, optional
#   no_bias : boolean, optional
#   num_filter : int, required
#   num_group : int, optional
#   pad : Shape(tuple), optional
#   stride : Shape(tuple), optional
#   workspace : int, optional
mx.nd.Convolution_v1 <- function(..., out = NULL) mx.internal.gen.nd("Convolution_v1", list(...), out)
mx.symbol.Convolution_v1 <- function(..., name = NULL) mx.internal.gen.sym("Convolution_v1", list(...), name)

# Displacement correlation (correlation-inl.h). kernel_size=1 path:
#   data1 : NDArray-or-Symbol
#   data2 : NDArray-or-Symbol
#   is_multiply : boolean, optional
#   kernel_size : int, optional
#   max_displacement : int, optional
#   pad_size : int, optional
#   stride1 : int, optional
#   stride2 : int, optional
mx.nd.Correlation <- function(..., out = NULL) mx.internal.gen.nd("Correlation", list(...), out)
mx.symbol.Correlation <- function(..., name = NULL) mx.internal.gen.sym("Correlation", list(...), name)

# Spatial crop (src/operator/crop-inl.h).
#   data : NDArray-or-Symbol
#   center_crop : boolean, optional
#   h_w : Shape(tuple), optional
#   num_args : int, optional
#   offset : Shape(tuple), optional
mx.nd.Crop <- function(..., out = NULL) mx.internal.gen.nd("Crop", list(...), out)
mx.symbol.Crop <- function(..., name = NULL) mx.internal.gen.sym("Crop", list(...), name)

# Normalize over all axes but channel (axis 1). In training, use batch
#   data : NDArray-or-Symbol
#   gamma : NDArray-or-Symbol
#   beta : NDArray-or-Symbol
#   eps : float, optional
#   fix_gamma : boolean, optional
#   momentum : float, optional
#   output_mean_var : boolean, optional
#   use_global_stats : boolean, optional
mx.nd.CuDNNBatchNorm <- function(..., out = NULL) mx.internal.gen.nd("CuDNNBatchNorm", list(...), out)
mx.symbol.CuDNNBatchNorm <- function(..., name = NULL) mx.internal.gen.sym("CuDNNBatchNorm", list(...), name)

# Transposed convolution = conv with lhs dilation
#   data : NDArray-or-Symbol
#   weight : NDArray-or-Symbol
#   adj : Shape(tuple), optional
#   kernel : Shape(tuple), required
#   no_bias : boolean, optional
#   num_filter : int, required
#   num_group : int, optional
#   pad : Shape(tuple), optional
#   stride : Shape(tuple), optional
#   target_shape : Shape(tuple), optional
#   workspace : int, optional
mx.nd.Deconvolution <- function(..., out = NULL) mx.internal.gen.nd("Deconvolution", list(...), out)
mx.symbol.Deconvolution <- function(..., name = NULL) mx.internal.gen.sym("Deconvolution", list(...), name)

# Dropout
#   data : NDArray-or-Symbol
#   p : float, optional
mx.nd.Dropout <- function(..., out = NULL) mx.internal.gen.nd("Dropout", list(...), out)

# Sum of N arrays in one fused op (src/ndarray/ndarray.cc:290
#   arg0 : NDArray-or-Symbol
#   num_args : int, required
mx.nd.ElementWiseSum <- function(..., out = NULL) mx.internal.gen.nd("ElementWiseSum", list(...), out)
mx.symbol.ElementWiseSum <- function(..., name = NULL) mx.internal.gen.sym("ElementWiseSum", list(...), name)

# Embedding lookup — gather from the weight table; backward is XLA
#   data : NDArray-or-Symbol
#   weight : NDArray-or-Symbol
#   input_dim : int, required
#   output_dim : int, required
mx.nd.Embedding <- function(..., out = NULL) mx.internal.gen.nd("Embedding", list(...), out)

# Flatten
#   data : NDArray-or-Symbol
mx.nd.Flatten <- function(..., out = NULL) mx.internal.gen.nd("Flatten", list(...), out)

# Y = X·Wᵀ + b. Flattens input to 2-D like the reference; the matmul is
#   data : NDArray-or-Symbol
#   weight : NDArray-or-Symbol
#   bias : NDArray-or-Symbol
#   no_bias : boolean, optional
#   num_hidden : int, required
mx.nd.FullyConnected <- function(..., out = NULL) mx.internal.gen.nd("FullyConnected", list(...), out)

# The gated delta rule (Kimi Delta Attention) over rows cut into
#   query : NDArray-or-Symbol
#   key : NDArray-or-Symbol
#   value : NDArray-or-Symbol
#   gate : NDArray-or-Symbol
#   beta : NDArray-or-Symbol
#   A_log : NDArray-or-Symbol
#   dt_bias : NDArray-or-Symbol
#   chunk : int, optional
#   head_dim : int, required
#   heads : int, required
#   seq_len : int, required
mx.nd.GatedDeltaRule <- function(..., out = NULL) mx.internal.gen.nd("GatedDeltaRule", list(...), out)
mx.symbol.GatedDeltaRule <- function(..., name = NULL) mx.internal.gen.sym("GatedDeltaRule", list(...), name)

# RMSNorm of ``data * silu(gate)`` over each of ``groups`` equal
#   data : NDArray-or-Symbol
#   gate : NDArray-or-Symbol
#   gamma : NDArray-or-Symbol
#   eps : float, optional
#   groups : int, optional
mx.nd.GatedRMSNorm <- function(..., out = NULL) mx.internal.gen.nd("GatedRMSNorm", list(...), out)
mx.symbol.GatedRMSNorm <- function(..., name = NULL) mx.internal.gen.sym("GatedRMSNorm", list(...), name)

# Affine/warp grid generation (src/operator/grid_generator-inl.h).
#   data : NDArray-or-Symbol
#   target_shape : Shape(tuple), optional
#   transform_type : string, optional
mx.nd.GridGenerator <- function(..., out = NULL) mx.internal.gen.nd("GridGenerator", list(...), out)
mx.symbol.GridGenerator <- function(..., name = NULL) mx.internal.gen.sym("GridGenerator", list(...), name)

# Causal attention of rows cut into sequences of ``seq_len``:
#   query : NDArray-or-Symbol
#   key : NDArray-or-Symbol
#   value : NDArray-or-Symbol
#   causal : boolean, optional
#   gated : boolean, optional
#   head_dim : int, required
#   num_heads : int, required
#   num_kv_heads : int, required
#   scale : float, optional
#   seq_len : int, required
#   v_head_dim : int, optional
#   window : int, optional
mx.nd.GroupedQueryAttention <- function(..., out = NULL) mx.internal.gen.nd("GroupedQueryAttention", list(...), out)
mx.symbol.GroupedQueryAttention <- function(..., name = NULL) mx.internal.gen.sym("GroupedQueryAttention", list(...), name)

# IdentityAttachKLSparseReg
#   data : NDArray-or-Symbol
#   momentum : float, optional
#   penalty : float, optional
#   sparseness_target : float, optional
mx.nd.IdentityAttachKLSparseReg <- function(..., out = NULL) mx.internal.gen.nd("IdentityAttachKLSparseReg", list(...), out)
mx.symbol.IdentityAttachKLSparseReg <- function(..., name = NULL) mx.internal.gen.sym("IdentityAttachKLSparseReg", list(...), name)

# InstanceNorm
#   data : NDArray-or-Symbol
#   gamma : NDArray-or-Symbol
#   beta : NDArray-or-Symbol
#   eps : float, optional
mx.nd.InstanceNorm <- function(..., out = NULL) mx.internal.gen.nd("InstanceNorm", list(...), out)
mx.symbol.InstanceNorm <- function(..., name = NULL) mx.internal.gen.sym("InstanceNorm", list(...), name)

# L2Normalization
#   data : NDArray-or-Symbol
#   eps : float, optional
#   mode : string, optional
mx.nd.L2Normalization <- function(..., out = NULL) mx.internal.gen.nd("L2Normalization", list(...), out)
mx.symbol.L2Normalization <- function(..., name = NULL) mx.internal.gen.sym("L2Normalization", list(...), name)

# Local response norm across channels (src/operator/lrn-inl.h).
#   data : NDArray-or-Symbol
#   alpha : float, optional
#   beta : float, optional
#   knorm : float, optional
#   nsize : int, optional
mx.nd.LRN <- function(..., out = NULL) mx.internal.gen.nd("LRN", list(...), out)

# The per-head keys and values of latent attention from the
#   data : NDArray-or-Symbol
#   shared_key : NDArray-or-Symbol
#   key_dim : int, required
#   num_heads : int, required
#   value_dim : int, required
mx.nd.LatentExpand <- function(..., out = NULL) mx.internal.gen.nd("LatentExpand", list(...), out)
mx.symbol.LatentExpand <- function(..., name = NULL) mx.internal.gen.sym("LatentExpand", list(...), name)

# leaky/prelu/elu/rrelu (src/operator/leaky_relu-inl.h).
#   data : NDArray-or-Symbol
#   act_type : string, optional
#   lower_bound : float, optional
#   slope : float, optional
#   upper_bound : float, optional
mx.nd.LeakyReLU <- function(..., out = NULL) mx.internal.gen.nd("LeakyReLU", list(...), out)

# LinearRegressionOutput
#   data : NDArray-or-Symbol
#   label : NDArray-or-Symbol
#   grad_scale : float, optional
mx.nd.LinearRegressionOutput <- function(..., out = NULL) mx.internal.gen.nd("LinearRegressionOutput", list(...), out)

# LogisticRegressionOutput
#   data : NDArray-or-Symbol
#   label : NDArray-or-Symbol
#   grad_scale : float, optional
mx.nd.LogisticRegressionOutput <- function(..., out = NULL) mx.internal.gen.nd("LogisticRegressionOutput", list(...), out)
mx.symbol.LogisticRegressionOutput <- function(..., name = NULL) mx.internal.gen.sym("LogisticRegressionOutput", list(...), name)

# MAERegressionOutput
#   data : NDArray-or-Symbol
#   label : NDArray-or-Symbol
#   grad_scale : float, optional
mx.nd.MAERegressionOutput <- function(..., out = NULL) mx.internal.gen.nd("MAERegressionOutput", list(...), out)
mx.symbol.MAERegressionOutput <- function(..., name = NULL) mx.internal.gen.sym("MAERegressionOutput", list(...), name)

# Forward identity; backward seeds grad_scale (src/operator/make_loss-inl.h).
#   data : NDArray-or-Symbol
#   grad_scale : float, optional
#   normalization : string, optional
#   valid_thresh : float, optional
mx.nd.MakeLoss <- function(..., out = NULL) mx.internal.gen.nd("MakeLoss", list(...), out)
mx.symbol.MakeLoss <- function(..., name = NULL) mx.internal.gen.sym("MakeLoss", list(...), name)

# Mixture-of-experts block, two layers on one router
#   data : NDArray-or-Symbol
#   gate_weight : NDArray-or-Symbol
#   expert1_weight : NDArray-or-Symbol
#   expert1_bias : NDArray-or-Symbol
#   expert2_weight : NDArray-or-Symbol
#   expert2_bias : NDArray-or-Symbol
#   capacity_factor : float, optional
#   expert_act : string, optional
#   experts_held : Shape(tuple), optional
#   gated : boolean, optional
#   hidden_size : int, required
#   load_balance_coeff : float, optional
#   num_experts : int, required
#   num_experts_per_tok : int, optional
#   route_norm : boolean, optional
#   route_scale : float, optional
#   score_func : string, optional
mx.nd.MoE <- function(..., out = NULL) mx.internal.gen.nd("MoE", list(...), out)
mx.symbol.MoE <- function(..., name = NULL) mx.internal.gen.sym("MoE", list(...), name)

# Pad
#   data : NDArray-or-Symbol
#   constant_value : float, optional
#   mode : string, optional
#   pad_width : Shape(tuple), optional
mx.nd.Pad <- function(..., out = NULL) mx.internal.gen.nd("Pad", list(...), out)
mx.symbol.Pad <- function(..., name = NULL) mx.internal.gen.sym("Pad", list(...), name)

# max/avg/sum pooling via lax.reduce_window (src/operator/pooling-inl.h,
#   data : NDArray-or-Symbol
#   cudnn_off : boolean, optional
#   global_pool : boolean, optional
#   kernel : Shape(tuple), optional
#   pad : Shape(tuple), optional
#   pool_type : string, optional
#   pooling_convention : string, optional
#   stride : Shape(tuple), optional
mx.nd.Pooling <- function(..., out = NULL) mx.internal.gen.nd("Pooling", list(...), out)

# max/avg/sum pooling via lax.reduce_window (src/operator/pooling-inl.h,
#   data : NDArray-or-Symbol
#   cudnn_off : boolean, optional
#   global_pool : boolean, optional
#   kernel : Shape(tuple), optional
#   pad : Shape(tuple), optional
#   pool_type : string, optional
#   pooling_convention : string, optional
#   stride : Shape(tuple), optional
mx.nd.Pooling_v1 <- function(..., out = NULL) mx.internal.gen.nd("Pooling_v1", list(...), out)
mx.symbol.Pooling_v1 <- function(..., name = NULL) mx.internal.gen.sym("Pooling_v1", list(...), name)

# RPN proposal generation (src/operator/contrib/proposal-inl.h):
#   cls_prob : NDArray-or-Symbol
#   bbox_pred : NDArray-or-Symbol
#   im_info : NDArray-or-Symbol
#   feature_stride : int, optional
#   iou_loss : boolean, optional
#   output_score : boolean, optional
#   ratios : Shape(tuple), optional
#   rpn_min_size : int, optional
#   rpn_post_nms_top_n : int, optional
#   rpn_pre_nms_top_n : int, optional
#   scales : Shape(tuple), optional
#   threshold : float, optional
mx.nd.Proposal <- function(..., out = NULL) mx.internal.gen.nd("Proposal", list(...), out)
mx.symbol.Proposal <- function(..., name = NULL) mx.internal.gen.sym("Proposal", list(...), name)

# y = x / sqrt(mean(x^2) + eps) * gamma over the last axis, or,
#   data : NDArray-or-Symbol
#   gamma : NDArray-or-Symbol
#   eps : float, optional
#   width : int, optional
mx.nd.RMSNorm <- function(..., out = NULL) mx.internal.gen.nd("RMSNorm", list(...), out)
mx.symbol.RMSNorm <- function(..., name = NULL) mx.internal.gen.sym("RMSNorm", list(...), name)

# RNN
#   data : NDArray-or-Symbol
#   parameters : NDArray-or-Symbol
#   state : NDArray-or-Symbol
#   state_cell : NDArray-or-Symbol
#   bidirectional : boolean, optional
#   lstm_state_clip_max : float, optional
#   lstm_state_clip_min : float, optional
#   mode : string, required
#   num_layers : int, required
#   p : float, optional
#   state_outputs : boolean, optional
#   state_size : int, required
mx.nd.RNN <- function(..., out = NULL) mx.internal.gen.nd("RNN", list(...), out)
mx.symbol.RNN <- function(..., name = NULL) mx.internal.gen.sym("RNN", list(...), name)

# ROI max pooling (src/operator/roi_pooling-inl.h). Computed with a
#   data : NDArray-or-Symbol
#   rois : NDArray-or-Symbol
#   pooled_size : Shape(tuple), optional
#   spatial_scale : float, optional
mx.nd.ROIPooling <- function(..., out = NULL) mx.internal.gen.nd("ROIPooling", list(...), out)
mx.symbol.ROIPooling <- function(..., name = NULL) mx.internal.gen.sym("ROIPooling", list(...), name)

# Reshape
#   data : NDArray-or-Symbol
#   reverse : boolean, optional
#   shape : Shape(tuple), optional
mx.nd.Reshape <- function(..., out = NULL) mx.internal.gen.nd("Reshape", list(...), out)

# Sequence-parallel self-attention over (B, H, T, D) inputs.
#   query : NDArray-or-Symbol
#   key : NDArray-or-Symbol
#   value : NDArray-or-Symbol
#   causal : boolean, optional
#   scale : float, optional
mx.nd.RingAttention <- function(..., out = NULL) mx.internal.gen.nd("RingAttention", list(...), out)
mx.symbol.RingAttention <- function(..., name = NULL) mx.internal.gen.sym("RingAttention", list(...), name)

# Rotary position embedding over whole heads (the two halves of a
#   data : NDArray-or-Symbol
#   head_dim : int, required
#   seq_len : int, required
#   theta : float, optional
mx.nd.RoPE <- function(..., out = NULL) mx.internal.gen.nd("RoPE", list(...), out)
mx.symbol.RoPE <- function(..., name = NULL) mx.internal.gen.sym("RoPE", list(...), name)

# The Mamba-2 selective scan over rows cut into sequences of
#   data : NDArray-or-Symbol
#   dt : NDArray-or-Symbol
#   B : NDArray-or-Symbol
#   C : NDArray-or-Symbol
#   A_log : NDArray-or-Symbol
#   dt_bias : NDArray-or-Symbol
#   D : NDArray-or-Symbol
#   chunk : int, optional
#   groups : int, required
#   head_dim : int, required
#   heads : int, required
#   seq_len : int, required
#   state : int, required
mx.nd.SSD <- function(..., out = NULL) mx.internal.gen.nd("SSD", list(...), out)
mx.symbol.SSD <- function(..., name = NULL) mx.internal.gen.sym("SSD", list(...), name)

# Hinge-loss output layer (src/operator/svm_output-inl.h).
#   data : NDArray-or-Symbol
#   label : NDArray-or-Symbol
#   margin : float, optional
#   regularization_coefficient : float, optional
#   use_linear : boolean, optional
mx.nd.SVMOutput <- function(..., out = NULL) mx.internal.gen.nd("SVMOutput", list(...), out)
mx.symbol.SVMOutput <- function(..., name = NULL) mx.internal.gen.sym("SVMOutput", list(...), name)

# SequenceLast
#   data : NDArray-or-Symbol
#   use_sequence_length : boolean, optional
mx.nd.SequenceLast <- function(..., out = NULL) mx.internal.gen.nd("SequenceLast", list(...), out)
mx.symbol.SequenceLast <- function(..., name = NULL) mx.internal.gen.sym("SequenceLast", list(...), name)

# SequenceMask
#   data : NDArray-or-Symbol
#   use_sequence_length : boolean, optional
#   value : float, optional
mx.nd.SequenceMask <- function(..., out = NULL) mx.internal.gen.nd("SequenceMask", list(...), out)
mx.symbol.SequenceMask <- function(..., name = NULL) mx.internal.gen.sym("SequenceMask", list(...), name)

# SequenceReverse
#   data : NDArray-or-Symbol
#   use_sequence_length : boolean, optional
mx.nd.SequenceReverse <- function(..., out = NULL) mx.internal.gen.nd("SequenceReverse", list(...), out)
mx.symbol.SequenceReverse <- function(..., name = NULL) mx.internal.gen.sym("SequenceReverse", list(...), name)

# SliceChannel
#   data : NDArray-or-Symbol
#   axis : int, optional
#   num_outputs : int, optional
#   squeeze_axis : boolean, optional
mx.nd.SliceChannel <- function(..., out = NULL) mx.internal.gen.nd("SliceChannel", list(...), out)
mx.symbol.SliceChannel <- function(..., name = NULL) mx.internal.gen.sym("SliceChannel", list(...), name)

# Softmax forward; backward = (p - onehot(label)) * grad_scale
#   data : NDArray-or-Symbol
#   label : NDArray-or-Symbol
#   grad_scale : float, optional
#   ignore_label : float, optional
#   multi_output : boolean, optional
#   normalization : string, optional
#   out_grad : boolean, optional
#   preserve_shape : boolean, optional
#   smooth_alpha : float, optional
#   use_ignore : boolean, optional
mx.nd.Softmax <- function(..., out = NULL) mx.internal.gen.nd("Softmax", list(...), out)
mx.symbol.Softmax <- function(..., name = NULL) mx.internal.gen.sym("Softmax", list(...), name)

# SoftmaxActivation
#   data : NDArray-or-Symbol
#   mode : string, optional
mx.nd.SoftmaxActivation <- function(..., out = NULL) mx.internal.gen.nd("SoftmaxActivation", list(...), out)
mx.symbol.SoftmaxActivation <- function(..., name = NULL) mx.internal.gen.sym("SoftmaxActivation", list(...), name)

# Softmax forward; backward = (p - onehot(label)) * grad_scale
#   data : NDArray-or-Symbol
#   label : NDArray-or-Symbol
#   grad_scale : float, optional
#   ignore_label : float, optional
#   multi_output : boolean, optional
#   normalization : string, optional
#   out_grad : boolean, optional
#   preserve_shape : boolean, optional
#   smooth_alpha : float, optional
#   use_ignore : boolean, optional
mx.nd.SoftmaxOutput <- function(..., out = NULL) mx.internal.gen.nd("SoftmaxOutput", list(...), out)

# Affine spatial transformer (src/operator/spatial_transformer-inl.h).
#   data : NDArray-or-Symbol
#   loc : NDArray-or-Symbol
#   sampler_type : string, optional
#   target_shape : Shape(tuple), optional
#   transform_type : string, optional
mx.nd.SpatialTransformer <- function(..., out = NULL) mx.internal.gen.nd("SpatialTransformer", list(...), out)
mx.symbol.SpatialTransformer <- function(..., name = NULL) mx.internal.gen.sym("SpatialTransformer", list(...), name)

# SwapAxis
#   data : NDArray-or-Symbol
#   dim1 : int, optional
#   dim2 : int, optional
mx.nd.SwapAxis <- function(..., out = NULL) mx.internal.gen.nd("SwapAxis", list(...), out)
mx.symbol.SwapAxis <- function(..., name = NULL) mx.internal.gen.sym("SwapAxis", list(...), name)

# TorchCriterion
#   data : NDArray-or-Symbol
#   label : NDArray-or-Symbol
#   grad_scale : float, optional
#   label_shape : Shape(tuple), optional
#   lua_string : string, required
mx.nd.TorchCriterion <- function(..., out = NULL) mx.internal.gen.nd("TorchCriterion", list(...), out)
mx.symbol.TorchCriterion <- function(..., name = NULL) mx.internal.gen.sym("TorchCriterion", list(...), name)

# Nearest/bilinear upsampling (src/operator/upsampling-inl.h).
#   arg0 : NDArray-or-Symbol
#   multi_input_mode : string, optional
#   num_args : int, required
#   num_filter : int, optional
#   sample_type : string, optional
#   scale : int, optional
mx.nd.UpSampling <- function(..., out = NULL) mx.internal.gen.nd("UpSampling", list(...), out)
mx.symbol.UpSampling <- function(..., name = NULL) mx.internal.gen.sym("UpSampling", list(...), name)

# WarpCTC
#   data : NDArray-or-Symbol
#   label : NDArray-or-Symbol
#   input_length : int, optional
#   label_length : int, optional
mx.nd.WarpCTC <- function(..., out = NULL) mx.internal.gen.nd("WarpCTC", list(...), out)
mx.symbol.WarpCTC <- function(..., name = NULL) mx.internal.gen.sym("WarpCTC", list(...), name)

# Elementwise abs.
#   data : NDArray-or-Symbol
mx.nd.abs <- function(..., out = NULL) mx.internal.gen.nd("abs", list(...), out)
mx.symbol.abs <- function(..., name = NULL) mx.internal.gen.sym("abs", list(...), name)

# adam_update
#   weight : NDArray-or-Symbol
#   grad : NDArray-or-Symbol
#   mean : NDArray-or-Symbol
#   var : NDArray-or-Symbol
#   beta1 : float, optional
#   beta2 : float, optional
#   clip_gradient : float, optional
#   epsilon : float, optional
#   lr : float, optional
#   rescale_grad : float, optional
#   wd : float, optional
mx.nd.adam_update <- function(..., out = NULL) mx.internal.gen.nd("adam_update", list(...), out)
mx.symbol.adam_update <- function(..., name = NULL) mx.internal.gen.sym("adam_update", list(...), name)

# Sum of N arrays in one fused op (src/ndarray/ndarray.cc:290
#   arg0 : NDArray-or-Symbol
#   num_args : int, required
mx.nd.add_n <- function(..., out = NULL) mx.internal.gen.nd("add_n", list(...), out)
mx.symbol.add_n <- function(..., name = NULL) mx.internal.gen.sym("add_n", list(...), name)

# arange_op
#   dtype : string, optional
#   repeat : int, optional
#   start : float, optional
#   step : float, optional
#   stop : float, optional
mx.nd.arange_op <- function(..., out = NULL) mx.internal.gen.nd("arange_op", list(...), out)
mx.symbol.arange_op <- function(..., name = NULL) mx.internal.gen.sym("arange_op", list(...), name)

# Elementwise arccos.
#   data : NDArray-or-Symbol
mx.nd.arccos <- function(..., out = NULL) mx.internal.gen.nd("arccos", list(...), out)
mx.symbol.arccos <- function(..., name = NULL) mx.internal.gen.sym("arccos", list(...), name)

# Elementwise arccosh.
#   data : NDArray-or-Symbol
mx.nd.arccosh <- function(..., out = NULL) mx.internal.gen.nd("arccosh", list(...), out)
mx.symbol.arccosh <- function(..., name = NULL) mx.internal.gen.sym("arccosh", list(...), name)

# Elementwise arcsin.
#   data : NDArray-or-Symbol
mx.nd.arcsin <- function(..., out = NULL) mx.internal.gen.nd("arcsin", list(...), out)
mx.symbol.arcsin <- function(..., name = NULL) mx.internal.gen.sym("arcsin", list(...), name)

# Elementwise arcsinh.
#   data : NDArray-or-Symbol
mx.nd.arcsinh <- function(..., out = NULL) mx.internal.gen.nd("arcsinh", list(...), out)
mx.symbol.arcsinh <- function(..., name = NULL) mx.internal.gen.sym("arcsinh", list(...), name)

# Elementwise arctan.
#   data : NDArray-or-Symbol
mx.nd.arctan <- function(..., out = NULL) mx.internal.gen.nd("arctan", list(...), out)
mx.symbol.arctan <- function(..., name = NULL) mx.internal.gen.sym("arctan", list(...), name)

# Elementwise arctanh.
#   data : NDArray-or-Symbol
mx.nd.arctanh <- function(..., out = NULL) mx.internal.gen.nd("arctanh", list(...), out)
mx.symbol.arctanh <- function(..., name = NULL) mx.internal.gen.sym("arctanh", list(...), name)

# argmax
#   data : NDArray-or-Symbol
#   axis : int, optional
#   keepdims : boolean, optional
mx.nd.argmax <- function(..., out = NULL) mx.internal.gen.nd("argmax", list(...), out)
mx.symbol.argmax <- function(..., name = NULL) mx.internal.gen.sym("argmax", list(...), name)

# argmax over axis 1 returning same dtype (used by Accuracy metric;
#   data : NDArray-or-Symbol
mx.nd.argmax_channel <- function(..., out = NULL) mx.internal.gen.nd("argmax_channel", list(...), out)
mx.symbol.argmax_channel <- function(..., name = NULL) mx.internal.gen.sym("argmax_channel", list(...), name)

# argmin
#   data : NDArray-or-Symbol
#   axis : int, optional
#   keepdims : boolean, optional
mx.nd.argmin <- function(..., out = NULL) mx.internal.gen.nd("argmin", list(...), out)
mx.symbol.argmin <- function(..., name = NULL) mx.internal.gen.sym("argmin", list(...), name)

# argsort
#   data : NDArray-or-Symbol
#   axis : int, optional
#   is_ascend : boolean, optional
mx.nd.argsort <- function(..., out = NULL) mx.internal.gen.nd("argsort", list(...), out)
mx.symbol.argsort <- function(..., name = NULL) mx.internal.gen.sym("argsort", list(...), name)

# batch_dot
#   lhs : NDArray-or-Symbol
#   rhs : NDArray-or-Symbol
#   transpose_a : boolean, optional
#   transpose_b : boolean, optional
mx.nd.batch_dot <- function(..., out = NULL) mx.internal.gen.nd("batch_dot", list(...), out)
mx.symbol.batch_dot <- function(..., name = NULL) mx.internal.gen.sym("batch_dot", list(...), name)

# batch_take
#   a : NDArray-or-Symbol
#   indices : NDArray-or-Symbol
mx.nd.batch_take <- function(..., out = NULL) mx.internal.gen.nd("batch_take", list(...), out)
mx.symbol.batch_take <- function(..., name = NULL) mx.internal.gen.sym("batch_take", list(...), name)

# broadcast_add
#   lhs : NDArray-or-Symbol
#   rhs : NDArray-or-Symbol
mx.nd.broadcast_add <- function(..., out = NULL) mx.internal.gen.nd("broadcast_add", list(...), out)
mx.symbol.broadcast_add <- function(..., name = NULL) mx.internal.gen.sym("broadcast_add", list(...), name)

# broadcast_axes
#   data : NDArray-or-Symbol
#   axis : Shape(tuple), optional
#   size : Shape(tuple), optional
mx.nd.broadcast_axes <- function(..., out = NULL) mx.internal.gen.nd("broadcast_axes", list(...), out)
mx.symbol.broadcast_axes <- function(..., name = NULL) mx.internal.gen.sym("broadcast_axes", list(...), name)

# broadcast_axis
#   data : NDArray-or-Symbol
#   axis : Shape(tuple), optional
#   size : Shape(tuple), optional
mx.nd.broadcast_axis <- function(..., out = NULL) mx.internal.gen.nd("broadcast_axis", list(...), out)
mx.symbol.broadcast_axis <- function(..., name = NULL) mx.internal.gen.sym("broadcast_axis", list(...), name)

# broadcast_div
#   lhs : NDArray-or-Symbol
#   rhs : NDArray-or-Symbol
mx.nd.broadcast_div <- function(..., out = NULL) mx.internal.gen.nd("broadcast_div", list(...), out)
mx.symbol.broadcast_div <- function(..., name = NULL) mx.internal.gen.sym("broadcast_div", list(...), name)

# broadcast_equal
#   lhs : NDArray-or-Symbol
#   rhs : NDArray-or-Symbol
mx.nd.broadcast_equal <- function(..., out = NULL) mx.internal.gen.nd("broadcast_equal", list(...), out)
mx.symbol.broadcast_equal <- function(..., name = NULL) mx.internal.gen.sym("broadcast_equal", list(...), name)

# broadcast_greater
#   lhs : NDArray-or-Symbol
#   rhs : NDArray-or-Symbol
mx.nd.broadcast_greater <- function(..., out = NULL) mx.internal.gen.nd("broadcast_greater", list(...), out)
mx.symbol.broadcast_greater <- function(..., name = NULL) mx.internal.gen.sym("broadcast_greater", list(...), name)

# broadcast_greater_equal
#   lhs : NDArray-or-Symbol
#   rhs : NDArray-or-Symbol
mx.nd.broadcast_greater_equal <- function(..., out = NULL) mx.internal.gen.nd("broadcast_greater_equal", list(...), out)
mx.symbol.broadcast_greater_equal <- function(..., name = NULL) mx.internal.gen.sym("broadcast_greater_equal", list(...), name)

# broadcast_hypot
#   lhs : NDArray-or-Symbol
#   rhs : NDArray-or-Symbol
mx.nd.broadcast_hypot <- function(..., out = NULL) mx.internal.gen.nd("broadcast_hypot", list(...), out)
mx.symbol.broadcast_hypot <- function(..., name = NULL) mx.internal.gen.sym("broadcast_hypot", list(...), name)

# broadcast_lesser
#   lhs : NDArray-or-Symbol
#   rhs : NDArray-or-Symbol
mx.nd.broadcast_lesser <- function(..., out = NULL) mx.internal.gen.nd("broadcast_lesser", list(...), out)
mx.symbol.broadcast_lesser <- function(..., name = NULL) mx.internal.gen.sym("broadcast_lesser", list(...), name)

# broadcast_lesser_equal
#   lhs : NDArray-or-Symbol
#   rhs : NDArray-or-Symbol
mx.nd.broadcast_lesser_equal <- function(..., out = NULL) mx.internal.gen.nd("broadcast_lesser_equal", list(...), out)
mx.symbol.broadcast_lesser_equal <- function(..., name = NULL) mx.internal.gen.sym("broadcast_lesser_equal", list(...), name)

# broadcast_maximum
#   lhs : NDArray-or-Symbol
#   rhs : NDArray-or-Symbol
mx.nd.broadcast_maximum <- function(..., out = NULL) mx.internal.gen.nd("broadcast_maximum", list(...), out)
mx.symbol.broadcast_maximum <- function(..., name = NULL) mx.internal.gen.sym("broadcast_maximum", list(...), name)

# broadcast_minimum
#   lhs : NDArray-or-Symbol
#   rhs : NDArray-or-Symbol
mx.nd.broadcast_minimum <- function(..., out = NULL) mx.internal.gen.nd("broadcast_minimum", list(...), out)
mx.symbol.broadcast_minimum <- function(..., name = NULL) mx.internal.gen.sym("broadcast_minimum", list(...), name)

# broadcast_minus
#   lhs : NDArray-or-Symbol
#   rhs : NDArray-or-Symbol
mx.nd.broadcast_minus <- function(..., out = NULL) mx.internal.gen.nd("broadcast_minus", list(...), out)
mx.symbol.broadcast_minus <- function(..., name = NULL) mx.internal.gen.sym("broadcast_minus", list(...), name)

# broadcast_mod
#   lhs : NDArray-or-Symbol
#   rhs : NDArray-or-Symbol
mx.nd.broadcast_mod <- function(..., out = NULL) mx.internal.gen.nd("broadcast_mod", list(...), out)
mx.symbol.broadcast_mod <- function(..., name = NULL) mx.internal.gen.sym("broadcast_mod", list(...), name)

# broadcast_mul
#   lhs : NDArray-or-Symbol
#   rhs : NDArray-or-Symbol
mx.nd.broadcast_mul <- function(..., out = NULL) mx.internal.gen.nd("broadcast_mul", list(...), out)
mx.symbol.broadcast_mul <- function(..., name = NULL) mx.internal.gen.sym("broadcast_mul", list(...), name)

# broadcast_not_equal
#   lhs : NDArray-or-Symbol
#   rhs : NDArray-or-Symbol
mx.nd.broadcast_not_equal <- function(..., out = NULL) mx.internal.gen.nd("broadcast_not_equal", list(...), out)
mx.symbol.broadcast_not_equal <- function(..., name = NULL) mx.internal.gen.sym("broadcast_not_equal", list(...), name)

# broadcast_plus
#   lhs : NDArray-or-Symbol
#   rhs : NDArray-or-Symbol
mx.nd.broadcast_plus <- function(..., out = NULL) mx.internal.gen.nd("broadcast_plus", list(...), out)
mx.symbol.broadcast_plus <- function(..., name = NULL) mx.internal.gen.sym("broadcast_plus", list(...), name)

# broadcast_power
#   lhs : NDArray-or-Symbol
#   rhs : NDArray-or-Symbol
mx.nd.broadcast_power <- function(..., out = NULL) mx.internal.gen.nd("broadcast_power", list(...), out)
mx.symbol.broadcast_power <- function(..., name = NULL) mx.internal.gen.sym("broadcast_power", list(...), name)

# broadcast_sub
#   lhs : NDArray-or-Symbol
#   rhs : NDArray-or-Symbol
mx.nd.broadcast_sub <- function(..., out = NULL) mx.internal.gen.nd("broadcast_sub", list(...), out)
mx.symbol.broadcast_sub <- function(..., name = NULL) mx.internal.gen.sym("broadcast_sub", list(...), name)

# broadcast_to
#   data : NDArray-or-Symbol
#   shape : Shape(tuple), optional
mx.nd.broadcast_to <- function(..., out = NULL) mx.internal.gen.nd("broadcast_to", list(...), out)
mx.symbol.broadcast_to <- function(..., name = NULL) mx.internal.gen.sym("broadcast_to", list(...), name)

# Cast to a new dtype (src/operator/tensor/elemwise_unary_op.cc Cast).
#   data : NDArray-or-Symbol
#   dtype : string, optional
mx.nd.cast <- function(..., out = NULL) mx.internal.gen.nd("cast", list(...), out)
mx.symbol.cast <- function(..., name = NULL) mx.internal.gen.sym("cast", list(...), name)

# Elementwise ceil.
#   data : NDArray-or-Symbol
mx.nd.ceil <- function(..., out = NULL) mx.internal.gen.nd("ceil", list(...), out)
mx.symbol.ceil <- function(..., name = NULL) mx.internal.gen.sym("ceil", list(...), name)

# out[i] = lhs[i, rhs[i]] (src/ndarray/ndarray.cc:765
#   lhs : NDArray-or-Symbol
#   rhs : NDArray-or-Symbol
mx.nd.choose_element_0index <- function(..., out = NULL) mx.internal.gen.nd("choose_element_0index", list(...), out)
mx.symbol.choose_element_0index <- function(..., name = NULL) mx.internal.gen.sym("choose_element_0index", list(...), name)

# Clip values to [a_min, a_max] (src/operator/tensor/matrix_op.cc clip).
#   data : NDArray-or-Symbol
#   a_max : float, optional
#   a_min : float, optional
mx.nd.clip <- function(..., out = NULL) mx.internal.gen.nd("clip", list(...), out)
mx.symbol.clip <- function(..., name = NULL) mx.internal.gen.sym("clip", list(...), name)

# concat
#   arg0 : NDArray-or-Symbol
#   dim : int, optional
#   num_args : int, required
mx.nd.concat <- function(..., out = NULL) mx.internal.gen.nd("concat", list(...), out)
mx.symbol.concat <- function(..., name = NULL) mx.internal.gen.sym("concat", list(...), name)

# Elementwise cos.
#   data : NDArray-or-Symbol
mx.nd.cos <- function(..., out = NULL) mx.internal.gen.nd("cos", list(...), out)
mx.symbol.cos <- function(..., name = NULL) mx.internal.gen.sym("cos", list(...), name)

# Elementwise cosh.
#   data : NDArray-or-Symbol
mx.nd.cosh <- function(..., out = NULL) mx.internal.gen.nd("cosh", list(...), out)
mx.symbol.cosh <- function(..., name = NULL) mx.internal.gen.sym("cosh", list(...), name)

# crop
#   data : NDArray-or-Symbol
#   begin : Shape(tuple), optional
#   end : Shape(tuple), optional
mx.nd.crop <- function(..., out = NULL) mx.internal.gen.nd("crop", list(...), out)
mx.symbol.crop <- function(..., name = NULL) mx.internal.gen.sym("crop", list(...), name)

# data (T, N, C) activations (softmax applied internally),
#   data : NDArray-or-Symbol
#   label : NDArray-or-Symbol
#   blank_label : string, optional
#   use_data_lengths : boolean, optional
#   use_label_lengths : boolean, optional
mx.nd.ctc_loss <- function(..., out = NULL) mx.internal.gen.nd("ctc_loss", list(...), out)
mx.symbol.ctc_loss <- function(..., name = NULL) mx.internal.gen.sym("ctc_loss", list(...), name)

# Elementwise degrees.
#   data : NDArray-or-Symbol
mx.nd.degrees <- function(..., out = NULL) mx.internal.gen.nd("degrees", list(...), out)
mx.symbol.degrees <- function(..., name = NULL) mx.internal.gen.sym("degrees", list(...), name)

# Quantized int -> float32 (src/operator/contrib/dequantize-inl.h);
#   data : NDArray-or-Symbol
#   min_range : NDArray-or-Symbol
#   max_range : NDArray-or-Symbol
#   out_type : string, optional
mx.nd.dequantize <- function(..., out = NULL) mx.internal.gen.nd("dequantize", list(...), out)
mx.symbol.dequantize <- function(..., name = NULL) mx.internal.gen.sym("dequantize", list(...), name)

# Matrix product (MXU path). Mirrors tensor/matrix_op dot incl. the
#   lhs : NDArray-or-Symbol
#   rhs : NDArray-or-Symbol
#   transpose_a : boolean, optional
#   transpose_b : boolean, optional
mx.nd.dot <- function(..., out = NULL) mx.internal.gen.nd("dot", list(...), out)
mx.symbol.dot <- function(..., name = NULL) mx.internal.gen.sym("dot", list(...), name)

# elemwise_add
#   lhs : NDArray-or-Symbol
#   rhs : NDArray-or-Symbol
mx.nd.elemwise_add <- function(..., out = NULL) mx.internal.gen.nd("elemwise_add", list(...), out)
mx.symbol.elemwise_add <- function(..., name = NULL) mx.internal.gen.sym("elemwise_add", list(...), name)

# elemwise_div
#   lhs : NDArray-or-Symbol
#   rhs : NDArray-or-Symbol
mx.nd.elemwise_div <- function(..., out = NULL) mx.internal.gen.nd("elemwise_div", list(...), out)
mx.symbol.elemwise_div <- function(..., name = NULL) mx.internal.gen.sym("elemwise_div", list(...), name)

# elemwise_mul
#   lhs : NDArray-or-Symbol
#   rhs : NDArray-or-Symbol
mx.nd.elemwise_mul <- function(..., out = NULL) mx.internal.gen.nd("elemwise_mul", list(...), out)
mx.symbol.elemwise_mul <- function(..., name = NULL) mx.internal.gen.sym("elemwise_mul", list(...), name)

# elemwise_sub
#   lhs : NDArray-or-Symbol
#   rhs : NDArray-or-Symbol
mx.nd.elemwise_sub <- function(..., out = NULL) mx.internal.gen.nd("elemwise_sub", list(...), out)
mx.symbol.elemwise_sub <- function(..., name = NULL) mx.internal.gen.sym("elemwise_sub", list(...), name)

# Elementwise erf.
#   data : NDArray-or-Symbol
mx.nd.erf <- function(..., out = NULL) mx.internal.gen.nd("erf", list(...), out)
mx.symbol.erf <- function(..., name = NULL) mx.internal.gen.sym("erf", list(...), name)

# Elementwise exp.
#   data : NDArray-or-Symbol
mx.nd.exp <- function(..., out = NULL) mx.internal.gen.nd("exp", list(...), out)
mx.symbol.exp <- function(..., name = NULL) mx.internal.gen.sym("exp", list(...), name)

# expand_dims
#   data : NDArray-or-Symbol
#   axis : int, optional
mx.nd.expand_dims <- function(..., out = NULL) mx.internal.gen.nd("expand_dims", list(...), out)
mx.symbol.expand_dims <- function(..., name = NULL) mx.internal.gen.sym("expand_dims", list(...), name)

# Elementwise expm1.
#   data : NDArray-or-Symbol
mx.nd.expm1 <- function(..., out = NULL) mx.internal.gen.nd("expm1", list(...), out)
mx.symbol.expm1 <- function(..., name = NULL) mx.internal.gen.sym("expm1", list(...), name)

# FFT over the last dim; complex output interleaved [re, im] pairs
#   data : NDArray-or-Symbol
#   compute_size : int, optional
mx.nd.fft <- function(..., out = NULL) mx.internal.gen.nd("fft", list(...), out)
mx.symbol.fft <- function(..., name = NULL) mx.internal.gen.sym("fft", list(...), name)

# lhs with lhs[i, rhs[i]] = mhs[i] (src/ndarray/ndarray.cc:771
#   lhs : NDArray-or-Symbol
#   mhs : NDArray-or-Symbol
#   rhs : NDArray-or-Symbol
mx.nd.fill_element_0index <- function(..., out = NULL) mx.internal.gen.nd("fill_element_0index", list(...), out)
mx.symbol.fill_element_0index <- function(..., name = NULL) mx.internal.gen.sym("fill_element_0index", list(...), name)

# Elementwise fix.
#   data : NDArray-or-Symbol
mx.nd.fix <- function(..., out = NULL) mx.internal.gen.nd("fix", list(...), out)
mx.symbol.fix <- function(..., name = NULL) mx.internal.gen.sym("fix", list(...), name)

# flatten
#   data : NDArray-or-Symbol
mx.nd.flatten <- function(..., out = NULL) mx.internal.gen.nd("flatten", list(...), out)
mx.symbol.flatten <- function(..., name = NULL) mx.internal.gen.sym("flatten", list(...), name)

# flip
#   data : NDArray-or-Symbol
#   axis : Shape(tuple), optional
mx.nd.flip <- function(..., out = NULL) mx.internal.gen.nd("flip", list(...), out)
mx.symbol.flip <- function(..., name = NULL) mx.internal.gen.sym("flip", list(...), name)

# Elementwise floor.
#   data : NDArray-or-Symbol
mx.nd.floor <- function(..., out = NULL) mx.internal.gen.nd("floor", list(...), out)
mx.symbol.floor <- function(..., name = NULL) mx.internal.gen.sym("floor", list(...), name)

# Elementwise gamma.
#   data : NDArray-or-Symbol
mx.nd.gamma <- function(..., out = NULL) mx.internal.gen.nd("gamma", list(...), out)
mx.symbol.gamma <- function(..., name = NULL) mx.internal.gen.sym("gamma", list(...), name)

# Elementwise gammaln.
#   data : NDArray-or-Symbol
mx.nd.gammaln <- function(..., out = NULL) mx.internal.gen.nd("gammaln", list(...), out)
mx.symbol.gammaln <- function(..., name = NULL) mx.internal.gen.sym("gammaln", list(...), name)

# gather_nd
#   data : NDArray-or-Symbol
#   indices : NDArray-or-Symbol
mx.nd.gather_nd <- function(..., out = NULL) mx.internal.gen.nd("gather_nd", list(...), out)
mx.symbol.gather_nd <- function(..., name = NULL) mx.internal.gen.sym("gather_nd", list(...), name)

# Elementwise identity.
#   data : NDArray-or-Symbol
mx.nd.identity <- function(..., out = NULL) mx.internal.gen.nd("identity", list(...), out)
mx.symbol.identity <- function(..., name = NULL) mx.internal.gen.sym("identity", list(...), name)

# ifft
#   data : NDArray-or-Symbol
#   compute_size : int, optional
mx.nd.ifft <- function(..., out = NULL) mx.internal.gen.nd("ifft", list(...), out)
mx.symbol.ifft <- function(..., name = NULL) mx.internal.gen.sym("ifft", list(...), name)

# linalg_gemm2
#   A : NDArray-or-Symbol
#   B : NDArray-or-Symbol
#   alpha : float, optional
#   transpose_a : boolean, optional
#   transpose_b : boolean, optional
mx.nd.linalg_gemm2 <- function(..., out = NULL) mx.internal.gen.nd("linalg_gemm2", list(...), out)
mx.symbol.linalg_gemm2 <- function(..., name = NULL) mx.internal.gen.sym("linalg_gemm2", list(...), name)

# Elementwise log.
#   data : NDArray-or-Symbol
mx.nd.log <- function(..., out = NULL) mx.internal.gen.nd("log", list(...), out)
mx.symbol.log <- function(..., name = NULL) mx.internal.gen.sym("log", list(...), name)

# Elementwise log10.
#   data : NDArray-or-Symbol
mx.nd.log10 <- function(..., out = NULL) mx.internal.gen.nd("log10", list(...), out)
mx.symbol.log10 <- function(..., name = NULL) mx.internal.gen.sym("log10", list(...), name)

# Elementwise log1p.
#   data : NDArray-or-Symbol
mx.nd.log1p <- function(..., out = NULL) mx.internal.gen.nd("log1p", list(...), out)
mx.symbol.log1p <- function(..., name = NULL) mx.internal.gen.sym("log1p", list(...), name)

# Elementwise log2.
#   data : NDArray-or-Symbol
mx.nd.log2 <- function(..., out = NULL) mx.internal.gen.nd("log2", list(...), out)
mx.symbol.log2 <- function(..., name = NULL) mx.internal.gen.sym("log2", list(...), name)

# log_softmax
#   data : NDArray-or-Symbol
#   axis : int, optional
mx.nd.log_softmax <- function(..., out = NULL) mx.internal.gen.nd("log_softmax", list(...), out)
mx.symbol.log_softmax <- function(..., name = NULL) mx.internal.gen.sym("log_softmax", list(...), name)

# Forward identity; backward seeds grad_scale (src/operator/make_loss-inl.h).
#   data : NDArray-or-Symbol
#   grad_scale : float, optional
#   normalization : string, optional
#   valid_thresh : float, optional
mx.nd.make_loss <- function(..., out = NULL) mx.internal.gen.nd("make_loss", list(...), out)
mx.symbol.make_loss <- function(..., name = NULL) mx.internal.gen.sym("make_loss", list(...), name)

# max
#   data : NDArray-or-Symbol
#   axis : Shape(tuple), optional
#   keepdims : boolean, optional
mx.nd.max <- function(..., out = NULL) mx.internal.gen.nd("max", list(...), out)
mx.symbol.max <- function(..., name = NULL) mx.internal.gen.sym("max", list(...), name)

# max_axis
#   data : NDArray-or-Symbol
#   axis : Shape(tuple), optional
#   keepdims : boolean, optional
mx.nd.max_axis <- function(..., out = NULL) mx.internal.gen.nd("max_axis", list(...), out)
mx.symbol.max_axis <- function(..., name = NULL) mx.internal.gen.sym("max_axis", list(...), name)

# mean
#   data : NDArray-or-Symbol
#   axis : Shape(tuple), optional
#   keepdims : boolean, optional
mx.nd.mean <- function(..., out = NULL) mx.internal.gen.nd("mean", list(...), out)
mx.symbol.mean <- function(..., name = NULL) mx.internal.gen.sym("mean", list(...), name)

# min
#   data : NDArray-or-Symbol
#   axis : Shape(tuple), optional
#   keepdims : boolean, optional
mx.nd.min <- function(..., out = NULL) mx.internal.gen.nd("min", list(...), out)
mx.symbol.min <- function(..., name = NULL) mx.internal.gen.sym("min", list(...), name)

# min_axis
#   data : NDArray-or-Symbol
#   axis : Shape(tuple), optional
#   keepdims : boolean, optional
mx.nd.min_axis <- function(..., out = NULL) mx.internal.gen.nd("min_axis", list(...), out)
mx.symbol.min_axis <- function(..., name = NULL) mx.internal.gen.sym("min_axis", list(...), name)

# nanprod
#   data : NDArray-or-Symbol
#   axis : Shape(tuple), optional
#   keepdims : boolean, optional
mx.nd.nanprod <- function(..., out = NULL) mx.internal.gen.nd("nanprod", list(...), out)
mx.symbol.nanprod <- function(..., name = NULL) mx.internal.gen.sym("nanprod", list(...), name)

# nansum
#   data : NDArray-or-Symbol
#   axis : Shape(tuple), optional
#   keepdims : boolean, optional
mx.nd.nansum <- function(..., out = NULL) mx.internal.gen.nd("nansum", list(...), out)
mx.symbol.nansum <- function(..., name = NULL) mx.internal.gen.sym("nansum", list(...), name)

# Elementwise negative.
#   data : NDArray-or-Symbol
mx.nd.negative <- function(..., out = NULL) mx.internal.gen.nd("negative", list(...), out)
mx.symbol.negative <- function(..., name = NULL) mx.internal.gen.sym("negative", list(...), name)

# norm
#   data : NDArray-or-Symbol
mx.nd.norm <- function(..., out = NULL) mx.internal.gen.nd("norm", list(...), out)
mx.symbol.norm <- function(..., name = NULL) mx.internal.gen.sym("norm", list(...), name)

# normal
#   dtype : string, optional
#   loc : float, optional
#   scale : float, optional
#   shape : Shape(tuple), optional
mx.nd.normal <- function(..., out = NULL) mx.internal.gen.nd("normal", list(...), out)
mx.symbol.normal <- function(..., name = NULL) mx.internal.gen.sym("normal", list(...), name)

# one_hot
#   data : NDArray-or-Symbol
#   depth : int, optional
#   dtype : string, optional
#   off_value : float, optional
#   on_value : float, optional
mx.nd.one_hot <- function(..., out = NULL) mx.internal.gen.nd("one_hot", list(...), out)
mx.symbol.one_hot <- function(..., name = NULL) mx.internal.gen.sym("one_hot", list(...), name)

# ones
#   dtype : string, optional
#   shape : Shape(tuple), optional
mx.symbol.ones <- function(..., name = NULL) mx.internal.gen.sym("ones", list(...), name)

# ones_like
#   data : NDArray-or-Symbol
mx.nd.ones_like <- function(..., out = NULL) mx.internal.gen.nd("ones_like", list(...), out)
mx.symbol.ones_like <- function(..., name = NULL) mx.internal.gen.sym("ones_like", list(...), name)

# pad
#   data : NDArray-or-Symbol
#   constant_value : float, optional
#   mode : string, optional
#   pad_width : Shape(tuple), optional
mx.nd.pad <- function(..., out = NULL) mx.internal.gen.nd("pad", list(...), out)
mx.symbol.pad <- function(..., name = NULL) mx.internal.gen.sym("pad", list(...), name)

# Pick elements along ``axis`` by per-position indices, clip mode
#   data : NDArray-or-Symbol
#   index : NDArray-or-Symbol
#   axis : int, optional
#   keepdims : boolean, optional
mx.nd.pick <- function(..., out = NULL) mx.internal.gen.nd("pick", list(...), out)
mx.symbol.pick <- function(..., name = NULL) mx.internal.gen.sym("pick", list(...), name)

# pow
#   lhs : NDArray-or-Symbol
#   rhs : NDArray-or-Symbol
mx.nd.pow <- function(..., out = NULL) mx.internal.gen.nd("pow", list(...), out)
mx.symbol.pow <- function(..., name = NULL) mx.internal.gen.sym("pow", list(...), name)

# prod
#   data : NDArray-or-Symbol
#   axis : Shape(tuple), optional
#   keepdims : boolean, optional
mx.nd.prod <- function(..., out = NULL) mx.internal.gen.nd("prod", list(...), out)
mx.symbol.prod <- function(..., name = NULL) mx.internal.gen.sym("prod", list(...), name)

# Affine quantization (src/operator/contrib/quantize-inl.h:29
#   data : NDArray-or-Symbol
#   min_range : NDArray-or-Symbol
#   max_range : NDArray-or-Symbol
#   out_type : string, optional
mx.nd.quantize <- function(..., out = NULL) mx.internal.gen.nd("quantize", list(...), out)
mx.symbol.quantize <- function(..., name = NULL) mx.internal.gen.sym("quantize", list(...), name)

# Elementwise radians.
#   data : NDArray-or-Symbol
mx.nd.radians <- function(..., out = NULL) mx.internal.gen.nd("radians", list(...), out)
mx.symbol.radians <- function(..., name = NULL) mx.internal.gen.sym("radians", list(...), name)

# random_exponential
#   dtype : string, optional
#   lam : float, optional
#   shape : Shape(tuple), optional
mx.nd.random_exponential <- function(..., out = NULL) mx.internal.gen.nd("random_exponential", list(...), out)
mx.symbol.random_exponential <- function(..., name = NULL) mx.internal.gen.sym("random_exponential", list(...), name)

# random_gamma
#   alpha : float, optional
#   beta : float, optional
#   dtype : string, optional
#   shape : Shape(tuple), optional
mx.nd.random_gamma <- function(..., out = NULL) mx.internal.gen.nd("random_gamma", list(...), out)
mx.symbol.random_gamma <- function(..., name = NULL) mx.internal.gen.sym("random_gamma", list(...), name)

# random_generalized_negative_binomial
#   alpha : float, optional
#   dtype : string, optional
#   mu : float, optional
#   shape : Shape(tuple), optional
mx.nd.random_generalized_negative_binomial <- function(..., out = NULL) mx.internal.gen.nd("random_generalized_negative_binomial", list(...), out)
mx.symbol.random_generalized_negative_binomial <- function(..., name = NULL) mx.internal.gen.sym("random_generalized_negative_binomial", list(...), name)

# random_negative_binomial
#   dtype : string, optional
#   k : int, optional
#   p : float, optional
#   shape : Shape(tuple), optional
mx.nd.random_negative_binomial <- function(..., out = NULL) mx.internal.gen.nd("random_negative_binomial", list(...), out)
mx.symbol.random_negative_binomial <- function(..., name = NULL) mx.internal.gen.sym("random_negative_binomial", list(...), name)

# random_normal
#   dtype : string, optional
#   loc : float, optional
#   scale : float, optional
#   shape : Shape(tuple), optional
mx.nd.random_normal <- function(..., out = NULL) mx.internal.gen.nd("random_normal", list(...), out)
mx.symbol.random_normal <- function(..., name = NULL) mx.internal.gen.sym("random_normal", list(...), name)

# random_poisson
#   dtype : string, optional
#   lam : float, optional
#   shape : Shape(tuple), optional
mx.nd.random_poisson <- function(..., out = NULL) mx.internal.gen.nd("random_poisson", list(...), out)
mx.symbol.random_poisson <- function(..., name = NULL) mx.internal.gen.sym("random_poisson", list(...), name)

# random_randint
#   dtype : string, optional
#   high : int, optional
#   low : int, optional
#   shape : Shape(tuple), optional
mx.nd.random_randint <- function(..., out = NULL) mx.internal.gen.nd("random_randint", list(...), out)
mx.symbol.random_randint <- function(..., name = NULL) mx.internal.gen.sym("random_randint", list(...), name)

# random_uniform
#   dtype : string, optional
#   high : float, optional
#   low : float, optional
#   shape : Shape(tuple), optional
mx.nd.random_uniform <- function(..., out = NULL) mx.internal.gen.nd("random_uniform", list(...), out)
mx.symbol.random_uniform <- function(..., name = NULL) mx.internal.gen.sym("random_uniform", list(...), name)

# Elementwise reciprocal.
#   data : NDArray-or-Symbol
mx.nd.reciprocal <- function(..., out = NULL) mx.internal.gen.nd("reciprocal", list(...), out)
mx.symbol.reciprocal <- function(..., name = NULL) mx.internal.gen.sym("reciprocal", list(...), name)

# Elementwise relu.
#   data : NDArray-or-Symbol
mx.nd.relu <- function(..., out = NULL) mx.internal.gen.nd("relu", list(...), out)
mx.symbol.relu <- function(..., name = NULL) mx.internal.gen.sym("relu", list(...), name)

# repeat
#   data : NDArray-or-Symbol
#   axis : int, optional
#   repeats : int, optional
mx.nd.repeat <- function(..., out = NULL) mx.internal.gen.nd("repeat", list(...), out)
mx.symbol.repeat <- function(..., name = NULL) mx.internal.gen.sym("repeat", list(...), name)

# reshape
#   data : NDArray-or-Symbol
#   reverse : boolean, optional
#   shape : Shape(tuple), optional
mx.symbol.reshape <- function(..., name = NULL) mx.internal.gen.sym("reshape", list(...), name)

# reverse
#   data : NDArray-or-Symbol
#   axis : Shape(tuple), optional
mx.nd.reverse <- function(..., out = NULL) mx.internal.gen.nd("reverse", list(...), out)
mx.symbol.reverse <- function(..., name = NULL) mx.internal.gen.sym("reverse", list(...), name)

# Elementwise rint.
#   data : NDArray-or-Symbol
mx.nd.rint <- function(..., out = NULL) mx.internal.gen.nd("rint", list(...), out)
mx.symbol.rint <- function(..., name = NULL) mx.internal.gen.sym("rint", list(...), name)

# rmsprop_update
#   weight : NDArray-or-Symbol
#   grad : NDArray-or-Symbol
#   n : NDArray-or-Symbol
#   clip_gradient : float, optional
#   clip_weights : float, optional
#   epsilon : float, optional
#   gamma1 : float, optional
#   lr : float, optional
#   rescale_grad : float, optional
#   wd : float, optional
mx.nd.rmsprop_update <- function(..., out = NULL) mx.internal.gen.nd("rmsprop_update", list(...), out)
mx.symbol.rmsprop_update <- function(..., name = NULL) mx.internal.gen.sym("rmsprop_update", list(...), name)

# Graves-form RMSProp (optimizer_op.cc rmspropalex_update).
#   weight : NDArray-or-Symbol
#   grad : NDArray-or-Symbol
#   n : NDArray-or-Symbol
#   g : NDArray-or-Symbol
#   delta : NDArray-or-Symbol
#   clip_gradient : float, optional
#   clip_weights : float, optional
#   epsilon : float, optional
#   gamma1 : float, optional
#   gamma2 : float, optional
#   lr : float, optional
#   rescale_grad : float, optional
#   wd : float, optional
mx.nd.rmspropalex_update <- function(..., out = NULL) mx.internal.gen.nd("rmspropalex_update", list(...), out)
mx.symbol.rmspropalex_update <- function(..., name = NULL) mx.internal.gen.sym("rmspropalex_update", list(...), name)

# Elementwise round.
#   data : NDArray-or-Symbol
mx.nd.round <- function(..., out = NULL) mx.internal.gen.nd("round", list(...), out)
mx.symbol.round <- function(..., name = NULL) mx.internal.gen.sym("round", list(...), name)

# Elementwise rsqrt.
#   data : NDArray-or-Symbol
mx.nd.rsqrt <- function(..., out = NULL) mx.internal.gen.nd("rsqrt", list(...), out)
mx.symbol.rsqrt <- function(..., name = NULL) mx.internal.gen.sym("rsqrt", list(...), name)

# sgd_mom_update
#   weight : NDArray-or-Symbol
#   grad : NDArray-or-Symbol
#   mom : NDArray-or-Symbol
#   clip_gradient : float, optional
#   lr : float, optional
#   momentum : float, optional
#   rescale_grad : float, optional
#   wd : float, optional
mx.nd.sgd_mom_update <- function(..., out = NULL) mx.internal.gen.nd("sgd_mom_update", list(...), out)
mx.symbol.sgd_mom_update <- function(..., name = NULL) mx.internal.gen.sym("sgd_mom_update", list(...), name)

# sgd_update
#   weight : NDArray-or-Symbol
#   grad : NDArray-or-Symbol
#   clip_gradient : float, optional
#   lr : float, optional
#   momentum : float, optional
#   rescale_grad : float, optional
#   wd : float, optional
mx.nd.sgd_update <- function(..., out = NULL) mx.internal.gen.nd("sgd_update", list(...), out)
mx.symbol.sgd_update <- function(..., name = NULL) mx.internal.gen.sym("sgd_update", list(...), name)

# Elementwise sigmoid.
#   data : NDArray-or-Symbol
mx.nd.sigmoid <- function(..., out = NULL) mx.internal.gen.nd("sigmoid", list(...), out)
mx.symbol.sigmoid <- function(..., name = NULL) mx.internal.gen.sym("sigmoid", list(...), name)

# Elementwise sign.
#   data : NDArray-or-Symbol
mx.nd.sign <- function(..., out = NULL) mx.internal.gen.nd("sign", list(...), out)
mx.symbol.sign <- function(..., name = NULL) mx.internal.gen.sym("sign", list(...), name)

# Elementwise sin.
#   data : NDArray-or-Symbol
mx.nd.sin <- function(..., out = NULL) mx.internal.gen.nd("sin", list(...), out)
mx.symbol.sin <- function(..., name = NULL) mx.internal.gen.sym("sin", list(...), name)

# Elementwise sinh.
#   data : NDArray-or-Symbol
mx.nd.sinh <- function(..., out = NULL) mx.internal.gen.nd("sinh", list(...), out)
mx.symbol.sinh <- function(..., name = NULL) mx.internal.gen.sym("sinh", list(...), name)

# slice
#   data : NDArray-or-Symbol
#   begin : Shape(tuple), optional
#   end : Shape(tuple), optional
mx.symbol.slice <- function(..., name = NULL) mx.internal.gen.sym("slice", list(...), name)

# slice_axis
#   data : NDArray-or-Symbol
#   axis : int, optional
#   begin : int, optional
#   end : int, optional
mx.nd.slice_axis <- function(..., out = NULL) mx.internal.gen.nd("slice_axis", list(...), out)
mx.symbol.slice_axis <- function(..., name = NULL) mx.internal.gen.sym("slice_axis", list(...), name)

# smooth_l1
#   data : NDArray-or-Symbol
#   scalar : float, optional
mx.nd.smooth_l1 <- function(..., out = NULL) mx.internal.gen.nd("smooth_l1", list(...), out)
mx.symbol.smooth_l1 <- function(..., name = NULL) mx.internal.gen.sym("smooth_l1", list(...), name)

# softmax
#   data : NDArray-or-Symbol
#   axis : int, optional
#   temperature : float, optional
mx.nd.softmax <- function(..., out = NULL) mx.internal.gen.nd("softmax", list(...), out)
mx.symbol.softmax <- function(..., name = NULL) mx.internal.gen.sym("softmax", list(...), name)

# Scalar -sum(log softmax(data)[i, label_i])
#   data : NDArray-or-Symbol
#   label : NDArray-or-Symbol
mx.nd.softmax_cross_entropy <- function(..., out = NULL) mx.internal.gen.nd("softmax_cross_entropy", list(...), out)
mx.symbol.softmax_cross_entropy <- function(..., name = NULL) mx.internal.gen.sym("softmax_cross_entropy", list(...), name)

# Elementwise softsign.
#   data : NDArray-or-Symbol
mx.nd.softsign <- function(..., out = NULL) mx.internal.gen.nd("softsign", list(...), out)
mx.symbol.softsign <- function(..., name = NULL) mx.internal.gen.sym("softsign", list(...), name)

# sort
#   data : NDArray-or-Symbol
#   axis : int, optional
#   is_ascend : boolean, optional
mx.nd.sort <- function(..., out = NULL) mx.internal.gen.nd("sort", list(...), out)
mx.symbol.sort <- function(..., name = NULL) mx.internal.gen.sym("sort", list(...), name)

# split
#   data : NDArray-or-Symbol
#   axis : int, optional
#   num_outputs : int, optional
#   squeeze_axis : boolean, optional
mx.nd.split <- function(..., out = NULL) mx.internal.gen.nd("split", list(...), out)
mx.symbol.split <- function(..., name = NULL) mx.internal.gen.sym("split", list(...), name)

# Elementwise sqrt.
#   data : NDArray-or-Symbol
mx.nd.sqrt <- function(..., out = NULL) mx.internal.gen.nd("sqrt", list(...), out)
mx.symbol.sqrt <- function(..., name = NULL) mx.internal.gen.sym("sqrt", list(...), name)

# Elementwise square.
#   data : NDArray-or-Symbol
mx.nd.square <- function(..., out = NULL) mx.internal.gen.nd("square", list(...), out)
mx.symbol.square <- function(..., name = NULL) mx.internal.gen.sym("square", list(...), name)

# Identity forward, zero gradient (src/operator/tensor/elemwise_unary_op.cc
#   data : NDArray-or-Symbol
mx.nd.stop_gradient <- function(..., out = NULL) mx.internal.gen.nd("stop_gradient", list(...), out)
mx.symbol.stop_gradient <- function(..., name = NULL) mx.internal.gen.sym("stop_gradient", list(...), name)

# sum
#   data : NDArray-or-Symbol
#   axis : Shape(tuple), optional
#   keepdims : boolean, optional
mx.nd.sum <- function(..., out = NULL) mx.internal.gen.nd("sum", list(...), out)
mx.symbol.sum <- function(..., name = NULL) mx.internal.gen.sym("sum", list(...), name)

# sum_axis
#   data : NDArray-or-Symbol
#   axis : Shape(tuple), optional
#   keepdims : boolean, optional
mx.nd.sum_axis <- function(..., out = NULL) mx.internal.gen.nd("sum_axis", list(...), out)
mx.symbol.sum_axis <- function(..., name = NULL) mx.internal.gen.sym("sum_axis", list(...), name)

# swapaxes
#   data : NDArray-or-Symbol
#   dim1 : int, optional
#   dim2 : int, optional
mx.nd.swapaxes <- function(..., out = NULL) mx.internal.gen.nd("swapaxes", list(...), out)
mx.symbol.swapaxes <- function(..., name = NULL) mx.internal.gen.sym("swapaxes", list(...), name)

# take
#   a : NDArray-or-Symbol
#   indices : NDArray-or-Symbol
#   axis : int, optional
#   mode : string, optional
mx.nd.take <- function(..., out = NULL) mx.internal.gen.nd("take", list(...), out)
mx.symbol.take <- function(..., name = NULL) mx.internal.gen.sym("take", list(...), name)

# Elementwise tan.
#   data : NDArray-or-Symbol
mx.nd.tan <- function(..., out = NULL) mx.internal.gen.nd("tan", list(...), out)
mx.symbol.tan <- function(..., name = NULL) mx.internal.gen.sym("tan", list(...), name)

# Elementwise tanh.
#   data : NDArray-or-Symbol
mx.nd.tanh <- function(..., out = NULL) mx.internal.gen.nd("tanh", list(...), out)
mx.symbol.tanh <- function(..., name = NULL) mx.internal.gen.sym("tanh", list(...), name)

# tile
#   data : NDArray-or-Symbol
#   reps : Shape(tuple), optional
mx.nd.tile <- function(..., out = NULL) mx.internal.gen.nd("tile", list(...), out)
mx.symbol.tile <- function(..., name = NULL) mx.internal.gen.sym("tile", list(...), name)

# topk
#   data : NDArray-or-Symbol
#   axis : int, optional
#   is_ascend : boolean, optional
#   k : int, optional
#   ret_typ : string, optional
mx.nd.topk <- function(..., out = NULL) mx.internal.gen.nd("topk", list(...), out)
mx.symbol.topk <- function(..., name = NULL) mx.internal.gen.sym("topk", list(...), name)

# transpose
#   data : NDArray-or-Symbol
#   axes : Shape(tuple), optional
mx.nd.transpose <- function(..., out = NULL) mx.internal.gen.nd("transpose", list(...), out)
mx.symbol.transpose <- function(..., name = NULL) mx.internal.gen.sym("transpose", list(...), name)

# uniform
#   dtype : string, optional
#   high : float, optional
#   low : float, optional
#   shape : Shape(tuple), optional
mx.nd.uniform <- function(..., out = NULL) mx.internal.gen.nd("uniform", list(...), out)
mx.symbol.uniform <- function(..., name = NULL) mx.internal.gen.sym("uniform", list(...), name)

# where
#   condition : NDArray-or-Symbol
#   x : NDArray-or-Symbol
#   y : NDArray-or-Symbol
mx.nd.where <- function(..., out = NULL) mx.internal.gen.nd("where", list(...), out)
mx.symbol.where <- function(..., name = NULL) mx.internal.gen.sym("where", list(...), name)

# zeros
#   dtype : string, optional
#   shape : Shape(tuple), optional
mx.symbol.zeros <- function(..., name = NULL) mx.internal.gen.sym("zeros", list(...), name)

# zeros_like
#   data : NDArray-or-Symbol
mx.nd.zeros_like <- function(..., out = NULL) mx.internal.gen.nd("zeros_like", list(...), out)
mx.symbol.zeros_like <- function(..., name = NULL) mx.internal.gen.sym("zeros_like", list(...), name)
