# Operator wrappers — GENERATED by scripts/gen_r_ops.py from
# MXListAllOpNames + MXSymbolGetAtomicSymbolInfo (the info tier the
# reference generates its R methods from at load time, R-package/R/zzz.R).
# Do not edit by hand; re-run the generator after registering operators.
#
# Calling convention (both namespaces):
#   - MXNDArray / MXSymbol arguments are op inputs, in positional order
#     (or keyword-composed when named);
#   - any other named argument becomes a string-typed op attribute;
#   - mx.nd.* additionally accepts out= (an NDArray or list of NDArrays)
#     for in-place ops like mx.nd.sgd_update.

mx.internal.nd.op.dispatch <- function(op, args, out, input.names = NULL) {
  ndargs <- list()
  nd_names <- character(0)
  params <- list()
  arg_names <- names(args)
  if (is.null(arg_names)) arg_names <- rep("", length(args))
  for (i in seq_along(args)) {
    v <- args[[i]]
    if (is.null(v)) next
    if (is.mx.ndarray(v)) {
      ndargs[[length(ndargs) + 1]] <- v
      nd_names <- c(nd_names, arg_names[i])
    } else if (is.list(v) && length(v) > 0 && is.mx.ndarray(v[[1]])) {
      for (a in v) {
        ndargs[[length(ndargs) + 1]] <- a
        nd_names <- c(nd_names, "")
      }
    } else {
      if (!nzchar(arg_names[i])) {
        stop(sprintf("%s: scalar arguments must be named", op))
      }
      params[[arg_names[i]]] <- v
    }
  }
  # named NDArray args map onto the op's declared input order; unnamed
  # ones fill the remaining slots positionally (symbol-path parity)
  if (any(nzchar(nd_names))) {
    if (is.null(input.names)) {
      stop(sprintf("%s: this op takes its NDArray inputs positionally",
                   op))
    }
    unknown <- setdiff(nd_names[nzchar(nd_names)], input.names)
    if (length(unknown) > 0) {
      stop(sprintf("%s: unknown input name(s) %s (inputs: %s)", op,
                   paste(unknown, collapse = ", "),
                   paste(input.names, collapse = ", ")))
    }
    slots <- vector("list", length(input.names))
    for (i in seq_along(ndargs)) {
      if (nzchar(nd_names[i])) {
        slots[[match(nd_names[i], input.names)]] <- ndargs[[i]]
      }
    }
    free <- which(vapply(slots, is.null, TRUE))
    j <- 1
    for (i in seq_along(ndargs)) {
      if (!nzchar(nd_names[i])) {
        slots[[free[j]]] <- ndargs[[i]]
        j <- j + 1
      }
    }
    ndargs <- Filter(Negate(is.null), slots)
  }
  if (!is.null(out) && !is.list(out)) out <- list(out)
  mx.nd.internal.invoke(op, ndargs, params, out)
}

#' Activation(data) with attrs act_type — registered operator, 1 output.
#' @param data Input data
#' @param act_type string, optional, default='relu'
#' @export
`mx.nd.Activation` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("Activation", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of Activation
#' @export
`mx.symbol.Activation` <- function(...) {
  mx.internal.symbol.create("Activation", list(...))
}

#' BatchNorm with running-stat update.
#' @param data Input data
#' @param gamma Input gamma
#' @param beta Input beta
#' @param moving_mean Input moving_mean
#' @param moving_var Input moving_var
#' @param __is_train__ boolean, optional, default=0
#' @param axis int, optional, default='1'
#' @param cudnn_off boolean, optional, default=0
#' @param eps float, optional, default=0.001
#' @param fix_gamma boolean, optional, default=1
#' @param momentum float, optional, default=0.9
#' @param output_mean_var boolean, optional, default=0
#' @param use_global_stats boolean, optional, default=0
#' @export
`mx.nd.BatchNorm` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("BatchNorm", list(...), out,
                             input.names = c("data", "gamma", "beta", "moving_mean", "moving_var"))
}

#' Symbolic form of BatchNorm
#' @export
`mx.symbol.BatchNorm` <- function(...) {
  mx.internal.symbol.create("BatchNorm", list(...))
}

#' BatchNorm with running-stat update.
#' @param data Input data
#' @param gamma Input gamma
#' @param beta Input beta
#' @param moving_mean Input moving_mean
#' @param moving_var Input moving_var
#' @param __is_train__ boolean, optional, default=0
#' @param axis int, optional, default='1'
#' @param cudnn_off boolean, optional, default=0
#' @param eps float, optional, default=0.001
#' @param fix_gamma boolean, optional, default=1
#' @param momentum float, optional, default=0.9
#' @param output_mean_var boolean, optional, default=0
#' @param use_global_stats boolean, optional, default=0
#' @export
`mx.nd.BatchNorm_v1` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("BatchNorm_v1", list(...), out,
                             input.names = c("data", "gamma", "beta", "moving_mean", "moving_var"))
}

#' Symbolic form of BatchNorm_v1
#' @export
`mx.symbol.BatchNorm_v1` <- function(...) {
  mx.internal.symbol.create("BatchNorm_v1", list(...))
}

#' ref: src/operator/bilinear_sampler.cc — sample data at grid coords.
#' @param data Input data
#' @param grid Input grid
#' @param cudnn_off boolean, optional, default=0
#' @export
`mx.nd.BilinearSampler` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("BilinearSampler", list(...), out,
                             input.names = c("data", "grid"))
}

#' Symbolic form of BilinearSampler
#' @export
`mx.symbol.BilinearSampler` <- function(...) {
  mx.internal.symbol.create("BilinearSampler", list(...))
}

#' BlockGrad(data) — registered operator, 1 output.
#' @param data Input data
#' @export
`mx.nd.BlockGrad` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("BlockGrad", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of BlockGrad
#' @export
`mx.symbol.BlockGrad` <- function(...) {
  mx.internal.symbol.create("BlockGrad", list(...))
}

#' CTC negative log-likelihood via log-semiring forward scan.
#' @param data Input data
#' @param label Input label
#' @param blank_label string, optional, default='last'
#' @param data_lengths string, optional, default='None'
#' @param label_lengths string, optional, default='None'
#' @param use_data_lengths boolean, optional, default=0
#' @param use_label_lengths boolean, optional, default=0
#' @export
`mx.nd.CTCLoss` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("CTCLoss", list(...), out,
                             input.names = c("data", "label"))
}

#' Symbolic form of CTCLoss
#' @export
`mx.symbol.CTCLoss` <- function(...) {
  mx.internal.symbol.create("CTCLoss", list(...))
}

#' Cast(data) with attrs dtype — registered operator, 1 output.
#' @param data Input data
#' @param dtype string, optional, default='float32'
#' @export
`mx.nd.Cast` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("Cast", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of Cast
#' @export
`mx.symbol.Cast` <- function(...) {
  mx.internal.symbol.create("Cast", list(...))
}

#' Concat(...) with attrs dim, num_args — registered operator, 1 output.
#' @param data List of input symbols
#' @param dim int, optional, default='1'
#' @param num_args string, optional, default='None'
#' @export
`mx.nd.Concat` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("Concat", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of Concat
#' @export
`mx.symbol.Concat` <- function(...) {
  mx.internal.symbol.create("Concat", list(...))
}

#' Convolution(data, weight, bias) with attrs cudnn_off, cudnn_tune, dilate, kernel, layout, no_bias, num_filter, num_group, pad, stride, workspace — registered operator, 1 output.
#' @param data Input data
#' @param weight Input weight
#' @param bias Input bias
#' @param cudnn_off boolean, optional, default=0
#' @param cudnn_tune string, optional, default='None'
#' @param dilate Shape(tuple), optional, default=()
#' @param kernel Shape(tuple), optional, default=()
#' @param layout string, optional, default='None'
#' @param no_bias boolean, optional, default=0
#' @param num_filter int, optional, default='1'
#' @param num_group int, optional, default='1'
#' @param pad Shape(tuple), optional, default=()
#' @param stride Shape(tuple), optional, default=()
#' @param workspace int, optional, default='1024'
#' @export
`mx.nd.Convolution` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("Convolution", list(...), out,
                             input.names = c("data", "weight", "bias"))
}

#' Symbolic form of Convolution
#' @export
`mx.symbol.Convolution` <- function(...) {
  mx.internal.symbol.create("Convolution", list(...))
}

#' Convolution(data, weight, bias) with attrs cudnn_off, cudnn_tune, dilate, kernel, layout, no_bias, num_filter, num_group, pad, stride, workspace — registered operator, 1 output.
#' @param data Input data
#' @param weight Input weight
#' @param bias Input bias
#' @param cudnn_off boolean, optional, default=0
#' @param cudnn_tune string, optional, default='None'
#' @param dilate Shape(tuple), optional, default=()
#' @param kernel Shape(tuple), optional, default=()
#' @param layout string, optional, default='None'
#' @param no_bias boolean, optional, default=0
#' @param num_filter int, optional, default='1'
#' @param num_group int, optional, default='1'
#' @param pad Shape(tuple), optional, default=()
#' @param stride Shape(tuple), optional, default=()
#' @param workspace int, optional, default='1024'
#' @export
`mx.nd.Convolution_v1` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("Convolution_v1", list(...), out,
                             input.names = c("data", "weight", "bias"))
}

#' Symbolic form of Convolution_v1
#' @export
`mx.symbol.Convolution_v1` <- function(...) {
  mx.internal.symbol.create("Convolution_v1", list(...))
}

#' Cost-volume correlation (ref: src/operator/correlation.cc).
#' @param data1 Input data1
#' @param data2 Input data2
#' @param is_multiply boolean, optional, default=1
#' @param kernel_size int, optional, default='1'
#' @param max_displacement int, optional, default='1'
#' @param pad_size int, optional, default='0'
#' @param stride1 int, optional, default='1'
#' @param stride2 int, optional, default='1'
#' @export
`mx.nd.Correlation` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("Correlation", list(...), out,
                             input.names = c("data1", "data2"))
}

#' Symbolic form of Correlation
#' @export
`mx.symbol.Correlation` <- function(...) {
  mx.internal.symbol.create("Correlation", list(...))
}

#' Crop(...) with attrs center_crop, h_w, num_args, offset — registered operator, 1 output.
#' @param data List of input symbols
#' @param center_crop boolean, optional, default=0
#' @param h_w Shape(tuple), optional, default=(0, 0)
#' @param num_args int, optional, default='1'
#' @param offset Shape(tuple), optional, default=(0, 0)
#' @export
`mx.nd.Crop` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("Crop", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of Crop
#' @export
`mx.symbol.Crop` <- function(...) {
  mx.internal.symbol.create("Crop", list(...))
}

#' BatchNorm with running-stat update.
#' @param data Input data
#' @param gamma Input gamma
#' @param beta Input beta
#' @param moving_mean Input moving_mean
#' @param moving_var Input moving_var
#' @param __is_train__ boolean, optional, default=0
#' @param axis int, optional, default='1'
#' @param cudnn_off boolean, optional, default=0
#' @param eps float, optional, default=0.001
#' @param fix_gamma boolean, optional, default=1
#' @param momentum float, optional, default=0.9
#' @param output_mean_var boolean, optional, default=0
#' @param use_global_stats boolean, optional, default=0
#' @export
`mx.nd.CuDNNBatchNorm` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("CuDNNBatchNorm", list(...), out,
                             input.names = c("data", "gamma", "beta", "moving_mean", "moving_var"))
}

#' Symbolic form of CuDNNBatchNorm
#' @export
`mx.symbol.CuDNNBatchNorm` <- function(...) {
  mx.internal.symbol.create("CuDNNBatchNorm", list(...))
}

#' Apply a registered custom operator (ref: mx.nd.Custom).
#' @param data List of input symbols
#' @param __is_train__ boolean, optional, default=0
#' @param op_type string, optional, default=''
#' @export
`mx.nd.Custom` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("Custom", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of Custom
#' @export
`mx.symbol.Custom` <- function(...) {
  mx.internal.symbol.create("Custom", list(...))
}

#' Transposed convolution (ref: src/operator/nn/deconvolution.cc).
#' @param data Input data
#' @param weight Input weight
#' @param bias Input bias
#' @param adj Shape(tuple), optional, default=()
#' @param cudnn_off boolean, optional, default=0
#' @param cudnn_tune string, optional, default='None'
#' @param dilate Shape(tuple), optional, default=()
#' @param kernel Shape(tuple), optional, default=()
#' @param layout string, optional, default='None'
#' @param no_bias boolean, optional, default=1
#' @param num_filter int, optional, default='1'
#' @param num_group int, optional, default='1'
#' @param pad Shape(tuple), optional, default=()
#' @param stride Shape(tuple), optional, default=()
#' @param target_shape Shape(tuple), optional, default=()
#' @param workspace int, optional, default='1024'
#' @export
`mx.nd.Deconvolution` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("Deconvolution", list(...), out,
                             input.names = c("data", "weight", "bias"))
}

#' Symbolic form of Deconvolution
#' @export
`mx.symbol.Deconvolution` <- function(...) {
  mx.internal.symbol.create("Deconvolution", list(...))
}

#' Deformable convolution v1 (ref contrib/deformable_convolution-inl.h +
#' @param data Input data
#' @param offset Input offset
#' @param weight Input weight
#' @param bias Input bias
#' @param dilate Shape(tuple), optional, default=(1, 1)
#' @param kernel Shape(tuple), optional, default=(3, 3)
#' @param layout string, optional, default='None'
#' @param no_bias boolean, optional, default=0
#' @param num_deformable_group int, optional, default='1'
#' @param num_filter int, optional, default='1'
#' @param num_group int, optional, default='1'
#' @param pad Shape(tuple), optional, default=(0, 0)
#' @param stride Shape(tuple), optional, default=(1, 1)
#' @param workspace int, optional, default='1024'
#' @export
`mx.nd.DeformableConvolution` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("DeformableConvolution", list(...), out,
                             input.names = c("data", "offset", "weight", "bias"))
}

#' Symbolic form of DeformableConvolution
#' @export
`mx.symbol.DeformableConvolution` <- function(...) {
  mx.internal.symbol.create("DeformableConvolution", list(...))
}

#' Deformable position-sensitive ROI pooling (ref
#' @param data Input data
#' @param rois Input rois
#' @param trans Input trans
#' @param group_size int, optional, default='1'
#' @param no_trans boolean, optional, default=0
#' @param output_dim int, optional, default='1'
#' @param part_size int, optional, default='0'
#' @param pooled_size int, optional, default='1'
#' @param sample_per_part int, optional, default='1'
#' @param spatial_scale float, optional, default=1
#' @param trans_std float, optional, default=0
#' @export
`mx.nd.DeformablePSROIPooling` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("DeformablePSROIPooling", list(...), out,
                             input.names = c("data", "rois", "trans"))
}

#' Symbolic form of DeformablePSROIPooling
#' @export
`mx.symbol.DeformablePSROIPooling` <- function(...) {
  mx.internal.symbol.create("DeformablePSROIPooling", list(...))
}

#' Dropout(data) with attrs __is_train__, axes, mode, p — registered operator, 2 outputs.
#' @param data Input data
#' @param __is_train__ boolean, optional, default=0
#' @param axes Shape(tuple), optional, default=()
#' @param mode string, optional, default='training'
#' @param p float, optional, default=0.5
#' @export
`mx.nd.Dropout` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("Dropout", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of Dropout
#' @export
`mx.symbol.Dropout` <- function(...) {
  mx.internal.symbol.create("Dropout", list(...))
}

#' Sum of N tensors in one op (ref src/operator/tensor/elemwise_sum.cc).
#' @param data List of input symbols
#' @export
`mx.nd.ElementWiseSum` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("ElementWiseSum", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of ElementWiseSum
#' @export
`mx.symbol.ElementWiseSum` <- function(...) {
  mx.internal.symbol.create("ElementWiseSum", list(...))
}

#' Embedding lookup (ref: src/operator/tensor/indexing_op.cc Embedding).
#' @param data Input data
#' @param weight Input weight
#' @param dtype string, optional, default='float32'
#' @param input_dim string, optional, default='None'
#' @param output_dim string, optional, default='None'
#' @param sparse_grad boolean, optional, default=0
#' @export
`mx.nd.Embedding` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("Embedding", list(...), out,
                             input.names = c("data", "weight"))
}

#' Symbolic form of Embedding
#' @export
`mx.symbol.Embedding` <- function(...) {
  mx.internal.symbol.create("Embedding", list(...))
}

#' Flatten(data) — registered operator, 1 output.
#' @param data Input data
#' @export
`mx.nd.Flatten` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("Flatten", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of Flatten
#' @export
`mx.symbol.Flatten` <- function(...) {
  mx.internal.symbol.create("Flatten", list(...))
}

#' FullyConnected(data, weight, bias) with attrs flatten, no_bias, num_hidden — registered operator, 1 output.
#' @param data Input data
#' @param weight Input weight
#' @param bias Input bias
#' @param flatten boolean, optional, default=1
#' @param no_bias boolean, optional, default=0
#' @param num_hidden int, optional, default='1'
#' @export
`mx.nd.FullyConnected` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("FullyConnected", list(...), out,
                             input.names = c("data", "weight", "bias"))
}

#' Symbolic form of FullyConnected
#' @export
`mx.symbol.FullyConnected` <- function(...) {
  mx.internal.symbol.create("FullyConnected", list(...))
}

#' Pre-activation bottleneck unit (BN-ReLU-conv ×3 + shortcut) as one
#' @param data Input data
#' @param conv1_weight Input conv1_weight
#' @param conv2_weight Input conv2_weight
#' @param conv3_weight Input conv3_weight
#' @param bn1_gamma Input bn1_gamma
#' @param bn1_beta Input bn1_beta
#' @param bn2_gamma Input bn2_gamma
#' @param bn2_beta Input bn2_beta
#' @param bn3_gamma Input bn3_gamma
#' @param bn3_beta Input bn3_beta
#' @param bn1_moving_mean Input bn1_moving_mean
#' @param bn1_moving_var Input bn1_moving_var
#' @param bn2_moving_mean Input bn2_moving_mean
#' @param bn2_moving_var Input bn2_moving_var
#' @param bn3_moving_mean Input bn3_moving_mean
#' @param bn3_moving_var Input bn3_moving_var
#' @param sc_weight Input sc_weight
#' @param __is_train__ boolean, optional, default=0
#' @param dim_match boolean, optional, default=1
#' @param eps float, optional, default=2e-05
#' @param momentum float, optional, default=0.9
#' @param num_filter int, optional, default='1'
#' @param stride int, optional, default='1'
#' @export
`mx.nd.FusedBottleneckUnit` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("FusedBottleneckUnit", list(...), out,
                             input.names = c("data", "conv1_weight", "conv2_weight", "conv3_weight", "bn1_gamma", "bn1_beta", "bn2_gamma", "bn2_beta", "bn3_gamma", "bn3_beta", "bn1_moving_mean", "bn1_moving_var", "bn2_moving_mean", "bn2_moving_var", "bn3_moving_mean", "bn3_moving_var", "sc_weight"))
}

#' Symbolic form of FusedBottleneckUnit
#' @export
`mx.symbol.FusedBottleneckUnit` <- function(...) {
  mx.internal.symbol.create("FusedBottleneckUnit", list(...))
}

#' GridGenerator(data) with attrs target_shape, transform_type — registered operator, 1 output.
#' @param data Input data
#' @param target_shape Shape(tuple), optional, default=(0, 0)
#' @param transform_type string, optional, default='affine'
#' @export
`mx.nd.GridGenerator` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("GridGenerator", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of GridGenerator
#' @export
`mx.symbol.GridGenerator` <- function(...) {
  mx.internal.symbol.create("GridGenerator", list(...))
}

#' IdentityAttachKLSparseReg(data) with attrs momentum, penalty, sparseness_target — registered operator, 1 output.
#' @param data Input data
#' @param momentum float, optional, default=0.9
#' @param penalty float, optional, default=0.001
#' @param sparseness_target float, optional, default=0.1
#' @export
`mx.nd.IdentityAttachKLSparseReg` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("IdentityAttachKLSparseReg", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of IdentityAttachKLSparseReg
#' @export
`mx.symbol.IdentityAttachKLSparseReg` <- function(...) {
  mx.internal.symbol.create("IdentityAttachKLSparseReg", list(...))
}

#' InstanceNorm(data, gamma, beta) with attrs eps — registered operator, 1 output.
#' @param data Input data
#' @param gamma Input gamma
#' @param beta Input beta
#' @param eps float, optional, default=0.001
#' @export
`mx.nd.InstanceNorm` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("InstanceNorm", list(...), out,
                             input.names = c("data", "gamma", "beta"))
}

#' Symbolic form of InstanceNorm
#' @export
`mx.symbol.InstanceNorm` <- function(...) {
  mx.internal.symbol.create("InstanceNorm", list(...))
}

#' L2Normalization(data) with attrs eps, mode — registered operator, 1 output.
#' @param data Input data
#' @param eps float, optional, default=1e-10
#' @param mode string, optional, default='instance'
#' @export
`mx.nd.L2Normalization` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("L2Normalization", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of L2Normalization
#' @export
`mx.symbol.L2Normalization` <- function(...) {
  mx.internal.symbol.create("L2Normalization", list(...))
}

#' Local response norm across channels (ref: src/operator/lrn.cc).
#' @param data Input data
#' @param alpha float, optional, default=0.0001
#' @param beta float, optional, default=0.75
#' @param knorm float, optional, default=2
#' @param nsize int, optional, default='5'
#' @export
`mx.nd.LRN` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("LRN", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of LRN
#' @export
`mx.symbol.LRN` <- function(...) {
  mx.internal.symbol.create("LRN", list(...))
}

#' LayerNorm(data, gamma, beta) with attrs axis, eps, output_mean_var — registered operator, 3 outputs.
#' @param data Input data
#' @param gamma Input gamma
#' @param beta Input beta
#' @param axis int, optional, default='-1'
#' @param eps float, optional, default=1e-05
#' @param output_mean_var boolean, optional, default=0
#' @export
`mx.nd.LayerNorm` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("LayerNorm", list(...), out,
                             input.names = c("data", "gamma", "beta"))
}

#' Symbolic form of LayerNorm
#' @export
`mx.symbol.LayerNorm` <- function(...) {
  mx.internal.symbol.create("LayerNorm", list(...))
}

#' ref: src/operator/leaky_relu.cc — leaky/prelu/elu/selu/rrelu/gelu.
#' @param data Input data
#' @param gamma Input gamma
#' @param __is_train__ boolean, optional, default=0
#' @param act_type string, optional, default='leaky'
#' @param lower_bound float, optional, default=0.125
#' @param slope float, optional, default=0.25
#' @param upper_bound float, optional, default=0.334
#' @export
`mx.nd.LeakyReLU` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("LeakyReLU", list(...), out,
                             input.names = c("data", "gamma"))
}

#' Symbolic form of LeakyReLU
#' @export
`mx.symbol.LeakyReLU` <- function(...) {
  mx.internal.symbol.create("LeakyReLU", list(...))
}

#' LinearRegressionOutput(data, label) with attrs grad_scale — registered operator, 1 output.
#' @param data Input data
#' @param label Input label
#' @param grad_scale float, optional, default=1
#' @export
`mx.nd.LinearRegressionOutput` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("LinearRegressionOutput", list(...), out,
                             input.names = c("data", "label"))
}

#' Symbolic form of LinearRegressionOutput
#' @export
`mx.symbol.LinearRegressionOutput` <- function(...) {
  mx.internal.symbol.create("LinearRegressionOutput", list(...))
}

#' LogisticRegressionOutput(data, label) with attrs grad_scale — registered operator, 1 output.
#' @param data Input data
#' @param label Input label
#' @param grad_scale float, optional, default=1
#' @export
`mx.nd.LogisticRegressionOutput` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("LogisticRegressionOutput", list(...), out,
                             input.names = c("data", "label"))
}

#' Symbolic form of LogisticRegressionOutput
#' @export
`mx.symbol.LogisticRegressionOutput` <- function(...) {
  mx.internal.symbol.create("LogisticRegressionOutput", list(...))
}

#' MAERegressionOutput(data, label) with attrs grad_scale — registered operator, 1 output.
#' @param data Input data
#' @param label Input label
#' @param grad_scale float, optional, default=1
#' @export
`mx.nd.MAERegressionOutput` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("MAERegressionOutput", list(...), out,
                             input.names = c("data", "label"))
}

#' Symbolic form of MAERegressionOutput
#' @export
`mx.symbol.MAERegressionOutput` <- function(...) {
  mx.internal.symbol.create("MAERegressionOutput", list(...))
}

#' Loss head: forward identity, backward a constant grad_scale field.
#' @param data Input data
#' @param grad_scale float, optional, default=1
#' @param normalization string, optional, default='null'
#' @param valid_thresh float, optional, default=0
#' @export
`mx.nd.MakeLoss` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("MakeLoss", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of MakeLoss
#' @export
`mx.symbol.MakeLoss` <- function(...) {
  mx.internal.symbol.create("MakeLoss", list(...))
}

#' Decode + NMS (ref: multibox_detection.cc). Vectorized XLA NMS.
#' @param cls_prob Input cls_prob
#' @param loc_pred Input loc_pred
#' @param anchor Input anchor
#' @param background_id int, optional, default='0'
#' @param clip boolean, optional, default=1
#' @param force_suppress boolean, optional, default=0
#' @param nms_threshold float, optional, default=0.5
#' @param nms_topk int, optional, default='-1'
#' @param threshold float, optional, default=0.01
#' @param variances Shape(tuple), optional, default=(0.1, 0.1, 0.2, 0.2)
#' @export
`mx.nd.MultiBoxDetection` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("MultiBoxDetection", list(...), out,
                             input.names = c("cls_prob", "loc_pred", "anchor"))
}

#' Symbolic form of MultiBoxDetection
#' @export
`mx.symbol.MultiBoxDetection` <- function(...) {
  mx.internal.symbol.create("MultiBoxDetection", list(...))
}

#' Anchor-box generation (ref: multibox_prior.cc). Pure XLA.
#' @param data Input data
#' @param clip boolean, optional, default=0
#' @param offsets Shape(tuple), optional, default=(0.5, 0.5)
#' @param ratios Shape(tuple), optional, default=(1.0,)
#' @param sizes Shape(tuple), optional, default=(1.0,)
#' @param steps Shape(tuple), optional, default=(-1.0, -1.0)
#' @export
`mx.nd.MultiBoxPrior` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("MultiBoxPrior", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of MultiBoxPrior
#' @export
`mx.symbol.MultiBoxPrior` <- function(...) {
  mx.internal.symbol.create("MultiBoxPrior", list(...))
}

#' Anchor→GT matching + box target encoding (ref: multibox_target.cc).
#' @param anchor Input anchor
#' @param label Input label
#' @param cls_pred Input cls_pred
#' @param ignore_label float, optional, default=-1
#' @param minimum_negative_samples int, optional, default='0'
#' @param negative_mining_ratio float, optional, default=-1
#' @param negative_mining_thresh float, optional, default=0.5
#' @param overlap_threshold float, optional, default=0.5
#' @param variances Shape(tuple), optional, default=(0.1, 0.1, 0.2, 0.2)
#' @export
`mx.nd.MultiBoxTarget` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("MultiBoxTarget", list(...), out,
                             input.names = c("anchor", "label", "cls_pred"))
}

#' Symbolic form of MultiBoxTarget
#' @export
`mx.symbol.MultiBoxTarget` <- function(...) {
  mx.internal.symbol.create("MultiBoxTarget", list(...))
}

#' Batched Proposal (ref contrib/multi_proposal.cc) — same math, all
#' @param cls_prob Input cls_prob
#' @param bbox_pred Input bbox_pred
#' @param im_info Input im_info
#' @param feature_stride int, optional, default='16'
#' @param iou_loss boolean, optional, default=0
#' @param output_score boolean, optional, default=0
#' @param ratios Shape(tuple), optional, default=(0.5, 1.0, 2.0)
#' @param rpn_min_size int, optional, default='16'
#' @param rpn_post_nms_top_n int, optional, default='300'
#' @param rpn_pre_nms_top_n int, optional, default='6000'
#' @param scales Shape(tuple), optional, default=(4.0, 8.0, 16.0, 32.0)
#' @param threshold float, optional, default=0.7
#' @export
`mx.nd.MultiProposal` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("MultiProposal", list(...), out,
                             input.names = c("cls_prob", "bbox_pred", "im_info"))
}

#' Symbolic form of MultiProposal
#' @export
`mx.symbol.MultiProposal` <- function(...) {
  mx.internal.symbol.create("MultiProposal", list(...))
}

#' Position-sensitive ROI average pooling (ref contrib/psroi_pooling-inl.h).
#' @param data Input data
#' @param rois Input rois
#' @param group_size int, optional, default='0'
#' @param output_dim int, optional, default='1'
#' @param pooled_size int, optional, default='1'
#' @param spatial_scale float, optional, default=1
#' @export
`mx.nd.PSROIPooling` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("PSROIPooling", list(...), out,
                             input.names = c("data", "rois"))
}

#' Symbolic form of PSROIPooling
#' @export
`mx.symbol.PSROIPooling` <- function(...) {
  mx.internal.symbol.create("PSROIPooling", list(...))
}

#' pad(data) with attrs constant_value, mode, pad_width — registered operator, 1 output.
#' @param data Input data
#' @param constant_value float, optional, default=0
#' @param mode string, optional, default='constant'
#' @param pad_width Shape(tuple), optional, default=()
#' @export
`mx.nd.Pad` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("Pad", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of Pad
#' @export
`mx.symbol.Pad` <- function(...) {
  mx.internal.symbol.create("Pad", list(...))
}

#' Pooling(data) with attrs cudnn_off, global_pool, kernel, pad, pool_type, pooling_convention, stride — registered operator, 1 output.
#' @param data Input data
#' @param cudnn_off boolean, optional, default=0
#' @param global_pool boolean, optional, default=0
#' @param kernel Shape(tuple), optional, default=()
#' @param pad Shape(tuple), optional, default=()
#' @param pool_type string, optional, default='max'
#' @param pooling_convention string, optional, default='valid'
#' @param stride Shape(tuple), optional, default=()
#' @export
`mx.nd.Pooling` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("Pooling", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of Pooling
#' @export
`mx.symbol.Pooling` <- function(...) {
  mx.internal.symbol.create("Pooling", list(...))
}

#' Pooling(data) with attrs cudnn_off, global_pool, kernel, pad, pool_type, pooling_convention, stride — registered operator, 1 output.
#' @param data Input data
#' @param cudnn_off boolean, optional, default=0
#' @param global_pool boolean, optional, default=0
#' @param kernel Shape(tuple), optional, default=()
#' @param pad Shape(tuple), optional, default=()
#' @param pool_type string, optional, default='max'
#' @param pooling_convention string, optional, default='valid'
#' @param stride Shape(tuple), optional, default=()
#' @export
`mx.nd.Pooling_v1` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("Pooling_v1", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of Pooling_v1
#' @export
`mx.symbol.Pooling_v1` <- function(...) {
  mx.internal.symbol.create("Pooling_v1", list(...))
}

#' RPN proposal generation (ref src/operator/contrib/proposal.cc).
#' @param cls_prob Input cls_prob
#' @param bbox_pred Input bbox_pred
#' @param im_info Input im_info
#' @param feature_stride int, optional, default='16'
#' @param iou_loss boolean, optional, default=0
#' @param output_score boolean, optional, default=0
#' @param ratios Shape(tuple), optional, default=(0.5, 1.0, 2.0)
#' @param rpn_min_size int, optional, default='16'
#' @param rpn_post_nms_top_n int, optional, default='300'
#' @param rpn_pre_nms_top_n int, optional, default='6000'
#' @param scales Shape(tuple), optional, default=(4.0, 8.0, 16.0, 32.0)
#' @param threshold float, optional, default=0.7
#' @export
`mx.nd.Proposal` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("Proposal", list(...), out,
                             input.names = c("cls_prob", "bbox_pred", "im_info"))
}

#' Symbolic form of Proposal
#' @export
`mx.symbol.Proposal` <- function(...) {
  mx.internal.symbol.create("Proposal", list(...))
}

#' Fused multi-layer (bi)RNN over the whole sequence.
#' @param data Input data
#' @param parameters Input parameters
#' @param state Input state
#' @param state_cell Input state_cell
#' @param __is_train__ boolean, optional, default=0
#' @param bidirectional boolean, optional, default=0
#' @param mode string, optional, default='lstm'
#' @param num_layers int, optional, default='1'
#' @param p float, optional, default=0
#' @param state_outputs boolean, optional, default=0
#' @param state_size int, optional, default='0'
#' @export
`mx.nd.RNN` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("RNN", list(...), out,
                             input.names = c("data", "parameters", "state", "state_cell"))
}

#' Symbolic form of RNN
#' @export
`mx.symbol.RNN` <- function(...) {
  mx.internal.symbol.create("RNN", list(...))
}

#' Max pooling over ROI bins (ref src/operator/roi_pooling-inl.h:51-128).
#' @param data Input data
#' @param rois Input rois
#' @param pooled_size Shape(tuple), optional, default=(1, 1)
#' @param spatial_scale float, optional, default=1
#' @export
`mx.nd.ROIPooling` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("ROIPooling", list(...), out,
                             input.names = c("data", "rois"))
}

#' Symbolic form of ROIPooling
#' @export
`mx.symbol.ROIPooling` <- function(...) {
  mx.internal.symbol.create("ROIPooling", list(...))
}

#' Reshape(data) with attrs reverse, shape — registered operator, 1 output.
#' @param data Input data
#' @param reverse boolean, optional, default=0
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd.Reshape` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("Reshape", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of Reshape
#' @export
`mx.symbol.Reshape` <- function(...) {
  mx.internal.symbol.create("Reshape", list(...))
}

#' SVMOutput(data, label) with attrs margin, regularization_coefficient, use_linear — registered operator, 1 output.
#' @param data Input data
#' @param label Input label
#' @param margin float, optional, default=1
#' @param regularization_coefficient float, optional, default=1
#' @param use_linear boolean, optional, default=0
#' @export
`mx.nd.SVMOutput` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("SVMOutput", list(...), out,
                             input.names = c("data", "label"))
}

#' Symbolic form of SVMOutput
#' @export
`mx.symbol.SVMOutput` <- function(...) {
  mx.internal.symbol.create("SVMOutput", list(...))
}

#' SequenceLast(data, sequence_length) with attrs axis, use_sequence_length — registered operator, 1 output.
#' @param data Input data
#' @param sequence_length Input sequence_length
#' @param axis int, optional, default='0'
#' @param use_sequence_length boolean, optional, default=0
#' @export
`mx.nd.SequenceLast` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("SequenceLast", list(...), out,
                             input.names = c("data", "sequence_length"))
}

#' Symbolic form of SequenceLast
#' @export
`mx.symbol.SequenceLast` <- function(...) {
  mx.internal.symbol.create("SequenceLast", list(...))
}

#' SequenceMask(data, sequence_length) with attrs axis, use_sequence_length, value — registered operator, 1 output.
#' @param data Input data
#' @param sequence_length Input sequence_length
#' @param axis int, optional, default='0'
#' @param use_sequence_length boolean, optional, default=0
#' @param value float, optional, default=0
#' @export
`mx.nd.SequenceMask` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("SequenceMask", list(...), out,
                             input.names = c("data", "sequence_length"))
}

#' Symbolic form of SequenceMask
#' @export
`mx.symbol.SequenceMask` <- function(...) {
  mx.internal.symbol.create("SequenceMask", list(...))
}

#' SequenceReverse(data, sequence_length) with attrs axis, use_sequence_length — registered operator, 1 output.
#' @param data Input data
#' @param sequence_length Input sequence_length
#' @param axis int, optional, default='0'
#' @param use_sequence_length boolean, optional, default=0
#' @export
`mx.nd.SequenceReverse` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("SequenceReverse", list(...), out,
                             input.names = c("data", "sequence_length"))
}

#' Symbolic form of SequenceReverse
#' @export
`mx.symbol.SequenceReverse` <- function(...) {
  mx.internal.symbol.create("SequenceReverse", list(...))
}

#' SliceChannel(data) with attrs axis, num_outputs, squeeze_axis — registered operator, 1 output.
#' @param data Input data
#' @param axis int, optional, default='1'
#' @param num_outputs int, optional, default='1'
#' @param squeeze_axis boolean, optional, default=0
#' @export
`mx.nd.SliceChannel` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("SliceChannel", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of SliceChannel
#' @export
`mx.symbol.SliceChannel` <- function(...) {
  mx.internal.symbol.create("SliceChannel", list(...))
}

#' Fused softmax + CE-grad head (ref: src/operator/softmax_output.cc).
#' @param data Input data
#' @param label Input label
#' @param grad_scale float, optional, default=1
#' @param ignore_label float, optional, default=-1
#' @param multi_output boolean, optional, default=0
#' @param normalization string, optional, default='null'
#' @param out_grad boolean, optional, default=0
#' @param preserve_shape boolean, optional, default=0
#' @param smooth_alpha float, optional, default=0
#' @param use_ignore boolean, optional, default=0
#' @export
`mx.nd.Softmax` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("Softmax", list(...), out,
                             input.names = c("data", "label"))
}

#' Symbolic form of Softmax
#' @export
`mx.symbol.Softmax` <- function(...) {
  mx.internal.symbol.create("Softmax", list(...))
}

#' SoftmaxActivation(data) with attrs mode — registered operator, 1 output.
#' @param data Input data
#' @param mode string, optional, default='instance'
#' @export
`mx.nd.SoftmaxActivation` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("SoftmaxActivation", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of SoftmaxActivation
#' @export
`mx.symbol.SoftmaxActivation` <- function(...) {
  mx.internal.symbol.create("SoftmaxActivation", list(...))
}

#' Fused softmax + CE-grad head (ref: src/operator/softmax_output.cc).
#' @param data Input data
#' @param label Input label
#' @param grad_scale float, optional, default=1
#' @param ignore_label float, optional, default=-1
#' @param multi_output boolean, optional, default=0
#' @param normalization string, optional, default='null'
#' @param out_grad boolean, optional, default=0
#' @param preserve_shape boolean, optional, default=0
#' @param smooth_alpha float, optional, default=0
#' @param use_ignore boolean, optional, default=0
#' @export
`mx.nd.SoftmaxOutput` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("SoftmaxOutput", list(...), out,
                             input.names = c("data", "label"))
}

#' Symbolic form of SoftmaxOutput
#' @export
`mx.symbol.SoftmaxOutput` <- function(...) {
  mx.internal.symbol.create("SoftmaxOutput", list(...))
}

#' SpatialTransformer(data, loc) with attrs cudnn_off, sampler_type, target_shape, transform_type — registered operator, 1 output.
#' @param data Input data
#' @param loc Input loc
#' @param cudnn_off boolean, optional, default=0
#' @param sampler_type string, optional, default='bilinear'
#' @param target_shape Shape(tuple), optional, default=(0, 0)
#' @param transform_type string, optional, default='affine'
#' @export
`mx.nd.SpatialTransformer` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("SpatialTransformer", list(...), out,
                             input.names = c("data", "loc"))
}

#' Symbolic form of SpatialTransformer
#' @export
`mx.symbol.SpatialTransformer` <- function(...) {
  mx.internal.symbol.create("SpatialTransformer", list(...))
}

#' swapaxes(data) with attrs dim1, dim2 — registered operator, 1 output.
#' @param data Input data
#' @param dim1 int, optional, default='0'
#' @param dim2 int, optional, default='0'
#' @export
`mx.nd.SwapAxis` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("SwapAxis", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of SwapAxis
#' @export
`mx.symbol.SwapAxis` <- function(...) {
  mx.internal.symbol.create("SwapAxis", list(...))
}

#' UpSampling(...) with attrs multi_input_mode, num_args, num_filter, sample_type, scale, workspace — registered operator, 1 output.
#' @param data List of input symbols
#' @param multi_input_mode string, optional, default='concat'
#' @param num_args int, optional, default='1'
#' @param num_filter int, optional, default='0'
#' @param sample_type string, optional, default='nearest'
#' @param scale int, optional, default='1'
#' @param workspace int, optional, default='512'
#' @export
`mx.nd.UpSampling` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("UpSampling", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of UpSampling
#' @export
`mx.symbol.UpSampling` <- function(...) {
  mx.internal.symbol.create("UpSampling", list(...))
}

#' Convolution with the residual add fused into its epilogue.
#' @param data Input data
#' @param weight Input weight
#' @param residual Input residual
#' @param bias Input bias
#' @param dilate Shape(tuple), optional, default=()
#' @param kernel Shape(tuple), optional, default=()
#' @param layout string, optional, default='None'
#' @param no_bias boolean, optional, default=0
#' @param num_filter int, optional, default='1'
#' @param num_group int, optional, default='1'
#' @param pad Shape(tuple), optional, default=()
#' @param stride Shape(tuple), optional, default=()
#' @param workspace int, optional, default='1024'
#' @export
`mx.nd._ConvResidualAdd` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_ConvResidualAdd", list(...), out,
                             input.names = c("data", "weight", "residual", "bias"))
}

#' Symbolic form of _ConvResidualAdd
#' @export
`mx.symbol._ConvResidualAdd` <- function(...) {
  mx.internal.symbol.create("_ConvResidualAdd", list(...))
}

#' Placement is XLA/pjit's job (ref PlaceDevice pass inserts these,
#' @param data Input data
#' @export
`mx.nd._CrossDeviceCopy` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_CrossDeviceCopy", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _CrossDeviceCopy
#' @export
`mx.symbol._CrossDeviceCopy` <- function(...) {
  mx.internal.symbol.create("_CrossDeviceCopy", list(...))
}

#' broadcast_div(lhs, rhs) — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @export
`mx.nd._Div` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_Div", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of _Div
#' @export
`mx.symbol._Div` <- function(...) {
  mx.internal.symbol.create("_Div", list(...))
}

#' _div_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param scalar float, optional, default=0
#' @export
`mx.nd._DivScalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_DivScalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _DivScalar
#' @export
`mx.symbol._DivScalar` <- function(...) {
  mx.internal.symbol.create("_DivScalar", list(...))
}

#' Return element-wise maximum of the input arrays.
#' @param data List of input symbols
#' @param out string, optional, default='None'
#' @param where string, optional, default='None'
#' @export
`mx.nd._Maximum` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_Maximum", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of _Maximum
#' @export
`mx.symbol._Maximum` <- function(...) {
  mx.internal.symbol.create("_Maximum", list(...))
}

#' _maximum_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param scalar float, optional, default=0
#' @export
`mx.nd._MaximumScalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_MaximumScalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _MaximumScalar
#' @export
`mx.symbol._MaximumScalar` <- function(...) {
  mx.internal.symbol.create("_MaximumScalar", list(...))
}

#' Return element-wise minimum of the input arrays.
#' @param data List of input symbols
#' @param out string, optional, default='None'
#' @param where string, optional, default='None'
#' @export
`mx.nd._Minimum` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_Minimum", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of _Minimum
#' @export
`mx.symbol._Minimum` <- function(...) {
  mx.internal.symbol.create("_Minimum", list(...))
}

#' _minimum_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param scalar float, optional, default=0
#' @export
`mx.nd._MinimumScalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_MinimumScalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _MinimumScalar
#' @export
`mx.symbol._MinimumScalar` <- function(...) {
  mx.internal.symbol.create("_MinimumScalar", list(...))
}

#' broadcast_sub(lhs, rhs) — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @export
`mx.nd._Minus` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_Minus", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of _Minus
#' @export
`mx.symbol._Minus` <- function(...) {
  mx.internal.symbol.create("_Minus", list(...))
}

#' _minus_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param scalar float, optional, default=0
#' @export
`mx.nd._MinusScalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_MinusScalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _MinusScalar
#' @export
`mx.symbol._MinusScalar` <- function(...) {
  mx.internal.symbol.create("_MinusScalar", list(...))
}

#' broadcast_mul(lhs, rhs) — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @export
`mx.nd._Mul` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_Mul", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of _Mul
#' @export
`mx.symbol._Mul` <- function(...) {
  mx.internal.symbol.create("_Mul", list(...))
}

#' _mul_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param scalar float, optional, default=0
#' @export
`mx.nd._MulScalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_MulScalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _MulScalar
#' @export
`mx.symbol._MulScalar` <- function(...) {
  mx.internal.symbol.create("_MulScalar", list(...))
}

#' broadcast_add(lhs, rhs) — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @export
`mx.nd._Plus` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_Plus", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of _Plus
#' @export
`mx.symbol._Plus` <- function(...) {
  mx.internal.symbol.create("_Plus", list(...))
}

#' _plus_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param scalar float, optional, default=0
#' @export
`mx.nd._PlusScalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_PlusScalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _PlusScalar
#' @export
`mx.symbol._PlusScalar` <- function(...) {
  mx.internal.symbol.create("_PlusScalar", list(...))
}

#' broadcast_power(lhs, rhs) — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @export
`mx.nd._Power` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_Power", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of _Power
#' @export
`mx.symbol._Power` <- function(...) {
  mx.internal.symbol.create("_Power", list(...))
}

#' _power_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param scalar float, optional, default=0
#' @export
`mx.nd._PowerScalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_PowerScalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _PowerScalar
#' @export
`mx.symbol._PowerScalar` <- function(...) {
  mx.internal.symbol.create("_PowerScalar", list(...))
}

#' _rdiv_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param scalar float, optional, default=0
#' @export
`mx.nd._RDivScalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_RDivScalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _RDivScalar
#' @export
`mx.symbol._RDivScalar` <- function(...) {
  mx.internal.symbol.create("_RDivScalar", list(...))
}

#' _rminus_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param scalar float, optional, default=0
#' @export
`mx.nd._RMinusScalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_RMinusScalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _RMinusScalar
#' @export
`mx.symbol._RMinusScalar` <- function(...) {
  mx.internal.symbol.create("_RMinusScalar", list(...))
}

#' _rpower_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param scalar float, optional, default=0
#' @export
`mx.nd._RPowerScalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_RPowerScalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _RPowerScalar
#' @export
`mx.symbol._RPowerScalar` <- function(...) {
  mx.internal.symbol.create("_RPowerScalar", list(...))
}

#' broadcast_add(lhs, rhs) — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @export
`mx.nd._add` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_add", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of _add
#' @export
`mx.symbol._add` <- function(...) {
  mx.internal.symbol.create("_add", list(...))
}

#' _arange(...) with attrs ctx, dtype, infer_range, repeat, start, step, stop — registered operator, 1 output.
#' @param ctx string, optional, default='None'
#' @param dtype string, optional, default='float32'
#' @param infer_range boolean, optional, default=0
#' @param repeat int, optional, default='1'
#' @param start float, optional, default=0
#' @param step float, optional, default=1
#' @param stop string, optional, default='None'
#' @export
`mx.nd._arange` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_arange", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of _arange
#' @export
`mx.symbol._arange` <- function(...) {
  mx.internal.symbol.create("_arange", list(...))
}

#' CTC negative log-likelihood via log-semiring forward scan.
#' @param data Input data
#' @param label Input label
#' @param blank_label string, optional, default='last'
#' @param data_lengths string, optional, default='None'
#' @param label_lengths string, optional, default='None'
#' @param use_data_lengths boolean, optional, default=0
#' @param use_label_lengths boolean, optional, default=0
#' @export
`mx.nd._contrib_CTCLoss` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_contrib_CTCLoss", list(...), out,
                             input.names = c("data", "label"))
}

#' Symbolic form of _contrib_CTCLoss
#' @export
`mx.symbol._contrib_CTCLoss` <- function(...) {
  mx.internal.symbol.create("_contrib_CTCLoss", list(...))
}

#' Deformable convolution v1 (ref contrib/deformable_convolution-inl.h +
#' @param data Input data
#' @param offset Input offset
#' @param weight Input weight
#' @param bias Input bias
#' @param dilate Shape(tuple), optional, default=(1, 1)
#' @param kernel Shape(tuple), optional, default=(3, 3)
#' @param layout string, optional, default='None'
#' @param no_bias boolean, optional, default=0
#' @param num_deformable_group int, optional, default='1'
#' @param num_filter int, optional, default='1'
#' @param num_group int, optional, default='1'
#' @param pad Shape(tuple), optional, default=(0, 0)
#' @param stride Shape(tuple), optional, default=(1, 1)
#' @param workspace int, optional, default='1024'
#' @export
`mx.nd._contrib_DeformableConvolution` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_contrib_DeformableConvolution", list(...), out,
                             input.names = c("data", "offset", "weight", "bias"))
}

#' Symbolic form of _contrib_DeformableConvolution
#' @export
`mx.symbol._contrib_DeformableConvolution` <- function(...) {
  mx.internal.symbol.create("_contrib_DeformableConvolution", list(...))
}

#' Deformable position-sensitive ROI pooling (ref
#' @param data Input data
#' @param rois Input rois
#' @param trans Input trans
#' @param group_size int, optional, default='1'
#' @param no_trans boolean, optional, default=0
#' @param output_dim int, optional, default='1'
#' @param part_size int, optional, default='0'
#' @param pooled_size int, optional, default='1'
#' @param sample_per_part int, optional, default='1'
#' @param spatial_scale float, optional, default=1
#' @param trans_std float, optional, default=0
#' @export
`mx.nd._contrib_DeformablePSROIPooling` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_contrib_DeformablePSROIPooling", list(...), out,
                             input.names = c("data", "rois", "trans"))
}

#' Symbolic form of _contrib_DeformablePSROIPooling
#' @export
`mx.symbol._contrib_DeformablePSROIPooling` <- function(...) {
  mx.internal.symbol.create("_contrib_DeformablePSROIPooling", list(...))
}

#' Decode + NMS (ref: multibox_detection.cc). Vectorized XLA NMS.
#' @param cls_prob Input cls_prob
#' @param loc_pred Input loc_pred
#' @param anchor Input anchor
#' @param background_id int, optional, default='0'
#' @param clip boolean, optional, default=1
#' @param force_suppress boolean, optional, default=0
#' @param nms_threshold float, optional, default=0.5
#' @param nms_topk int, optional, default='-1'
#' @param threshold float, optional, default=0.01
#' @param variances Shape(tuple), optional, default=(0.1, 0.1, 0.2, 0.2)
#' @export
`mx.nd._contrib_MultiBoxDetection` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_contrib_MultiBoxDetection", list(...), out,
                             input.names = c("cls_prob", "loc_pred", "anchor"))
}

#' Symbolic form of _contrib_MultiBoxDetection
#' @export
`mx.symbol._contrib_MultiBoxDetection` <- function(...) {
  mx.internal.symbol.create("_contrib_MultiBoxDetection", list(...))
}

#' Anchor-box generation (ref: multibox_prior.cc). Pure XLA.
#' @param data Input data
#' @param clip boolean, optional, default=0
#' @param offsets Shape(tuple), optional, default=(0.5, 0.5)
#' @param ratios Shape(tuple), optional, default=(1.0,)
#' @param sizes Shape(tuple), optional, default=(1.0,)
#' @param steps Shape(tuple), optional, default=(-1.0, -1.0)
#' @export
`mx.nd._contrib_MultiBoxPrior` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_contrib_MultiBoxPrior", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _contrib_MultiBoxPrior
#' @export
`mx.symbol._contrib_MultiBoxPrior` <- function(...) {
  mx.internal.symbol.create("_contrib_MultiBoxPrior", list(...))
}

#' Anchor→GT matching + box target encoding (ref: multibox_target.cc).
#' @param anchor Input anchor
#' @param label Input label
#' @param cls_pred Input cls_pred
#' @param ignore_label float, optional, default=-1
#' @param minimum_negative_samples int, optional, default='0'
#' @param negative_mining_ratio float, optional, default=-1
#' @param negative_mining_thresh float, optional, default=0.5
#' @param overlap_threshold float, optional, default=0.5
#' @param variances Shape(tuple), optional, default=(0.1, 0.1, 0.2, 0.2)
#' @export
`mx.nd._contrib_MultiBoxTarget` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_contrib_MultiBoxTarget", list(...), out,
                             input.names = c("anchor", "label", "cls_pred"))
}

#' Symbolic form of _contrib_MultiBoxTarget
#' @export
`mx.symbol._contrib_MultiBoxTarget` <- function(...) {
  mx.internal.symbol.create("_contrib_MultiBoxTarget", list(...))
}

#' Batched Proposal (ref contrib/multi_proposal.cc) — same math, all
#' @param cls_prob Input cls_prob
#' @param bbox_pred Input bbox_pred
#' @param im_info Input im_info
#' @param feature_stride int, optional, default='16'
#' @param iou_loss boolean, optional, default=0
#' @param output_score boolean, optional, default=0
#' @param ratios Shape(tuple), optional, default=(0.5, 1.0, 2.0)
#' @param rpn_min_size int, optional, default='16'
#' @param rpn_post_nms_top_n int, optional, default='300'
#' @param rpn_pre_nms_top_n int, optional, default='6000'
#' @param scales Shape(tuple), optional, default=(4.0, 8.0, 16.0, 32.0)
#' @param threshold float, optional, default=0.7
#' @export
`mx.nd._contrib_MultiProposal` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_contrib_MultiProposal", list(...), out,
                             input.names = c("cls_prob", "bbox_pred", "im_info"))
}

#' Symbolic form of _contrib_MultiProposal
#' @export
`mx.symbol._contrib_MultiProposal` <- function(...) {
  mx.internal.symbol.create("_contrib_MultiProposal", list(...))
}

#' Position-sensitive ROI average pooling (ref contrib/psroi_pooling-inl.h).
#' @param data Input data
#' @param rois Input rois
#' @param group_size int, optional, default='0'
#' @param output_dim int, optional, default='1'
#' @param pooled_size int, optional, default='1'
#' @param spatial_scale float, optional, default=1
#' @export
`mx.nd._contrib_PSROIPooling` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_contrib_PSROIPooling", list(...), out,
                             input.names = c("data", "rois"))
}

#' Symbolic form of _contrib_PSROIPooling
#' @export
`mx.symbol._contrib_PSROIPooling` <- function(...) {
  mx.internal.symbol.create("_contrib_PSROIPooling", list(...))
}

#' RPN proposal generation (ref src/operator/contrib/proposal.cc).
#' @param cls_prob Input cls_prob
#' @param bbox_pred Input bbox_pred
#' @param im_info Input im_info
#' @param feature_stride int, optional, default='16'
#' @param iou_loss boolean, optional, default=0
#' @param output_score boolean, optional, default=0
#' @param ratios Shape(tuple), optional, default=(0.5, 1.0, 2.0)
#' @param rpn_min_size int, optional, default='16'
#' @param rpn_post_nms_top_n int, optional, default='300'
#' @param rpn_pre_nms_top_n int, optional, default='6000'
#' @param scales Shape(tuple), optional, default=(4.0, 8.0, 16.0, 32.0)
#' @param threshold float, optional, default=0.7
#' @export
`mx.nd._contrib_Proposal` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_contrib_Proposal", list(...), out,
                             input.names = c("cls_prob", "bbox_pred", "im_info"))
}

#' Symbolic form of _contrib_Proposal
#' @export
`mx.symbol._contrib_Proposal` <- function(...) {
  mx.internal.symbol.create("_contrib_Proposal", list(...))
}

#' Embedding lookup (ref: src/operator/tensor/indexing_op.cc Embedding).
#' @param data Input data
#' @param weight Input weight
#' @param dtype string, optional, default='float32'
#' @param input_dim string, optional, default='None'
#' @param output_dim string, optional, default='None'
#' @param sparse_grad boolean, optional, default=0
#' @export
`mx.nd._contrib_SparseEmbedding` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_contrib_SparseEmbedding", list(...), out,
                             input.names = c("data", "weight"))
}

#' Symbolic form of _contrib_SparseEmbedding
#' @export
`mx.symbol._contrib_SparseEmbedding` <- function(...) {
  mx.internal.symbol.create("_contrib_SparseEmbedding", list(...))
}

#' Count-sketch projection (ref: src/operator/contrib/count_sketch.cc).
#' @param data Input data
#' @param h Input h
#' @param s Input s
#' @param out_dim int, optional, default='0'
#' @param processing_batch_size int, optional, default='32'
#' @export
`mx.nd._contrib_count_sketch` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_contrib_count_sketch", list(...), out,
                             input.names = c("data", "h", "s"))
}

#' Symbolic form of _contrib_count_sketch
#' @export
`mx.symbol._contrib_count_sketch` <- function(...) {
  mx.internal.symbol.create("_contrib_count_sketch", list(...))
}

#' CTC negative log-likelihood via log-semiring forward scan.
#' @param data Input data
#' @param label Input label
#' @param blank_label string, optional, default='last'
#' @param data_lengths string, optional, default='None'
#' @param label_lengths string, optional, default='None'
#' @param use_data_lengths boolean, optional, default=0
#' @param use_label_lengths boolean, optional, default=0
#' @export
`mx.nd._contrib_ctc_loss` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_contrib_ctc_loss", list(...), out,
                             input.names = c("data", "label"))
}

#' Symbolic form of _contrib_ctc_loss
#' @export
`mx.symbol._contrib_ctc_loss` <- function(...) {
  mx.internal.symbol.create("_contrib_ctc_loss", list(...))
}

#' _contrib_dequantize(data, min_range, max_range) with attrs out_type — registered operator, 1 output.
#' @param data Input data
#' @param min_range Input min_range
#' @param max_range Input max_range
#' @param out_type string, optional, default='float32'
#' @export
`mx.nd._contrib_dequantize` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_contrib_dequantize", list(...), out,
                             input.names = c("data", "min_range", "max_range"))
}

#' Symbolic form of _contrib_dequantize
#' @export
`mx.symbol._contrib_dequantize` <- function(...) {
  mx.internal.symbol.create("_contrib_dequantize", list(...))
}

#' _contrib_dequantize_2bit(data) with attrs threshold — registered operator, 1 output.
#' @param data Input data
#' @param threshold float, optional, default=0.5
#' @export
`mx.nd._contrib_dequantize_2bit` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_contrib_dequantize_2bit", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _contrib_dequantize_2bit
#' @export
`mx.symbol._contrib_dequantize_2bit` <- function(...) {
  mx.internal.symbol.create("_contrib_dequantize_2bit", list(...))
}

#' ref: src/operator/contrib/fft.cc (cuFFT) → XLA fft. Output packs
#' @param data Input data
#' @param compute_size int, optional, default='128'
#' @export
`mx.nd._contrib_fft` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_contrib_fft", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _contrib_fft
#' @export
`mx.symbol._contrib_fft` <- function(...) {
  mx.internal.symbol.create("_contrib_fft", list(...))
}

#' _contrib_ifft(data) with attrs compute_size — registered operator, 1 output.
#' @param data Input data
#' @param compute_size int, optional, default='128'
#' @export
`mx.nd._contrib_ifft` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_contrib_ifft", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _contrib_ifft
#' @export
`mx.symbol._contrib_ifft` <- function(...) {
  mx.internal.symbol.create("_contrib_ifft", list(...))
}

#' _contrib_quantize(data, min_range, max_range) with attrs out_type — registered operator, 3 outputs.
#' @param data Input data
#' @param min_range Input min_range
#' @param max_range Input max_range
#' @param out_type string, optional, default='uint8'
#' @export
`mx.nd._contrib_quantize` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_contrib_quantize", list(...), out,
                             input.names = c("data", "min_range", "max_range"))
}

#' Symbolic form of _contrib_quantize
#' @export
`mx.symbol._contrib_quantize` <- function(...) {
  mx.internal.symbol.create("_contrib_quantize", list(...))
}

#' _contrib_quantize_2bit(grad, residual) with attrs threshold — registered operator, 2 outputs.
#' @param grad Input grad
#' @param residual Input residual
#' @param threshold float, optional, default=0.5
#' @export
`mx.nd._contrib_quantize_2bit` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_contrib_quantize_2bit", list(...), out,
                             input.names = c("grad", "residual"))
}

#' Symbolic form of _contrib_quantize_2bit
#' @export
`mx.symbol._contrib_quantize_2bit` <- function(...) {
  mx.internal.symbol.create("_contrib_quantize_2bit", list(...))
}

#' _copy(data) — registered operator, 1 output.
#' @param data Input data
#' @export
`mx.nd._copy` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_copy", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _copy
#' @export
`mx.symbol._copy` <- function(...) {
  mx.internal.symbol.create("_copy", list(...))
}

#' broadcast_div(lhs, rhs) — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @export
`mx.nd._div` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_div", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of _div
#' @export
`mx.symbol._div` <- function(...) {
  mx.internal.symbol.create("_div", list(...))
}

#' _div_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param scalar float, optional, default=0
#' @export
`mx.nd._div_scalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_div_scalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _div_scalar
#' @export
`mx.symbol._div_scalar` <- function(...) {
  mx.internal.symbol.create("_div_scalar", list(...))
}

#' broadcast_equal(lhs, rhs) with attrs _fn — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @param _fn string, optional
#' @export
`mx.nd._equal` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_equal", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of _equal
#' @export
`mx.symbol._equal` <- function(...) {
  mx.internal.symbol.create("_equal", list(...))
}

#' _equal_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param scalar float, optional, default=0
#' @export
`mx.nd._equal_scalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_equal_scalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _equal_scalar
#' @export
`mx.symbol._equal_scalar` <- function(...) {
  mx.internal.symbol.create("_equal_scalar", list(...))
}

#' _eye(...) with attrs M, N, ctx, dtype, k — registered operator, 1 output.
#' @param M int, optional, default='0'
#' @param N int, optional, default='0'
#' @param ctx string, optional, default='None'
#' @param dtype string, optional, default='float32'
#' @param k int, optional, default='0'
#' @export
`mx.nd._eye` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_eye", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of _eye
#' @export
`mx.symbol._eye` <- function(...) {
  mx.internal.symbol.create("_eye", list(...))
}

#' _full(...) with attrs ctx, dtype, shape, value — registered operator, 1 output.
#' @param ctx string, optional, default='None'
#' @param dtype string, optional, default='float32'
#' @param shape Shape(tuple), optional, default=()
#' @param value float, optional, default=0
#' @export
`mx.nd._full` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_full", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of _full
#' @export
`mx.symbol._full` <- function(...) {
  mx.internal.symbol.create("_full", list(...))
}

#' broadcast_add(lhs, rhs) — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @export
`mx.nd._grad_add` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_grad_add", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of _grad_add
#' @export
`mx.symbol._grad_add` <- function(...) {
  mx.internal.symbol.create("_grad_add", list(...))
}

#' broadcast_greater(lhs, rhs) with attrs _fn — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @param _fn string, optional
#' @export
`mx.nd._greater` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_greater", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of _greater
#' @export
`mx.symbol._greater` <- function(...) {
  mx.internal.symbol.create("_greater", list(...))
}

#' broadcast_greater_equal(lhs, rhs) with attrs _fn — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @param _fn string, optional
#' @export
`mx.nd._greater_equal` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_greater_equal", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of _greater_equal
#' @export
`mx.symbol._greater_equal` <- function(...) {
  mx.internal.symbol.create("_greater_equal", list(...))
}

#' _greater_equal_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param scalar float, optional, default=0
#' @export
`mx.nd._greater_equal_scalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_greater_equal_scalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _greater_equal_scalar
#' @export
`mx.symbol._greater_equal_scalar` <- function(...) {
  mx.internal.symbol.create("_greater_equal_scalar", list(...))
}

#' _greater_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param scalar float, optional, default=0
#' @export
`mx.nd._greater_scalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_greater_scalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _greater_scalar
#' @export
`mx.symbol._greater_scalar` <- function(...) {
  mx.internal.symbol.create("_greater_scalar", list(...))
}

#' Return element-wise hypotenuse for the given legs of a right angle triangle.
#' @param x1 Input x1
#' @param x2 Input x2
#' @export
`mx.nd._hypot` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_hypot", list(...), out,
                             input.names = c("x1", "x2"))
}

#' Symbolic form of _hypot
#' @export
`mx.symbol._hypot` <- function(...) {
  mx.internal.symbol.create("_hypot", list(...))
}

#' _hypot_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param scalar float, optional, default=0
#' @export
`mx.nd._hypot_scalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_hypot_scalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _hypot_scalar
#' @export
`mx.symbol._hypot_scalar` <- function(...) {
  mx.internal.symbol.create("_hypot_scalar", list(...))
}

#' _identity_with_attr_like_rhs(lhs, rhs) — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @export
`mx.nd._identity_with_attr_like_rhs` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_identity_with_attr_like_rhs", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of _identity_with_attr_like_rhs
#' @export
`mx.symbol._identity_with_attr_like_rhs` <- function(...) {
  mx.internal.symbol.create("_identity_with_attr_like_rhs", list(...))
}

#' Convolution (NCHW x OIHW) on int8 operands; dequantized f32
#' @param data Input data
#' @param weight Input weight
#' @param wscale Input wscale
#' @param bias Input bias
#' @param dilate Shape(tuple), optional, default=()
#' @param kernel Shape(tuple), optional, default=()
#' @param no_bias boolean, optional, default=0
#' @param num_filter int, optional, default='1'
#' @param num_group int, optional, default='1'
#' @param pad Shape(tuple), optional, default=()
#' @param scale float, optional, default=1
#' @param stride Shape(tuple), optional, default=()
#' @export
`mx.nd._int8_convolution` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_int8_convolution", list(...), out,
                             input.names = c("data", "weight", "wscale", "bias"))
}

#' Symbolic form of _int8_convolution
#' @export
`mx.symbol._int8_convolution` <- function(...) {
  mx.internal.symbol.create("_int8_convolution", list(...))
}

#' FullyConnected on int8 operands; dequantized f32 output.
#' @param data Input data
#' @param weight Input weight
#' @param wscale Input wscale
#' @param bias Input bias
#' @param flatten boolean, optional, default=1
#' @param no_bias boolean, optional, default=0
#' @param num_hidden int, optional, default='1'
#' @param scale float, optional, default=1
#' @export
`mx.nd._int8_fully_connected` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_int8_fully_connected", list(...), out,
                             input.names = c("data", "weight", "wscale", "bias"))
}

#' Symbolic form of _int8_fully_connected
#' @export
`mx.symbol._int8_fully_connected` <- function(...) {
  mx.internal.symbol.create("_int8_fully_connected", list(...))
}

#' broadcast_lesser(lhs, rhs) with attrs _fn — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @param _fn string, optional
#' @export
`mx.nd._lesser` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_lesser", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of _lesser
#' @export
`mx.symbol._lesser` <- function(...) {
  mx.internal.symbol.create("_lesser", list(...))
}

#' broadcast_lesser_equal(lhs, rhs) with attrs _fn — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @param _fn string, optional
#' @export
`mx.nd._lesser_equal` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_lesser_equal", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of _lesser_equal
#' @export
`mx.symbol._lesser_equal` <- function(...) {
  mx.internal.symbol.create("_lesser_equal", list(...))
}

#' _lesser_equal_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param scalar float, optional, default=0
#' @export
`mx.nd._lesser_equal_scalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_lesser_equal_scalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _lesser_equal_scalar
#' @export
`mx.symbol._lesser_equal_scalar` <- function(...) {
  mx.internal.symbol.create("_lesser_equal_scalar", list(...))
}

#' _lesser_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param scalar float, optional, default=0
#' @export
`mx.nd._lesser_scalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_lesser_scalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _lesser_scalar
#' @export
`mx.symbol._lesser_scalar` <- function(...) {
  mx.internal.symbol.create("_lesser_scalar", list(...))
}

#' _linalg_extractdiag(A) with attrs offset — registered operator, 1 output.
#' @param A Input A
#' @param offset int, optional, default='0'
#' @export
`mx.nd._linalg_extractdiag` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_linalg_extractdiag", list(...), out,
                             input.names = c("A"))
}

#' Symbolic form of _linalg_extractdiag
#' @export
`mx.symbol._linalg_extractdiag` <- function(...) {
  mx.internal.symbol.create("_linalg_extractdiag", list(...))
}

#' _linalg_extracttrian(A) with attrs lower, offset — registered operator, 1 output.
#' @param A Input A
#' @param lower boolean, optional, default=1
#' @param offset int, optional, default='0'
#' @export
`mx.nd._linalg_extracttrian` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_linalg_extracttrian", list(...), out,
                             input.names = c("A"))
}

#' Symbolic form of _linalg_extracttrian
#' @export
`mx.symbol._linalg_extracttrian` <- function(...) {
  mx.internal.symbol.create("_linalg_extracttrian", list(...))
}

#' LQ factorization A = L @ Q with Q orthonormal rows (ref
#' @param A Input A
#' @export
`mx.nd._linalg_gelqf` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_linalg_gelqf", list(...), out,
                             input.names = c("A"))
}

#' Symbolic form of _linalg_gelqf
#' @export
`mx.symbol._linalg_gelqf` <- function(...) {
  mx.internal.symbol.create("_linalg_gelqf", list(...))
}

#' _linalg_gemm(A, B, C) with attrs alpha, axis, beta, transpose_a, transpose_b — registered operator, 1 output.
#' @param A Input A
#' @param B Input B
#' @param C Input C
#' @param alpha float, optional, default=1
#' @param axis int, optional, default='-2'
#' @param beta float, optional, default=1
#' @param transpose_a boolean, optional, default=0
#' @param transpose_b boolean, optional, default=0
#' @export
`mx.nd._linalg_gemm` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_linalg_gemm", list(...), out,
                             input.names = c("A", "B", "C"))
}

#' Symbolic form of _linalg_gemm
#' @export
`mx.symbol._linalg_gemm` <- function(...) {
  mx.internal.symbol.create("_linalg_gemm", list(...))
}

#' _linalg_gemm2(A, B) with attrs alpha, axis, transpose_a, transpose_b — registered operator, 1 output.
#' @param A Input A
#' @param B Input B
#' @param alpha float, optional, default=1
#' @param axis int, optional, default='-2'
#' @param transpose_a boolean, optional, default=0
#' @param transpose_b boolean, optional, default=0
#' @export
`mx.nd._linalg_gemm2` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_linalg_gemm2", list(...), out,
                             input.names = c("A", "B"))
}

#' Symbolic form of _linalg_gemm2
#' @export
`mx.symbol._linalg_gemm2` <- function(...) {
  mx.internal.symbol.create("_linalg_gemm2", list(...))
}

#' _linalg_makediag(A) with attrs offset — registered operator, 1 output.
#' @param A Input A
#' @param offset int, optional, default='0'
#' @export
`mx.nd._linalg_makediag` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_linalg_makediag", list(...), out,
                             input.names = c("A"))
}

#' Symbolic form of _linalg_makediag
#' @export
`mx.symbol._linalg_makediag` <- function(...) {
  mx.internal.symbol.create("_linalg_makediag", list(...))
}

#' _linalg_potrf(A) — registered operator, 1 output.
#' @param A Input A
#' @export
`mx.nd._linalg_potrf` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_linalg_potrf", list(...), out,
                             input.names = c("A"))
}

#' Symbolic form of _linalg_potrf
#' @export
`mx.symbol._linalg_potrf` <- function(...) {
  mx.internal.symbol.create("_linalg_potrf", list(...))
}

#' _linalg_potri(A) — registered operator, 1 output.
#' @param A Input A
#' @export
`mx.nd._linalg_potri` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_linalg_potri", list(...), out,
                             input.names = c("A"))
}

#' Symbolic form of _linalg_potri
#' @export
`mx.symbol._linalg_potri` <- function(...) {
  mx.internal.symbol.create("_linalg_potri", list(...))
}

#' _linalg_sumlogdiag(A) — registered operator, 1 output.
#' @param A Input A
#' @export
`mx.nd._linalg_sumlogdiag` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_linalg_sumlogdiag", list(...), out,
                             input.names = c("A"))
}

#' Symbolic form of _linalg_sumlogdiag
#' @export
`mx.symbol._linalg_sumlogdiag` <- function(...) {
  mx.internal.symbol.create("_linalg_sumlogdiag", list(...))
}

#' _linalg_syevd(A) — registered operator, 2 outputs.
#' @param A Input A
#' @export
`mx.nd._linalg_syevd` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_linalg_syevd", list(...), out,
                             input.names = c("A"))
}

#' Symbolic form of _linalg_syevd
#' @export
`mx.symbol._linalg_syevd` <- function(...) {
  mx.internal.symbol.create("_linalg_syevd", list(...))
}

#' _linalg_syrk(A) with attrs alpha, transpose — registered operator, 1 output.
#' @param A Input A
#' @param alpha float, optional, default=1
#' @param transpose boolean, optional, default=0
#' @export
`mx.nd._linalg_syrk` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_linalg_syrk", list(...), out,
                             input.names = c("A"))
}

#' Symbolic form of _linalg_syrk
#' @export
`mx.symbol._linalg_syrk` <- function(...) {
  mx.internal.symbol.create("_linalg_syrk", list(...))
}

#' _linalg_trmm(A, B) with attrs alpha, lower, rightside, transpose — registered operator, 1 output.
#' @param A Input A
#' @param B Input B
#' @param alpha float, optional, default=1
#' @param lower boolean, optional, default=1
#' @param rightside boolean, optional, default=0
#' @param transpose boolean, optional, default=0
#' @export
`mx.nd._linalg_trmm` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_linalg_trmm", list(...), out,
                             input.names = c("A", "B"))
}

#' Symbolic form of _linalg_trmm
#' @export
`mx.symbol._linalg_trmm` <- function(...) {
  mx.internal.symbol.create("_linalg_trmm", list(...))
}

#' _linalg_trsm(A, B) with attrs alpha, lower, rightside, transpose — registered operator, 1 output.
#' @param A Input A
#' @param B Input B
#' @param alpha float, optional, default=1
#' @param lower boolean, optional, default=1
#' @param rightside boolean, optional, default=0
#' @param transpose boolean, optional, default=0
#' @export
`mx.nd._linalg_trsm` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_linalg_trsm", list(...), out,
                             input.names = c("A", "B"))
}

#' Symbolic form of _linalg_trsm
#' @export
`mx.symbol._linalg_trsm` <- function(...) {
  mx.internal.symbol.create("_linalg_trsm", list(...))
}

#' broadcast_logical_and(lhs, rhs) with attrs _fn — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @param _fn string, optional
#' @export
`mx.nd._logical_and` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_logical_and", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of _logical_and
#' @export
`mx.symbol._logical_and` <- function(...) {
  mx.internal.symbol.create("_logical_and", list(...))
}

#' _logical_and_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param scalar float, optional, default=0
#' @export
`mx.nd._logical_and_scalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_logical_and_scalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _logical_and_scalar
#' @export
`mx.symbol._logical_and_scalar` <- function(...) {
  mx.internal.symbol.create("_logical_and_scalar", list(...))
}

#' broadcast_logical_or(lhs, rhs) with attrs _fn — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @param _fn string, optional
#' @export
`mx.nd._logical_or` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_logical_or", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of _logical_or
#' @export
`mx.symbol._logical_or` <- function(...) {
  mx.internal.symbol.create("_logical_or", list(...))
}

#' _logical_or_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param scalar float, optional, default=0
#' @export
`mx.nd._logical_or_scalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_logical_or_scalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _logical_or_scalar
#' @export
`mx.symbol._logical_or_scalar` <- function(...) {
  mx.internal.symbol.create("_logical_or_scalar", list(...))
}

#' broadcast_logical_xor(lhs, rhs) with attrs _fn — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @param _fn string, optional
#' @export
`mx.nd._logical_xor` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_logical_xor", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of _logical_xor
#' @export
`mx.symbol._logical_xor` <- function(...) {
  mx.internal.symbol.create("_logical_xor", list(...))
}

#' _logical_xor_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param scalar float, optional, default=0
#' @export
`mx.nd._logical_xor_scalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_logical_xor_scalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _logical_xor_scalar
#' @export
`mx.symbol._logical_xor_scalar` <- function(...) {
  mx.internal.symbol.create("_logical_xor_scalar", list(...))
}

#' Return element-wise maximum of the input arrays.
#' @param data List of input symbols
#' @param out string, optional, default='None'
#' @param where string, optional, default='None'
#' @export
`mx.nd._maximum` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_maximum", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of _maximum
#' @export
`mx.symbol._maximum` <- function(...) {
  mx.internal.symbol.create("_maximum", list(...))
}

#' _maximum_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param scalar float, optional, default=0
#' @export
`mx.nd._maximum_scalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_maximum_scalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _maximum_scalar
#' @export
`mx.symbol._maximum_scalar` <- function(...) {
  mx.internal.symbol.create("_maximum_scalar", list(...))
}

#' Return element-wise minimum of the input arrays.
#' @param data List of input symbols
#' @param out string, optional, default='None'
#' @param where string, optional, default='None'
#' @export
`mx.nd._minimum` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_minimum", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of _minimum
#' @export
`mx.symbol._minimum` <- function(...) {
  mx.internal.symbol.create("_minimum", list(...))
}

#' _minimum_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param scalar float, optional, default=0
#' @export
`mx.nd._minimum_scalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_minimum_scalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _minimum_scalar
#' @export
`mx.symbol._minimum_scalar` <- function(...) {
  mx.internal.symbol.create("_minimum_scalar", list(...))
}

#' broadcast_sub(lhs, rhs) — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @export
`mx.nd._minus` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_minus", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of _minus
#' @export
`mx.symbol._minus` <- function(...) {
  mx.internal.symbol.create("_minus", list(...))
}

#' _minus_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param scalar float, optional, default=0
#' @export
`mx.nd._minus_scalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_minus_scalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _minus_scalar
#' @export
`mx.symbol._minus_scalar` <- function(...) {
  mx.internal.symbol.create("_minus_scalar", list(...))
}

#' broadcast_mod(lhs, rhs) — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @export
`mx.nd._mod` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_mod", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of _mod
#' @export
`mx.symbol._mod` <- function(...) {
  mx.internal.symbol.create("_mod", list(...))
}

#' _mod_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param scalar float, optional, default=0
#' @export
`mx.nd._mod_scalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_mod_scalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _mod_scalar
#' @export
`mx.symbol._mod_scalar` <- function(...) {
  mx.internal.symbol.create("_mod_scalar", list(...))
}

#' broadcast_mul(lhs, rhs) — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @export
`mx.nd._mul` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_mul", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of _mul
#' @export
`mx.symbol._mul` <- function(...) {
  mx.internal.symbol.create("_mul", list(...))
}

#' _mul_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param scalar float, optional, default=0
#' @export
`mx.nd._mul_scalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_mul_scalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _mul_scalar
#' @export
`mx.symbol._mul_scalar` <- function(...) {
  mx.internal.symbol.create("_mul_scalar", list(...))
}

#' negative(data) — registered operator, 1 output.
#' @param data Input data
#' @export
`mx.nd._neg` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_neg", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _neg
#' @export
`mx.symbol._neg` <- function(...) {
  mx.internal.symbol.create("_neg", list(...))
}

#' broadcast_not_equal(lhs, rhs) with attrs _fn — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @param _fn string, optional
#' @export
`mx.nd._not_equal` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_not_equal", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of _not_equal
#' @export
`mx.symbol._not_equal` <- function(...) {
  mx.internal.symbol.create("_not_equal", list(...))
}

#' _not_equal_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param scalar float, optional, default=0
#' @export
`mx.nd._not_equal_scalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_not_equal_scalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _not_equal_scalar
#' @export
`mx.symbol._not_equal_scalar` <- function(...) {
  mx.internal.symbol.create("_not_equal_scalar", list(...))
}

#' _ones(...) with attrs ctx, dtype, shape — registered operator, 1 output.
#' @param ctx string, optional, default='None'
#' @param dtype string, optional, default='float32'
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd._ones` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_ones", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of _ones
#' @export
`mx.symbol._ones` <- function(...) {
  mx.internal.symbol.create("_ones", list(...))
}

#' broadcast_add(lhs, rhs) — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @export
`mx.nd._plus` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_plus", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of _plus
#' @export
`mx.symbol._plus` <- function(...) {
  mx.internal.symbol.create("_plus", list(...))
}

#' _plus_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param scalar float, optional, default=0
#' @export
`mx.nd._plus_scalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_plus_scalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _plus_scalar
#' @export
`mx.symbol._plus_scalar` <- function(...) {
  mx.internal.symbol.create("_plus_scalar", list(...))
}

#' broadcast_power(lhs, rhs) — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @export
`mx.nd._pow` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_pow", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of _pow
#' @export
`mx.symbol._pow` <- function(...) {
  mx.internal.symbol.create("_pow", list(...))
}

#' broadcast_power(lhs, rhs) — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @export
`mx.nd._power` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_power", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of _power
#' @export
`mx.symbol._power` <- function(...) {
  mx.internal.symbol.create("_power", list(...))
}

#' _power_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param scalar float, optional, default=0
#' @export
`mx.nd._power_scalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_power_scalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _power_scalar
#' @export
`mx.symbol._power_scalar` <- function(...) {
  mx.internal.symbol.create("_power_scalar", list(...))
}

#' Symmetric int8 quantization: round(clip(x / scale)) in
#' @param data Input data
#' @param scale float, optional, default=1
#' @export
`mx.nd._quantize_int8` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_quantize_int8", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _quantize_int8
#' @export
`mx.symbol._quantize_int8` <- function(...) {
  mx.internal.symbol.create("_quantize_int8", list(...))
}

#' Per-output-channel (axis 0) symmetric int8 weight quantization.
#' @param data Input data
#' @export
`mx.nd._quantize_rows_int8` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_quantize_rows_int8", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _quantize_rows_int8
#' @export
`mx.symbol._quantize_rows_int8` <- function(...) {
  mx.internal.symbol.create("_quantize_rows_int8", list(...))
}

#' _random_exponential(...) with attrs ctx, dtype, lam, shape — registered operator, 1 output.
#' @param ctx string, optional, default='None'
#' @param dtype string, optional, default='float32'
#' @param lam float, optional, default=1
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd._random_exponential` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_random_exponential", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of _random_exponential
#' @export
`mx.symbol._random_exponential` <- function(...) {
  mx.internal.symbol.create("_random_exponential", list(...))
}

#' _random_gamma(...) with attrs alpha, beta, ctx, dtype, shape — registered operator, 1 output.
#' @param alpha float, optional, default=1
#' @param beta float, optional, default=1
#' @param ctx string, optional, default='None'
#' @param dtype string, optional, default='float32'
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd._random_gamma` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_random_gamma", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of _random_gamma
#' @export
`mx.symbol._random_gamma` <- function(...) {
  mx.internal.symbol.create("_random_gamma", list(...))
}

#' _random_generalized_negative_binomial(...) with attrs alpha, ctx, dtype, mu, shape — registered operator, 1 output.
#' @param alpha float, optional, default=1
#' @param ctx string, optional, default='None'
#' @param dtype string, optional, default='float32'
#' @param mu float, optional, default=1
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd._random_generalized_negative_binomial` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_random_generalized_negative_binomial", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of _random_generalized_negative_binomial
#' @export
`mx.symbol._random_generalized_negative_binomial` <- function(...) {
  mx.internal.symbol.create("_random_generalized_negative_binomial", list(...))
}

#' _random_negative_binomial(...) with attrs ctx, dtype, k, p, shape — registered operator, 1 output.
#' @param ctx string, optional, default='None'
#' @param dtype string, optional, default='float32'
#' @param k int, optional, default='1'
#' @param p float, optional, default=1
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd._random_negative_binomial` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_random_negative_binomial", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of _random_negative_binomial
#' @export
`mx.symbol._random_negative_binomial` <- function(...) {
  mx.internal.symbol.create("_random_negative_binomial", list(...))
}

#' _random_normal(...) with attrs ctx, dtype, loc, scale, shape — registered operator, 1 output.
#' @param ctx string, optional, default='None'
#' @param dtype string, optional, default='float32'
#' @param loc float, optional, default=0
#' @param scale float, optional, default=1
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd._random_normal` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_random_normal", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of _random_normal
#' @export
`mx.symbol._random_normal` <- function(...) {
  mx.internal.symbol.create("_random_normal", list(...))
}

#' _random_poisson(...) with attrs ctx, dtype, lam, shape — registered operator, 1 output.
#' @param ctx string, optional, default='None'
#' @param dtype string, optional, default='float32'
#' @param lam float, optional, default=1
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd._random_poisson` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_random_poisson", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of _random_poisson
#' @export
`mx.symbol._random_poisson` <- function(...) {
  mx.internal.symbol.create("_random_poisson", list(...))
}

#' _random_randint(...) with attrs ctx, dtype, high, low, shape — registered operator, 1 output.
#' @param ctx string, optional, default='None'
#' @param dtype string, optional, default='int32'
#' @param high int, optional, default='1'
#' @param low int, optional, default='0'
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd._random_randint` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_random_randint", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of _random_randint
#' @export
`mx.symbol._random_randint` <- function(...) {
  mx.internal.symbol.create("_random_randint", list(...))
}

#' _random_uniform(...) with attrs ctx, dtype, high, low, shape — registered operator, 1 output.
#' @param ctx string, optional, default='None'
#' @param dtype string, optional, default='float32'
#' @param high float, optional, default=1
#' @param low float, optional, default=0
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd._random_uniform` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_random_uniform", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of _random_uniform
#' @export
`mx.symbol._random_uniform` <- function(...) {
  mx.internal.symbol.create("_random_uniform", list(...))
}

#' _rdiv_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param scalar float, optional, default=0
#' @export
`mx.nd._rdiv_scalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_rdiv_scalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _rdiv_scalar
#' @export
`mx.symbol._rdiv_scalar` <- function(...) {
  mx.internal.symbol.create("_rdiv_scalar", list(...))
}

#' _rminus_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param scalar float, optional, default=0
#' @export
`mx.nd._rminus_scalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_rminus_scalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _rminus_scalar
#' @export
`mx.symbol._rminus_scalar` <- function(...) {
  mx.internal.symbol.create("_rminus_scalar", list(...))
}

#' _rmod_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param scalar float, optional, default=0
#' @export
`mx.nd._rmod_scalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_rmod_scalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _rmod_scalar
#' @export
`mx.symbol._rmod_scalar` <- function(...) {
  mx.internal.symbol.create("_rmod_scalar", list(...))
}

#' _rpower_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param scalar float, optional, default=0
#' @export
`mx.nd._rpower_scalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_rpower_scalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _rpower_scalar
#' @export
`mx.symbol._rpower_scalar` <- function(...) {
  mx.internal.symbol.create("_rpower_scalar", list(...))
}

#' _sample_exponential(lam) with attrs dtype, shape — registered operator, 1 output.
#' @param lam Input lam
#' @param dtype string, optional, default='float32'
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd._sample_exponential` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_sample_exponential", list(...), out,
                             input.names = c("lam"))
}

#' Symbolic form of _sample_exponential
#' @export
`mx.symbol._sample_exponential` <- function(...) {
  mx.internal.symbol.create("_sample_exponential", list(...))
}

#' _sample_gamma(alpha, beta) with attrs dtype, shape — registered operator, 1 output.
#' @param alpha Input alpha
#' @param beta Input beta
#' @param dtype string, optional, default='float32'
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd._sample_gamma` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_sample_gamma", list(...), out,
                             input.names = c("alpha", "beta"))
}

#' Symbolic form of _sample_gamma
#' @export
`mx.symbol._sample_gamma` <- function(...) {
  mx.internal.symbol.create("_sample_gamma", list(...))
}

#' Sample from categorical rows (ref: src/operator/random/multisample_op).
#' @param data Input data
#' @param dtype string, optional, default='int32'
#' @param get_prob boolean, optional, default=0
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd._sample_multinomial` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_sample_multinomial", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _sample_multinomial
#' @export
`mx.symbol._sample_multinomial` <- function(...) {
  mx.internal.symbol.create("_sample_multinomial", list(...))
}

#' _sample_normal(mu, sigma) with attrs dtype, shape — registered operator, 1 output.
#' @param mu Input mu
#' @param sigma Input sigma
#' @param dtype string, optional, default='float32'
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd._sample_normal` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_sample_normal", list(...), out,
                             input.names = c("mu", "sigma"))
}

#' Symbolic form of _sample_normal
#' @export
`mx.symbol._sample_normal` <- function(...) {
  mx.internal.symbol.create("_sample_normal", list(...))
}

#' _sample_poisson(lam) with attrs dtype, shape — registered operator, 1 output.
#' @param lam Input lam
#' @param dtype string, optional, default='float32'
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd._sample_poisson` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_sample_poisson", list(...), out,
                             input.names = c("lam"))
}

#' Symbolic form of _sample_poisson
#' @export
`mx.symbol._sample_poisson` <- function(...) {
  mx.internal.symbol.create("_sample_poisson", list(...))
}

#' _sample_uniform(low, high) with attrs dtype, shape — registered operator, 1 output.
#' @param low Input low
#' @param high Input high
#' @param dtype string, optional, default='float32'
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd._sample_uniform` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_sample_uniform", list(...), out,
                             input.names = c("low", "high"))
}

#' Symbolic form of _sample_uniform
#' @export
`mx.symbol._sample_uniform` <- function(...) {
  mx.internal.symbol.create("_sample_uniform", list(...))
}

#' _scatter_elemwise_div(lhs, rhs) — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @export
`mx.nd._scatter_elemwise_div` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_scatter_elemwise_div", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of _scatter_elemwise_div
#' @export
`mx.symbol._scatter_elemwise_div` <- function(...) {
  mx.internal.symbol.create("_scatter_elemwise_div", list(...))
}

#' _minus_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param scalar float, optional, default=0
#' @export
`mx.nd._scatter_minus_scalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_scatter_minus_scalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _scatter_minus_scalar
#' @export
`mx.symbol._scatter_minus_scalar` <- function(...) {
  mx.internal.symbol.create("_scatter_minus_scalar", list(...))
}

#' _plus_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param scalar float, optional, default=0
#' @export
`mx.nd._scatter_plus_scalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_scatter_plus_scalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _scatter_plus_scalar
#' @export
`mx.symbol._scatter_plus_scalar` <- function(...) {
  mx.internal.symbol.create("_scatter_plus_scalar", list(...))
}

#' _scatter_set_nd(lhs, rhs, indices) with attrs shape — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @param indices Input indices
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd._scatter_set_nd` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_scatter_set_nd", list(...), out,
                             input.names = c("lhs", "rhs", "indices"))
}

#' Symbolic form of _scatter_set_nd
#' @export
`mx.symbol._scatter_set_nd` <- function(...) {
  mx.internal.symbol.create("_scatter_set_nd", list(...))
}

#' shuffle(data) — registered operator, 1 output.
#' @param data Input data
#' @export
`mx.nd._shuffle` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_shuffle", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _shuffle
#' @export
`mx.symbol._shuffle` <- function(...) {
  mx.internal.symbol.create("_shuffle", list(...))
}

#' _slice_assign(lhs, rhs) with attrs begin, end, step — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @param begin Shape(tuple), optional, default=()
#' @param end Shape(tuple), optional, default=()
#' @param step Shape(tuple), optional, default=()
#' @export
`mx.nd._slice_assign` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_slice_assign", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of _slice_assign
#' @export
`mx.symbol._slice_assign` <- function(...) {
  mx.internal.symbol.create("_slice_assign", list(...))
}

#' _slice_assign_scalar(data) with attrs begin, end, scalar, step — registered operator, 1 output.
#' @param data Input data
#' @param begin Shape(tuple), optional, default=()
#' @param end Shape(tuple), optional, default=()
#' @param scalar float, optional, default=0
#' @param step Shape(tuple), optional, default=()
#' @export
`mx.nd._slice_assign_scalar` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_slice_assign_scalar", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _slice_assign_scalar
#' @export
`mx.symbol._slice_assign_scalar` <- function(...) {
  mx.internal.symbol.create("_slice_assign_scalar", list(...))
}

#' Dense fallback of row-sparse retain: zero all rows not in `indices`
#' @param data Input data
#' @param indices Input indices
#' @export
`mx.nd._sparse_retain` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_sparse_retain", list(...), out,
                             input.names = c("data", "indices"))
}

#' Symbolic form of _sparse_retain
#' @export
`mx.symbol._sparse_retain` <- function(...) {
  mx.internal.symbol.create("_sparse_retain", list(...))
}

#' sum(data**2) — the row-sparse-aware square_sum (ref
#' @param data Input data
#' @param axis string, optional, default='None'
#' @param exclude boolean, optional, default=0
#' @param keepdims boolean, optional, default=0
#' @export
`mx.nd._square_sum` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_square_sum", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of _square_sum
#' @export
`mx.symbol._square_sum` <- function(...) {
  mx.internal.symbol.create("_square_sum", list(...))
}

#' broadcast_sub(lhs, rhs) — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @export
`mx.nd._sub` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_sub", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of _sub
#' @export
`mx.symbol._sub` <- function(...) {
  mx.internal.symbol.create("_sub", list(...))
}

#' Sum of N tensors in one op (ref src/operator/tensor/elemwise_sum.cc).
#' @param data List of input symbols
#' @export
`mx.nd._sum` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_sum", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of _sum
#' @export
`mx.symbol._sum` <- function(...) {
  mx.internal.symbol.create("_sum", list(...))
}

#' _zeros(...) with attrs ctx, dtype, shape — registered operator, 1 output.
#' @param ctx string, optional, default='None'
#' @param dtype string, optional, default='float32'
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd._zeros` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("_zeros", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of _zeros
#' @export
`mx.symbol._zeros` <- function(...) {
  mx.internal.symbol.create("_zeros", list(...))
}

#' Alias of :func:`jax.numpy.absolute`.
#' @param x Input x
#' @export
`mx.nd.abs` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("abs", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of abs
#' @export
`mx.symbol.abs` <- function(...) {
  mx.internal.symbol.create("abs", list(...))
}

#' Activation(data) with attrs act_type — registered operator, 1 output.
#' @param data Input data
#' @param act_type string, optional, default='relu'
#' @export
`mx.nd.activation` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("activation", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of activation
#' @export
`mx.symbol.activation` <- function(...) {
  mx.internal.symbol.create("activation", list(...))
}

#' adam_update(weight, grad, mean, var) with attrs beta1, beta2, clip_gradient, epsilon, lazy_update, lr, rescale_grad, wd — registered operator, 3 outputs.
#' @param weight Input weight
#' @param grad Input grad
#' @param mean Input mean
#' @param var Input var
#' @param beta1 float, optional, default=0.9
#' @param beta2 float, optional, default=0.999
#' @param clip_gradient float, optional, default=-1
#' @param epsilon float, optional, default=1e-08
#' @param lazy_update boolean, optional, default=1
#' @param lr float, optional, default=0.001
#' @param rescale_grad float, optional, default=1
#' @param wd float, optional, default=0
#' @export
`mx.nd.adam_update` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("adam_update", list(...), out,
                             input.names = c("weight", "grad", "mean", "var"))
}

#' Symbolic form of adam_update
#' @export
`mx.symbol.adam_update` <- function(...) {
  mx.internal.symbol.create("adam_update", list(...))
}

#' Sum of N tensors in one op (ref src/operator/tensor/elemwise_sum.cc).
#' @param data List of input symbols
#' @export
`mx.nd.add_n` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("add_n", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of add_n
#' @export
`mx.symbol.add_n` <- function(...) {
  mx.internal.symbol.create("add_n", list(...))
}

#' Compute element-wise inverse of trigonometric cosine of input.
#' @param x Input x
#' @export
`mx.nd.arccos` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("arccos", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of arccos
#' @export
`mx.symbol.arccos` <- function(...) {
  mx.internal.symbol.create("arccos", list(...))
}

#' Calculate element-wise inverse of hyperbolic cosine of input.
#' @param x Input x
#' @export
`mx.nd.arccosh` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("arccosh", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of arccosh
#' @export
`mx.symbol.arccosh` <- function(...) {
  mx.internal.symbol.create("arccosh", list(...))
}

#' Compute element-wise inverse of trigonometric sine of input.
#' @param x Input x
#' @export
`mx.nd.arcsin` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("arcsin", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of arcsin
#' @export
`mx.symbol.arcsin` <- function(...) {
  mx.internal.symbol.create("arcsin", list(...))
}

#' Calculate element-wise inverse of hyperbolic sine of input.
#' @param x Input x
#' @export
`mx.nd.arcsinh` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("arcsinh", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of arcsinh
#' @export
`mx.symbol.arcsinh` <- function(...) {
  mx.internal.symbol.create("arcsinh", list(...))
}

#' Compute element-wise inverse of trigonometric tangent of input.
#' @param x Input x
#' @export
`mx.nd.arctan` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("arctan", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of arctan
#' @export
`mx.symbol.arctan` <- function(...) {
  mx.internal.symbol.create("arctan", list(...))
}

#' Calculate element-wise inverse of hyperbolic tangent of input.
#' @param x Input x
#' @export
`mx.nd.arctanh` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("arctanh", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of arctanh
#' @export
`mx.symbol.arctanh` <- function(...) {
  mx.internal.symbol.create("arctanh", list(...))
}

#' argmax(data) with attrs axis, keepdims — registered operator, 1 output.
#' @param data Input data
#' @param axis string, optional, default='None'
#' @param keepdims boolean, optional, default=0
#' @export
`mx.nd.argmax` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("argmax", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of argmax
#' @export
`mx.symbol.argmax` <- function(...) {
  mx.internal.symbol.create("argmax", list(...))
}

#' argmax_channel(data) — registered operator, 1 output.
#' @param data Input data
#' @export
`mx.nd.argmax_channel` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("argmax_channel", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of argmax_channel
#' @export
`mx.symbol.argmax_channel` <- function(...) {
  mx.internal.symbol.create("argmax_channel", list(...))
}

#' argmin(data) with attrs axis, keepdims — registered operator, 1 output.
#' @param data Input data
#' @param axis string, optional, default='None'
#' @param keepdims boolean, optional, default=0
#' @export
`mx.nd.argmin` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("argmin", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of argmin
#' @export
`mx.symbol.argmin` <- function(...) {
  mx.internal.symbol.create("argmin", list(...))
}

#' argsort(data) with attrs axis, dtype, is_ascend — registered operator, 1 output.
#' @param data Input data
#' @param axis int, optional, default='-1'
#' @param dtype string, optional, default='float32'
#' @param is_ascend boolean, optional, default=1
#' @export
`mx.nd.argsort` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("argsort", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of argsort
#' @export
`mx.symbol.argsort` <- function(...) {
  mx.internal.symbol.create("argsort", list(...))
}

#' batch_dot(lhs, rhs) with attrs transpose_a, transpose_b — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @param transpose_a boolean, optional, default=0
#' @param transpose_b boolean, optional, default=0
#' @export
`mx.nd.batch_dot` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("batch_dot", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of batch_dot
#' @export
`mx.symbol.batch_dot` <- function(...) {
  mx.internal.symbol.create("batch_dot", list(...))
}

#' BatchNorm with running-stat update.
#' @param data Input data
#' @param gamma Input gamma
#' @param beta Input beta
#' @param moving_mean Input moving_mean
#' @param moving_var Input moving_var
#' @param __is_train__ boolean, optional, default=0
#' @param axis int, optional, default='1'
#' @param cudnn_off boolean, optional, default=0
#' @param eps float, optional, default=0.001
#' @param fix_gamma boolean, optional, default=1
#' @param momentum float, optional, default=0.9
#' @param output_mean_var boolean, optional, default=0
#' @param use_global_stats boolean, optional, default=0
#' @export
`mx.nd.batch_norm` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("batch_norm", list(...), out,
                             input.names = c("data", "gamma", "beta", "moving_mean", "moving_var"))
}

#' Symbolic form of batch_norm
#' @export
`mx.symbol.batch_norm` <- function(...) {
  mx.internal.symbol.create("batch_norm", list(...))
}

#' batch_take(a, indices) — registered operator, 1 output.
#' @param a Input a
#' @param indices Input indices
#' @export
`mx.nd.batch_take` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("batch_take", list(...), out,
                             input.names = c("a", "indices"))
}

#' Symbolic form of batch_take
#' @export
`mx.symbol.batch_take` <- function(...) {
  mx.internal.symbol.create("batch_take", list(...))
}

#' ref: src/operator/bilinear_sampler.cc — sample data at grid coords.
#' @param data Input data
#' @param grid Input grid
#' @param cudnn_off boolean, optional, default=0
#' @export
`mx.nd.bilinear_sampler` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("bilinear_sampler", list(...), out,
                             input.names = c("data", "grid"))
}

#' Symbolic form of bilinear_sampler
#' @export
`mx.symbol.bilinear_sampler` <- function(...) {
  mx.internal.symbol.create("bilinear_sampler", list(...))
}

#' BlockGrad(data) — registered operator, 1 output.
#' @param data Input data
#' @export
`mx.nd.block_grad` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("block_grad", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of block_grad
#' @export
`mx.symbol.block_grad` <- function(...) {
  mx.internal.symbol.create("block_grad", list(...))
}

#' broadcast_add(lhs, rhs) — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @export
`mx.nd.broadcast_add` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("broadcast_add", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of broadcast_add
#' @export
`mx.symbol.broadcast_add` <- function(...) {
  mx.internal.symbol.create("broadcast_add", list(...))
}

#' broadcast_axis(data) with attrs axis, size — registered operator, 1 output.
#' @param data Input data
#' @param axis Shape(tuple), optional, default=()
#' @param size Shape(tuple), optional, default=()
#' @export
`mx.nd.broadcast_axes` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("broadcast_axes", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of broadcast_axes
#' @export
`mx.symbol.broadcast_axes` <- function(...) {
  mx.internal.symbol.create("broadcast_axes", list(...))
}

#' broadcast_axis(data) with attrs axis, size — registered operator, 1 output.
#' @param data Input data
#' @param axis Shape(tuple), optional, default=()
#' @param size Shape(tuple), optional, default=()
#' @export
`mx.nd.broadcast_axis` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("broadcast_axis", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of broadcast_axis
#' @export
`mx.symbol.broadcast_axis` <- function(...) {
  mx.internal.symbol.create("broadcast_axis", list(...))
}

#' broadcast_div(lhs, rhs) — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @export
`mx.nd.broadcast_div` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("broadcast_div", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of broadcast_div
#' @export
`mx.symbol.broadcast_div` <- function(...) {
  mx.internal.symbol.create("broadcast_div", list(...))
}

#' broadcast_equal(lhs, rhs) with attrs _fn — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @param _fn string, optional
#' @export
`mx.nd.broadcast_equal` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("broadcast_equal", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of broadcast_equal
#' @export
`mx.symbol.broadcast_equal` <- function(...) {
  mx.internal.symbol.create("broadcast_equal", list(...))
}

#' broadcast_greater(lhs, rhs) with attrs _fn — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @param _fn string, optional
#' @export
`mx.nd.broadcast_greater` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("broadcast_greater", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of broadcast_greater
#' @export
`mx.symbol.broadcast_greater` <- function(...) {
  mx.internal.symbol.create("broadcast_greater", list(...))
}

#' broadcast_greater_equal(lhs, rhs) with attrs _fn — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @param _fn string, optional
#' @export
`mx.nd.broadcast_greater_equal` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("broadcast_greater_equal", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of broadcast_greater_equal
#' @export
`mx.symbol.broadcast_greater_equal` <- function(...) {
  mx.internal.symbol.create("broadcast_greater_equal", list(...))
}

#' Return element-wise hypotenuse for the given legs of a right angle triangle.
#' @param x1 Input x1
#' @param x2 Input x2
#' @export
`mx.nd.broadcast_hypot` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("broadcast_hypot", list(...), out,
                             input.names = c("x1", "x2"))
}

#' Symbolic form of broadcast_hypot
#' @export
`mx.symbol.broadcast_hypot` <- function(...) {
  mx.internal.symbol.create("broadcast_hypot", list(...))
}

#' broadcast_lesser(lhs, rhs) with attrs _fn — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @param _fn string, optional
#' @export
`mx.nd.broadcast_lesser` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("broadcast_lesser", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of broadcast_lesser
#' @export
`mx.symbol.broadcast_lesser` <- function(...) {
  mx.internal.symbol.create("broadcast_lesser", list(...))
}

#' broadcast_lesser_equal(lhs, rhs) with attrs _fn — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @param _fn string, optional
#' @export
`mx.nd.broadcast_lesser_equal` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("broadcast_lesser_equal", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of broadcast_lesser_equal
#' @export
`mx.symbol.broadcast_lesser_equal` <- function(...) {
  mx.internal.symbol.create("broadcast_lesser_equal", list(...))
}

#' broadcast_like(lhs, rhs) — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @export
`mx.nd.broadcast_like` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("broadcast_like", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of broadcast_like
#' @export
`mx.symbol.broadcast_like` <- function(...) {
  mx.internal.symbol.create("broadcast_like", list(...))
}

#' broadcast_logical_and(lhs, rhs) with attrs _fn — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @param _fn string, optional
#' @export
`mx.nd.broadcast_logical_and` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("broadcast_logical_and", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of broadcast_logical_and
#' @export
`mx.symbol.broadcast_logical_and` <- function(...) {
  mx.internal.symbol.create("broadcast_logical_and", list(...))
}

#' broadcast_logical_or(lhs, rhs) with attrs _fn — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @param _fn string, optional
#' @export
`mx.nd.broadcast_logical_or` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("broadcast_logical_or", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of broadcast_logical_or
#' @export
`mx.symbol.broadcast_logical_or` <- function(...) {
  mx.internal.symbol.create("broadcast_logical_or", list(...))
}

#' broadcast_logical_xor(lhs, rhs) with attrs _fn — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @param _fn string, optional
#' @export
`mx.nd.broadcast_logical_xor` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("broadcast_logical_xor", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of broadcast_logical_xor
#' @export
`mx.symbol.broadcast_logical_xor` <- function(...) {
  mx.internal.symbol.create("broadcast_logical_xor", list(...))
}

#' Return element-wise maximum of the input arrays.
#' @param data List of input symbols
#' @param out string, optional, default='None'
#' @param where string, optional, default='None'
#' @export
`mx.nd.broadcast_maximum` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("broadcast_maximum", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of broadcast_maximum
#' @export
`mx.symbol.broadcast_maximum` <- function(...) {
  mx.internal.symbol.create("broadcast_maximum", list(...))
}

#' Return element-wise minimum of the input arrays.
#' @param data List of input symbols
#' @param out string, optional, default='None'
#' @param where string, optional, default='None'
#' @export
`mx.nd.broadcast_minimum` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("broadcast_minimum", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of broadcast_minimum
#' @export
`mx.symbol.broadcast_minimum` <- function(...) {
  mx.internal.symbol.create("broadcast_minimum", list(...))
}

#' broadcast_mod(lhs, rhs) — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @export
`mx.nd.broadcast_mod` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("broadcast_mod", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of broadcast_mod
#' @export
`mx.symbol.broadcast_mod` <- function(...) {
  mx.internal.symbol.create("broadcast_mod", list(...))
}

#' broadcast_mul(lhs, rhs) — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @export
`mx.nd.broadcast_mul` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("broadcast_mul", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of broadcast_mul
#' @export
`mx.symbol.broadcast_mul` <- function(...) {
  mx.internal.symbol.create("broadcast_mul", list(...))
}

#' broadcast_not_equal(lhs, rhs) with attrs _fn — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @param _fn string, optional
#' @export
`mx.nd.broadcast_not_equal` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("broadcast_not_equal", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of broadcast_not_equal
#' @export
`mx.symbol.broadcast_not_equal` <- function(...) {
  mx.internal.symbol.create("broadcast_not_equal", list(...))
}

#' broadcast_power(lhs, rhs) — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @export
`mx.nd.broadcast_power` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("broadcast_power", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of broadcast_power
#' @export
`mx.symbol.broadcast_power` <- function(...) {
  mx.internal.symbol.create("broadcast_power", list(...))
}

#' broadcast_sub(lhs, rhs) — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @export
`mx.nd.broadcast_sub` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("broadcast_sub", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of broadcast_sub
#' @export
`mx.symbol.broadcast_sub` <- function(...) {
  mx.internal.symbol.create("broadcast_sub", list(...))
}

#' broadcast_to(data) with attrs shape — registered operator, 1 output.
#' @param data Input data
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd.broadcast_to` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("broadcast_to", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of broadcast_to
#' @export
`mx.symbol.broadcast_to` <- function(...) {
  mx.internal.symbol.create("broadcast_to", list(...))
}

#' Cast(data) with attrs dtype — registered operator, 1 output.
#' @param data Input data
#' @param dtype string, optional, default='float32'
#' @export
`mx.nd.cast` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("cast", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of cast
#' @export
`mx.symbol.cast` <- function(...) {
  mx.internal.symbol.create("cast", list(...))
}

#' Dense kernel is identity; stype conversion happens in the NDArray
#' @param data Input data
#' @param stype string, optional, default='default'
#' @export
`mx.nd.cast_storage` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("cast_storage", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of cast_storage
#' @export
`mx.symbol.cast_storage` <- function(...) {
  mx.internal.symbol.create("cast_storage", list(...))
}

#' Calculates element-wise cube root of the input array.
#' @param x Input x
#' @export
`mx.nd.cbrt` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("cbrt", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of cbrt
#' @export
`mx.symbol.cbrt` <- function(...) {
  mx.internal.symbol.create("cbrt", list(...))
}

#' Round input to the nearest integer upwards.
#' @param x Input x
#' @export
`mx.nd.ceil` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("ceil", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of ceil
#' @export
`mx.symbol.ceil` <- function(...) {
  mx.internal.symbol.create("ceil", list(...))
}

#' clip(data) with attrs a_max, a_min — registered operator, 1 output.
#' @param data Input data
#' @param a_max float, optional, default=1
#' @param a_min float, optional, default=0
#' @export
`mx.nd.clip` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("clip", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of clip
#' @export
`mx.symbol.clip` <- function(...) {
  mx.internal.symbol.create("clip", list(...))
}

#' Concat(...) with attrs dim, num_args — registered operator, 1 output.
#' @param data List of input symbols
#' @param dim int, optional, default='1'
#' @param num_args string, optional, default='None'
#' @export
`mx.nd.concat` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("concat", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of concat
#' @export
`mx.symbol.concat` <- function(...) {
  mx.internal.symbol.create("concat", list(...))
}

#' Convolution(data, weight, bias) with attrs cudnn_off, cudnn_tune, dilate, kernel, layout, no_bias, num_filter, num_group, pad, stride, workspace — registered operator, 1 output.
#' @param data Input data
#' @param weight Input weight
#' @param bias Input bias
#' @param cudnn_off boolean, optional, default=0
#' @param cudnn_tune string, optional, default='None'
#' @param dilate Shape(tuple), optional, default=()
#' @param kernel Shape(tuple), optional, default=()
#' @param layout string, optional, default='None'
#' @param no_bias boolean, optional, default=0
#' @param num_filter int, optional, default='1'
#' @param num_group int, optional, default='1'
#' @param pad Shape(tuple), optional, default=()
#' @param stride Shape(tuple), optional, default=()
#' @param workspace int, optional, default='1024'
#' @export
`mx.nd.convolution` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("convolution", list(...), out,
                             input.names = c("data", "weight", "bias"))
}

#' Symbolic form of convolution
#' @export
`mx.symbol.convolution` <- function(...) {
  mx.internal.symbol.create("convolution", list(...))
}

#' Cost-volume correlation (ref: src/operator/correlation.cc).
#' @param data1 Input data1
#' @param data2 Input data2
#' @param is_multiply boolean, optional, default=1
#' @param kernel_size int, optional, default='1'
#' @param max_displacement int, optional, default='1'
#' @param pad_size int, optional, default='0'
#' @param stride1 int, optional, default='1'
#' @param stride2 int, optional, default='1'
#' @export
`mx.nd.correlation` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("correlation", list(...), out,
                             input.names = c("data1", "data2"))
}

#' Symbolic form of correlation
#' @export
`mx.symbol.correlation` <- function(...) {
  mx.internal.symbol.create("correlation", list(...))
}

#' Compute a trigonometric cosine of each element of input.
#' @param x Input x
#' @export
`mx.nd.cos` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("cos", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of cos
#' @export
`mx.symbol.cos` <- function(...) {
  mx.internal.symbol.create("cos", list(...))
}

#' Calculate element-wise hyperbolic cosine of input.
#' @param x Input x
#' @export
`mx.nd.cosh` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("cosh", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of cosh
#' @export
`mx.symbol.cosh` <- function(...) {
  mx.internal.symbol.create("cosh", list(...))
}

#' Crop(...) with attrs center_crop, h_w, num_args, offset — registered operator, 1 output.
#' @param data List of input symbols
#' @param center_crop boolean, optional, default=0
#' @param h_w Shape(tuple), optional, default=(0, 0)
#' @param num_args int, optional, default='1'
#' @param offset Shape(tuple), optional, default=(0, 0)
#' @export
`mx.nd.crop` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("crop", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of crop
#' @export
`mx.symbol.crop` <- function(...) {
  mx.internal.symbol.create("crop", list(...))
}

#' CTC negative log-likelihood via log-semiring forward scan.
#' @param data Input data
#' @param label Input label
#' @param blank_label string, optional, default='last'
#' @param data_lengths string, optional, default='None'
#' @param label_lengths string, optional, default='None'
#' @param use_data_lengths boolean, optional, default=0
#' @param use_label_lengths boolean, optional, default=0
#' @export
`mx.nd.ctc_loss` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("ctc_loss", list(...), out,
                             input.names = c("data", "label"))
}

#' Symbolic form of ctc_loss
#' @export
`mx.symbol.ctc_loss` <- function(...) {
  mx.internal.symbol.create("ctc_loss", list(...))
}

#' cumsum(a) with attrs axis, dtype — registered operator, 1 output.
#' @param a Input a
#' @param axis string, optional, default='None'
#' @param dtype string, optional, default='None'
#' @export
`mx.nd.cumsum` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("cumsum", list(...), out,
                             input.names = c("a"))
}

#' Symbolic form of cumsum
#' @export
`mx.symbol.cumsum` <- function(...) {
  mx.internal.symbol.create("cumsum", list(...))
}

#' Transposed convolution (ref: src/operator/nn/deconvolution.cc).
#' @param data Input data
#' @param weight Input weight
#' @param bias Input bias
#' @param adj Shape(tuple), optional, default=()
#' @param cudnn_off boolean, optional, default=0
#' @param cudnn_tune string, optional, default='None'
#' @param dilate Shape(tuple), optional, default=()
#' @param kernel Shape(tuple), optional, default=()
#' @param layout string, optional, default='None'
#' @param no_bias boolean, optional, default=1
#' @param num_filter int, optional, default='1'
#' @param num_group int, optional, default='1'
#' @param pad Shape(tuple), optional, default=()
#' @param stride Shape(tuple), optional, default=()
#' @param target_shape Shape(tuple), optional, default=()
#' @param workspace int, optional, default='1024'
#' @export
`mx.nd.deconvolution` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("deconvolution", list(...), out,
                             input.names = c("data", "weight", "bias"))
}

#' Symbolic form of deconvolution
#' @export
`mx.symbol.deconvolution` <- function(...) {
  mx.internal.symbol.create("deconvolution", list(...))
}

#' Alias of :func:`jax.numpy.rad2deg`
#' @param x Input x
#' @export
`mx.nd.degrees` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("degrees", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of degrees
#' @export
`mx.symbol.degrees` <- function(...) {
  mx.internal.symbol.create("degrees", list(...))
}

#' depth_to_space(data) with attrs block_size — registered operator, 1 output.
#' @param data Input data
#' @param block_size int, optional, default='1'
#' @export
`mx.nd.depth_to_space` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("depth_to_space", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of depth_to_space
#' @export
`mx.symbol.depth_to_space` <- function(...) {
  mx.internal.symbol.create("depth_to_space", list(...))
}

#' diag(data) with attrs k — registered operator, 1 output.
#' @param data Input data
#' @param k int, optional, default='0'
#' @export
`mx.nd.diag` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("diag", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of diag
#' @export
`mx.symbol.diag` <- function(...) {
  mx.internal.symbol.create("diag", list(...))
}

#' General tensor dot: contracts last axis of lhs with first of rhs
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @param transpose_a boolean, optional, default=0
#' @param transpose_b boolean, optional, default=0
#' @export
`mx.nd.dot` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("dot", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of dot
#' @export
`mx.symbol.dot` <- function(...) {
  mx.internal.symbol.create("dot", list(...))
}

#' Dropout(data) with attrs __is_train__, axes, mode, p — registered operator, 2 outputs.
#' @param data Input data
#' @param __is_train__ boolean, optional, default=0
#' @param axes Shape(tuple), optional, default=()
#' @param mode string, optional, default='training'
#' @param p float, optional, default=0.5
#' @export
`mx.nd.dropout` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("dropout", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of dropout
#' @export
`mx.symbol.dropout` <- function(...) {
  mx.internal.symbol.create("dropout", list(...))
}

#' broadcast_add(lhs, rhs) — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @export
`mx.nd.elemwise_add` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("elemwise_add", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of elemwise_add
#' @export
`mx.symbol.elemwise_add` <- function(...) {
  mx.internal.symbol.create("elemwise_add", list(...))
}

#' broadcast_div(lhs, rhs) — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @export
`mx.nd.elemwise_div` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("elemwise_div", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of elemwise_div
#' @export
`mx.symbol.elemwise_div` <- function(...) {
  mx.internal.symbol.create("elemwise_div", list(...))
}

#' broadcast_mul(lhs, rhs) — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @export
`mx.nd.elemwise_mul` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("elemwise_mul", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of elemwise_mul
#' @export
`mx.symbol.elemwise_mul` <- function(...) {
  mx.internal.symbol.create("elemwise_mul", list(...))
}

#' broadcast_sub(lhs, rhs) — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @export
`mx.nd.elemwise_sub` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("elemwise_sub", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of elemwise_sub
#' @export
`mx.symbol.elemwise_sub` <- function(...) {
  mx.internal.symbol.create("elemwise_sub", list(...))
}

#' The error function
#' @param x Input x
#' @export
`mx.nd.erf` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("erf", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of erf
#' @export
`mx.symbol.erf` <- function(...) {
  mx.internal.symbol.create("erf", list(...))
}

#' The inverse of the error function
#' @param x Input x
#' @export
`mx.nd.erfinv` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("erfinv", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of erfinv
#' @export
`mx.symbol.erfinv` <- function(...) {
  mx.internal.symbol.create("erfinv", list(...))
}

#' Calculate element-wise exponential of the input.
#' @param x Input x
#' @export
`mx.nd.exp` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("exp", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of exp
#' @export
`mx.symbol.exp` <- function(...) {
  mx.internal.symbol.create("exp", list(...))
}

#' expand_dims(data) with attrs axis — registered operator, 1 output.
#' @param data Input data
#' @param axis int, optional, default='0'
#' @export
`mx.nd.expand_dims` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("expand_dims", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of expand_dims
#' @export
`mx.symbol.expand_dims` <- function(...) {
  mx.internal.symbol.create("expand_dims", list(...))
}

#' Calculate ``exp(x)-1`` of each element of the input.
#' @param x Input x
#' @export
`mx.nd.expm1` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("expm1", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of expm1
#' @export
`mx.symbol.expm1` <- function(...) {
  mx.internal.symbol.create("expm1", list(...))
}

#' Round input to the nearest integer towards zero.
#' @param x Input x
#' @export
`mx.nd.fix` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("fix", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of fix
#' @export
`mx.symbol.fix` <- function(...) {
  mx.internal.symbol.create("fix", list(...))
}

#' Flatten(data) — registered operator, 1 output.
#' @param data Input data
#' @export
`mx.nd.flatten` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("flatten", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of flatten
#' @export
`mx.symbol.flatten` <- function(...) {
  mx.internal.symbol.create("flatten", list(...))
}

#' flip(data) with attrs axis — registered operator, 1 output.
#' @param data Input data
#' @param axis Shape(tuple), optional, default=()
#' @export
`mx.nd.flip` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("flip", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of flip
#' @export
`mx.symbol.flip` <- function(...) {
  mx.internal.symbol.create("flip", list(...))
}

#' Round input to the nearest integer downwards.
#' @param x Input x
#' @export
`mx.nd.floor` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("floor", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of floor
#' @export
`mx.symbol.floor` <- function(...) {
  mx.internal.symbol.create("floor", list(...))
}

#' ftrl_update(weight, grad, z, n) with attrs beta, clip_gradient, lamda1, lr, rescale_grad, wd — registered operator, 3 outputs.
#' @param weight Input weight
#' @param grad Input grad
#' @param z Input z
#' @param n Input n
#' @param beta float, optional, default=1
#' @param clip_gradient float, optional, default=-1
#' @param lamda1 float, optional, default=0.01
#' @param lr float, optional, default=0.1
#' @param rescale_grad float, optional, default=1
#' @param wd float, optional, default=0
#' @export
`mx.nd.ftrl_update` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("ftrl_update", list(...), out,
                             input.names = c("weight", "grad", "z", "n"))
}

#' Symbolic form of ftrl_update
#' @export
`mx.symbol.ftrl_update` <- function(...) {
  mx.internal.symbol.create("ftrl_update", list(...))
}

#' FullyConnected(data, weight, bias) with attrs flatten, no_bias, num_hidden — registered operator, 1 output.
#' @param data Input data
#' @param weight Input weight
#' @param bias Input bias
#' @param flatten boolean, optional, default=1
#' @param no_bias boolean, optional, default=0
#' @param num_hidden int, optional, default='1'
#' @export
`mx.nd.fully_connected` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("fully_connected", list(...), out,
                             input.names = c("data", "weight", "bias"))
}

#' Symbolic form of fully_connected
#' @export
`mx.symbol.fully_connected` <- function(...) {
  mx.internal.symbol.create("fully_connected", list(...))
}

#' gamma(data) — registered operator, 1 output.
#' @param data Input data
#' @export
`mx.nd.gamma` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("gamma", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of gamma
#' @export
`mx.symbol.gamma` <- function(...) {
  mx.internal.symbol.create("gamma", list(...))
}

#' Natural log of the absolute value of the gamma function.
#' @param x Input x
#' @export
`mx.nd.gammaln` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("gammaln", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of gammaln
#' @export
`mx.symbol.gammaln` <- function(...) {
  mx.internal.symbol.create("gammaln", list(...))
}

#' gather_nd(data, indices) — registered operator, 1 output.
#' @param data Input data
#' @param indices Input indices
#' @export
`mx.nd.gather_nd` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("gather_nd", list(...), out,
                             input.names = c("data", "indices"))
}

#' Symbolic form of gather_nd
#' @export
`mx.symbol.gather_nd` <- function(...) {
  mx.internal.symbol.create("gather_nd", list(...))
}

#' GridGenerator(data) with attrs target_shape, transform_type — registered operator, 1 output.
#' @param data Input data
#' @param target_shape Shape(tuple), optional, default=(0, 0)
#' @param transform_type string, optional, default='affine'
#' @export
`mx.nd.grid_generator` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("grid_generator", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of grid_generator
#' @export
`mx.symbol.grid_generator` <- function(...) {
  mx.internal.symbol.create("grid_generator", list(...))
}

#' _copy(data) — registered operator, 1 output.
#' @param data Input data
#' @export
`mx.nd.identity` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("identity", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of identity
#' @export
`mx.symbol.identity` <- function(...) {
  mx.internal.symbol.create("identity", list(...))
}

#' IdentityAttachKLSparseReg(data) with attrs momentum, penalty, sparseness_target — registered operator, 1 output.
#' @param data Input data
#' @param momentum float, optional, default=0.9
#' @param penalty float, optional, default=0.001
#' @param sparseness_target float, optional, default=0.1
#' @export
`mx.nd.identity_attach_kl_sparse_reg` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("identity_attach_kl_sparse_reg", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of identity_attach_kl_sparse_reg
#' @export
`mx.symbol.identity_attach_kl_sparse_reg` <- function(...) {
  mx.internal.symbol.create("identity_attach_kl_sparse_reg", list(...))
}

#' InstanceNorm(data, gamma, beta) with attrs eps — registered operator, 1 output.
#' @param data Input data
#' @param gamma Input gamma
#' @param beta Input beta
#' @param eps float, optional, default=0.001
#' @export
`mx.nd.instance_norm` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("instance_norm", list(...), out,
                             input.names = c("data", "gamma", "beta"))
}

#' Symbolic form of instance_norm
#' @export
`mx.symbol.instance_norm` <- function(...) {
  mx.internal.symbol.create("instance_norm", list(...))
}

#' Column-wise Khatri-Rao product (ref: src/operator/contrib/krprod.h).
#' @param data List of input symbols
#' @param num_args string, optional, default='None'
#' @export
`mx.nd.khatri_rao` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("khatri_rao", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of khatri_rao
#' @export
`mx.symbol.khatri_rao` <- function(...) {
  mx.internal.symbol.create("khatri_rao", list(...))
}

#' L2Normalization(data) with attrs eps, mode — registered operator, 1 output.
#' @param data Input data
#' @param eps float, optional, default=1e-10
#' @param mode string, optional, default='instance'
#' @export
`mx.nd.l2_normalization` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("l2_normalization", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of l2_normalization
#' @export
`mx.symbol.l2_normalization` <- function(...) {
  mx.internal.symbol.create("l2_normalization", list(...))
}

#' LayerNorm(data, gamma, beta) with attrs axis, eps, output_mean_var — registered operator, 3 outputs.
#' @param data Input data
#' @param gamma Input gamma
#' @param beta Input beta
#' @param axis int, optional, default='-1'
#' @param eps float, optional, default=1e-05
#' @param output_mean_var boolean, optional, default=0
#' @export
`mx.nd.layer_norm` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("layer_norm", list(...), out,
                             input.names = c("data", "gamma", "beta"))
}

#' Symbolic form of layer_norm
#' @export
`mx.symbol.layer_norm` <- function(...) {
  mx.internal.symbol.create("layer_norm", list(...))
}

#' ref: src/operator/leaky_relu.cc — leaky/prelu/elu/selu/rrelu/gelu.
#' @param data Input data
#' @param gamma Input gamma
#' @param __is_train__ boolean, optional, default=0
#' @param act_type string, optional, default='leaky'
#' @param lower_bound float, optional, default=0.125
#' @param slope float, optional, default=0.25
#' @param upper_bound float, optional, default=0.334
#' @export
`mx.nd.leaky_relu` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("leaky_relu", list(...), out,
                             input.names = c("data", "gamma"))
}

#' Symbolic form of leaky_relu
#' @export
`mx.symbol.leaky_relu` <- function(...) {
  mx.internal.symbol.create("leaky_relu", list(...))
}

#' LinearRegressionOutput(data, label) with attrs grad_scale — registered operator, 1 output.
#' @param data Input data
#' @param label Input label
#' @param grad_scale float, optional, default=1
#' @export
`mx.nd.linear_regression_output` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("linear_regression_output", list(...), out,
                             input.names = c("data", "label"))
}

#' Symbolic form of linear_regression_output
#' @export
`mx.symbol.linear_regression_output` <- function(...) {
  mx.internal.symbol.create("linear_regression_output", list(...))
}

#' Calculate element-wise natural logarithm of the input.
#' @param x Input x
#' @export
`mx.nd.log` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("log", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of log
#' @export
`mx.symbol.log` <- function(...) {
  mx.internal.symbol.create("log", list(...))
}

#' Calculates the base-10 logarithm of x element-wise
#' @param x Input x
#' @export
`mx.nd.log10` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("log10", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of log10
#' @export
`mx.symbol.log10` <- function(...) {
  mx.internal.symbol.create("log10", list(...))
}

#' Calculates element-wise logarithm of one plus input, ``log(x+1)``.
#' @param x Input x
#' @export
`mx.nd.log1p` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("log1p", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of log1p
#' @export
`mx.symbol.log1p` <- function(...) {
  mx.internal.symbol.create("log1p", list(...))
}

#' Calculates the base-2 logarithm of ``x`` element-wise.
#' @param x Input x
#' @export
`mx.nd.log2` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("log2", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of log2
#' @export
`mx.symbol.log2` <- function(...) {
  mx.internal.symbol.create("log2", list(...))
}

#' log_softmax(data) with attrs axis, temperature — registered operator, 1 output.
#' @param data Input data
#' @param axis int, optional, default='-1'
#' @param temperature string, optional, default='None'
#' @export
`mx.nd.log_softmax` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("log_softmax", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of log_softmax
#' @export
`mx.symbol.log_softmax` <- function(...) {
  mx.internal.symbol.create("log_softmax", list(...))
}

#' logical_not(data) — registered operator, 1 output.
#' @param data Input data
#' @export
`mx.nd.logical_not` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("logical_not", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of logical_not
#' @export
`mx.symbol.logical_not` <- function(...) {
  mx.internal.symbol.create("logical_not", list(...))
}

#' LogisticRegressionOutput(data, label) with attrs grad_scale — registered operator, 1 output.
#' @param data Input data
#' @param label Input label
#' @param grad_scale float, optional, default=1
#' @export
`mx.nd.logistic_regression_output` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("logistic_regression_output", list(...), out,
                             input.names = c("data", "label"))
}

#' Symbolic form of logistic_regression_output
#' @export
`mx.symbol.logistic_regression_output` <- function(...) {
  mx.internal.symbol.create("logistic_regression_output", list(...))
}

#' Local response norm across channels (ref: src/operator/lrn.cc).
#' @param data Input data
#' @param alpha float, optional, default=0.0001
#' @param beta float, optional, default=0.75
#' @param knorm float, optional, default=2
#' @param nsize int, optional, default='5'
#' @export
`mx.nd.lrn` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("lrn", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of lrn
#' @export
`mx.symbol.lrn` <- function(...) {
  mx.internal.symbol.create("lrn", list(...))
}

#' MAERegressionOutput(data, label) with attrs grad_scale — registered operator, 1 output.
#' @param data Input data
#' @param label Input label
#' @param grad_scale float, optional, default=1
#' @export
`mx.nd.mae_regression_output` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("mae_regression_output", list(...), out,
                             input.names = c("data", "label"))
}

#' Symbolic form of mae_regression_output
#' @export
`mx.symbol.mae_regression_output` <- function(...) {
  mx.internal.symbol.create("mae_regression_output", list(...))
}

#' Loss head: forward identity, backward a constant grad_scale field.
#' @param data Input data
#' @param grad_scale float, optional, default=1
#' @param normalization string, optional, default='null'
#' @param valid_thresh float, optional, default=0
#' @export
`mx.nd.make_loss` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("make_loss", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of make_loss
#' @export
`mx.symbol.make_loss` <- function(...) {
  mx.internal.symbol.create("make_loss", list(...))
}

#' max(data) with attrs _fn, axis, exclude, keepdims — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param axis string, optional, default='None'
#' @param exclude boolean, optional, default=0
#' @param keepdims boolean, optional, default=0
#' @export
`mx.nd.max` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("max", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of max
#' @export
`mx.symbol.max` <- function(...) {
  mx.internal.symbol.create("max", list(...))
}

#' max(data) with attrs _fn, axis, exclude, keepdims — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param axis string, optional, default='None'
#' @param exclude boolean, optional, default=0
#' @param keepdims boolean, optional, default=0
#' @export
`mx.nd.max_axis` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("max_axis", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of max_axis
#' @export
`mx.symbol.max_axis` <- function(...) {
  mx.internal.symbol.create("max_axis", list(...))
}

#' mean(data) with attrs _fn, axis, exclude, keepdims — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param axis string, optional, default='None'
#' @param exclude boolean, optional, default=0
#' @param keepdims boolean, optional, default=0
#' @export
`mx.nd.mean` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("mean", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of mean
#' @export
`mx.symbol.mean` <- function(...) {
  mx.internal.symbol.create("mean", list(...))
}

#' min(data) with attrs _fn, axis, exclude, keepdims — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param axis string, optional, default='None'
#' @param exclude boolean, optional, default=0
#' @param keepdims boolean, optional, default=0
#' @export
`mx.nd.min` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("min", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of min
#' @export
`mx.symbol.min` <- function(...) {
  mx.internal.symbol.create("min", list(...))
}

#' min(data) with attrs _fn, axis, exclude, keepdims — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param axis string, optional, default='None'
#' @param exclude boolean, optional, default=0
#' @param keepdims boolean, optional, default=0
#' @export
`mx.nd.min_axis` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("min_axis", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of min_axis
#' @export
`mx.symbol.min_axis` <- function(...) {
  mx.internal.symbol.create("min_axis", list(...))
}

#' mp_sgd_mom_update(weight, grad, mom, weight32) with attrs clip_gradient, lazy_update, lr, momentum, rescale_grad, wd — registered operator, 3 outputs.
#' @param weight Input weight
#' @param grad Input grad
#' @param mom Input mom
#' @param weight32 Input weight32
#' @param clip_gradient float, optional, default=-1
#' @param lazy_update boolean, optional, default=1
#' @param lr float, optional, default=0.01
#' @param momentum float, optional, default=0
#' @param rescale_grad float, optional, default=1
#' @param wd float, optional, default=0
#' @export
`mx.nd.mp_sgd_mom_update` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("mp_sgd_mom_update", list(...), out,
                             input.names = c("weight", "grad", "mom", "weight32"))
}

#' Symbolic form of mp_sgd_mom_update
#' @export
`mx.symbol.mp_sgd_mom_update` <- function(...) {
  mx.internal.symbol.create("mp_sgd_mom_update", list(...))
}

#' Multi-precision SGD: fp32 master weights, low-precision model weights.
#' @param weight Input weight
#' @param grad Input grad
#' @param weight32 Input weight32
#' @param clip_gradient float, optional, default=-1
#' @param lazy_update boolean, optional, default=1
#' @param lr float, optional, default=0.01
#' @param rescale_grad float, optional, default=1
#' @param wd float, optional, default=0
#' @export
`mx.nd.mp_sgd_update` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("mp_sgd_update", list(...), out,
                             input.names = c("weight", "grad", "weight32"))
}

#' Symbolic form of mp_sgd_update
#' @export
`mx.symbol.mp_sgd_update` <- function(...) {
  mx.internal.symbol.create("mp_sgd_update", list(...))
}

#' nanprod(data) with attrs _fn, axis, exclude, keepdims — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param axis string, optional, default='None'
#' @param exclude boolean, optional, default=0
#' @param keepdims boolean, optional, default=0
#' @export
`mx.nd.nanprod` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("nanprod", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of nanprod
#' @export
`mx.symbol.nanprod` <- function(...) {
  mx.internal.symbol.create("nanprod", list(...))
}

#' nansum(data) with attrs _fn, axis, exclude, keepdims — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param axis string, optional, default='None'
#' @param exclude boolean, optional, default=0
#' @param keepdims boolean, optional, default=0
#' @export
`mx.nd.nansum` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("nansum", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of nansum
#' @export
`mx.symbol.nansum` <- function(...) {
  mx.internal.symbol.create("nansum", list(...))
}

#' negative(data) — registered operator, 1 output.
#' @param data Input data
#' @export
`mx.nd.negative` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("negative", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of negative
#' @export
`mx.symbol.negative` <- function(...) {
  mx.internal.symbol.create("negative", list(...))
}

#' norm(data) with attrs axis, keepdims, ord — registered operator, 1 output.
#' @param data Input data
#' @param axis string, optional, default='None'
#' @param keepdims boolean, optional, default=0
#' @param ord int, optional, default='2'
#' @export
`mx.nd.norm` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("norm", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of norm
#' @export
`mx.symbol.norm` <- function(...) {
  mx.internal.symbol.create("norm", list(...))
}

#' _random_normal(...) with attrs ctx, dtype, loc, scale, shape — registered operator, 1 output.
#' @param ctx string, optional, default='None'
#' @param dtype string, optional, default='float32'
#' @param loc float, optional, default=0
#' @param scale float, optional, default=1
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd.normal` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("normal", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of normal
#' @export
`mx.symbol.normal` <- function(...) {
  mx.internal.symbol.create("normal", list(...))
}

#' one_hot(indices) with attrs depth, dtype, off_value, on_value — registered operator, 1 output.
#' @param indices Input indices
#' @param depth int, optional, default='0'
#' @param dtype string, optional, default='float32'
#' @param off_value float, optional, default=0
#' @param on_value float, optional, default=1
#' @export
`mx.nd.one_hot` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("one_hot", list(...), out,
                             input.names = c("indices"))
}

#' Symbolic form of one_hot
#' @export
`mx.symbol.one_hot` <- function(...) {
  mx.internal.symbol.create("one_hot", list(...))
}

#' ones_like(data) — registered operator, 1 output.
#' @param data Input data
#' @export
`mx.nd.ones_like` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("ones_like", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of ones_like
#' @export
`mx.symbol.ones_like` <- function(...) {
  mx.internal.symbol.create("ones_like", list(...))
}

#' pad(data) with attrs constant_value, mode, pad_width — registered operator, 1 output.
#' @param data Input data
#' @param constant_value float, optional, default=0
#' @param mode string, optional, default='constant'
#' @param pad_width Shape(tuple), optional, default=()
#' @export
`mx.nd.pad` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("pad", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of pad
#' @export
`mx.symbol.pad` <- function(...) {
  mx.internal.symbol.create("pad", list(...))
}

#' Pick per-row elements by index (ref: src/operator/tensor/broadcast_reduce_op_index.cc).
#' @param data Input data
#' @param index Input index
#' @param axis int, optional, default='-1'
#' @param keepdims boolean, optional, default=0
#' @param mode string, optional, default='clip'
#' @export
`mx.nd.pick` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("pick", list(...), out,
                             input.names = c("data", "index"))
}

#' Symbolic form of pick
#' @export
`mx.symbol.pick` <- function(...) {
  mx.internal.symbol.create("pick", list(...))
}

#' Pooling(data) with attrs cudnn_off, global_pool, kernel, pad, pool_type, pooling_convention, stride — registered operator, 1 output.
#' @param data Input data
#' @param cudnn_off boolean, optional, default=0
#' @param global_pool boolean, optional, default=0
#' @param kernel Shape(tuple), optional, default=()
#' @param pad Shape(tuple), optional, default=()
#' @param pool_type string, optional, default='max'
#' @param pooling_convention string, optional, default='valid'
#' @param stride Shape(tuple), optional, default=()
#' @export
`mx.nd.pooling` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("pooling", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of pooling
#' @export
`mx.symbol.pooling` <- function(...) {
  mx.internal.symbol.create("pooling", list(...))
}

#' prod(data) with attrs _fn, axis, exclude, keepdims — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param axis string, optional, default='None'
#' @param exclude boolean, optional, default=0
#' @param keepdims boolean, optional, default=0
#' @export
`mx.nd.prod` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("prod", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of prod
#' @export
`mx.symbol.prod` <- function(...) {
  mx.internal.symbol.create("prod", list(...))
}

#' Alias of :func:`jax.numpy.deg2rad`
#' @param x Input x
#' @export
`mx.nd.radians` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("radians", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of radians
#' @export
`mx.symbol.radians` <- function(...) {
  mx.internal.symbol.create("radians", list(...))
}

#' _random_exponential(...) with attrs ctx, dtype, lam, shape — registered operator, 1 output.
#' @param ctx string, optional, default='None'
#' @param dtype string, optional, default='float32'
#' @param lam float, optional, default=1
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd.random_exponential` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("random_exponential", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of random_exponential
#' @export
`mx.symbol.random_exponential` <- function(...) {
  mx.internal.symbol.create("random_exponential", list(...))
}

#' _random_gamma(...) with attrs alpha, beta, ctx, dtype, shape — registered operator, 1 output.
#' @param alpha float, optional, default=1
#' @param beta float, optional, default=1
#' @param ctx string, optional, default='None'
#' @param dtype string, optional, default='float32'
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd.random_gamma` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("random_gamma", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of random_gamma
#' @export
`mx.symbol.random_gamma` <- function(...) {
  mx.internal.symbol.create("random_gamma", list(...))
}

#' _random_generalized_negative_binomial(...) with attrs alpha, ctx, dtype, mu, shape — registered operator, 1 output.
#' @param alpha float, optional, default=1
#' @param ctx string, optional, default='None'
#' @param dtype string, optional, default='float32'
#' @param mu float, optional, default=1
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd.random_generalized_negative_binomial` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("random_generalized_negative_binomial", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of random_generalized_negative_binomial
#' @export
`mx.symbol.random_generalized_negative_binomial` <- function(...) {
  mx.internal.symbol.create("random_generalized_negative_binomial", list(...))
}

#' _random_negative_binomial(...) with attrs ctx, dtype, k, p, shape — registered operator, 1 output.
#' @param ctx string, optional, default='None'
#' @param dtype string, optional, default='float32'
#' @param k int, optional, default='1'
#' @param p float, optional, default=1
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd.random_negative_binomial` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("random_negative_binomial", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of random_negative_binomial
#' @export
`mx.symbol.random_negative_binomial` <- function(...) {
  mx.internal.symbol.create("random_negative_binomial", list(...))
}

#' _random_normal(...) with attrs ctx, dtype, loc, scale, shape — registered operator, 1 output.
#' @param ctx string, optional, default='None'
#' @param dtype string, optional, default='float32'
#' @param loc float, optional, default=0
#' @param scale float, optional, default=1
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd.random_normal` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("random_normal", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of random_normal
#' @export
`mx.symbol.random_normal` <- function(...) {
  mx.internal.symbol.create("random_normal", list(...))
}

#' _random_poisson(...) with attrs ctx, dtype, lam, shape — registered operator, 1 output.
#' @param ctx string, optional, default='None'
#' @param dtype string, optional, default='float32'
#' @param lam float, optional, default=1
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd.random_poisson` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("random_poisson", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of random_poisson
#' @export
`mx.symbol.random_poisson` <- function(...) {
  mx.internal.symbol.create("random_poisson", list(...))
}

#' _random_randint(...) with attrs ctx, dtype, high, low, shape — registered operator, 1 output.
#' @param ctx string, optional, default='None'
#' @param dtype string, optional, default='int32'
#' @param high int, optional, default='1'
#' @param low int, optional, default='0'
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd.random_randint` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("random_randint", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of random_randint
#' @export
`mx.symbol.random_randint` <- function(...) {
  mx.internal.symbol.create("random_randint", list(...))
}

#' _random_uniform(...) with attrs ctx, dtype, high, low, shape — registered operator, 1 output.
#' @param ctx string, optional, default='None'
#' @param dtype string, optional, default='float32'
#' @param high float, optional, default=1
#' @param low float, optional, default=0
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd.random_uniform` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("random_uniform", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of random_uniform
#' @export
`mx.symbol.random_uniform` <- function(...) {
  mx.internal.symbol.create("random_uniform", list(...))
}

#' rcbrt(data) — registered operator, 1 output.
#' @param data Input data
#' @export
`mx.nd.rcbrt` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("rcbrt", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of rcbrt
#' @export
`mx.symbol.rcbrt` <- function(...) {
  mx.internal.symbol.create("rcbrt", list(...))
}

#' reciprocal(data) — registered operator, 1 output.
#' @param data Input data
#' @export
`mx.nd.reciprocal` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("reciprocal", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of reciprocal
#' @export
`mx.symbol.reciprocal` <- function(...) {
  mx.internal.symbol.create("reciprocal", list(...))
}

#' relu(data) — registered operator, 1 output.
#' @param data Input data
#' @export
`mx.nd.relu` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("relu", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of relu
#' @export
`mx.symbol.relu` <- function(...) {
  mx.internal.symbol.create("relu", list(...))
}

#' repeat(data) with attrs axis, repeats — registered operator, 1 output.
#' @param data Input data
#' @param axis string, optional, default='None'
#' @param repeats int, optional, default='1'
#' @export
`mx.nd.repeat` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("repeat", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of repeat
#' @export
`mx.symbol.repeat` <- function(...) {
  mx.internal.symbol.create("repeat", list(...))
}

#' Reshape(data) with attrs reverse, shape — registered operator, 1 output.
#' @param data Input data
#' @param reverse boolean, optional, default=0
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd.reshape` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("reshape", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of reshape
#' @export
`mx.symbol.reshape` <- function(...) {
  mx.internal.symbol.create("reshape", list(...))
}

#' reshape_like(lhs, rhs) — registered operator, 1 output.
#' @param lhs Input lhs
#' @param rhs Input rhs
#' @export
`mx.nd.reshape_like` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("reshape_like", list(...), out,
                             input.names = c("lhs", "rhs"))
}

#' Symbolic form of reshape_like
#' @export
`mx.symbol.reshape_like` <- function(...) {
  mx.internal.symbol.create("reshape_like", list(...))
}

#' flip(data) with attrs axis — registered operator, 1 output.
#' @param data Input data
#' @param axis Shape(tuple), optional, default=()
#' @export
`mx.nd.reverse` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("reverse", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of reverse
#' @export
`mx.symbol.reverse` <- function(...) {
  mx.internal.symbol.create("reverse", list(...))
}

#' Rounds the elements of x to the nearest integer
#' @param x Input x
#' @export
`mx.nd.rint` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("rint", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of rint
#' @export
`mx.symbol.rint` <- function(...) {
  mx.internal.symbol.create("rint", list(...))
}

#' rmsprop_update(weight, grad, n) with attrs clip_gradient, clip_weights, epsilon, gamma1, lr, rescale_grad, wd — registered operator, 2 outputs.
#' @param weight Input weight
#' @param grad Input grad
#' @param n Input n
#' @param clip_gradient float, optional, default=-1
#' @param clip_weights float, optional, default=-1
#' @param epsilon float, optional, default=1e-08
#' @param gamma1 float, optional, default=0.9
#' @param lr float, optional, default=0.001
#' @param rescale_grad float, optional, default=1
#' @param wd float, optional, default=0
#' @export
`mx.nd.rmsprop_update` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("rmsprop_update", list(...), out,
                             input.names = c("weight", "grad", "n"))
}

#' Symbolic form of rmsprop_update
#' @export
`mx.symbol.rmsprop_update` <- function(...) {
  mx.internal.symbol.create("rmsprop_update", list(...))
}

#' rmspropalex_update(weight, grad, n, g_state, delta) with attrs clip_gradient, clip_weights, epsilon, gamma1, gamma2, lr, rescale_grad, wd — registered operator, 4 outputs.
#' @param weight Input weight
#' @param grad Input grad
#' @param n Input n
#' @param g_state Input g_state
#' @param delta Input delta
#' @param clip_gradient float, optional, default=-1
#' @param clip_weights float, optional, default=-1
#' @param epsilon float, optional, default=1e-08
#' @param gamma1 float, optional, default=0.95
#' @param gamma2 float, optional, default=0.9
#' @param lr float, optional, default=0.001
#' @param rescale_grad float, optional, default=1
#' @param wd float, optional, default=0
#' @export
`mx.nd.rmspropalex_update` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("rmspropalex_update", list(...), out,
                             input.names = c("weight", "grad", "n", "g_state", "delta"))
}

#' Symbolic form of rmspropalex_update
#' @export
`mx.symbol.rmspropalex_update` <- function(...) {
  mx.internal.symbol.create("rmspropalex_update", list(...))
}

#' Fused multi-layer (bi)RNN over the whole sequence.
#' @param data Input data
#' @param parameters Input parameters
#' @param state Input state
#' @param state_cell Input state_cell
#' @param __is_train__ boolean, optional, default=0
#' @param bidirectional boolean, optional, default=0
#' @param mode string, optional, default='lstm'
#' @param num_layers int, optional, default='1'
#' @param p float, optional, default=0
#' @param state_outputs boolean, optional, default=0
#' @param state_size int, optional, default='0'
#' @export
`mx.nd.rnn` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("rnn", list(...), out,
                             input.names = c("data", "parameters", "state", "state_cell"))
}

#' Symbolic form of rnn
#' @export
`mx.symbol.rnn` <- function(...) {
  mx.internal.symbol.create("rnn", list(...))
}

#' Round input evenly to the given number of decimals.
#' @param a Input a
#' @param decimals int, optional, default='0'
#' @param out string, optional, default='None'
#' @export
`mx.nd.round` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("round", list(...), out,
                             input.names = c("a"))
}

#' Symbolic form of round
#' @export
`mx.symbol.round` <- function(...) {
  mx.internal.symbol.create("round", list(...))
}

#' rsqrt(data) — registered operator, 1 output.
#' @param data Input data
#' @export
`mx.nd.rsqrt` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("rsqrt", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of rsqrt
#' @export
`mx.symbol.rsqrt` <- function(...) {
  mx.internal.symbol.create("rsqrt", list(...))
}

#' _sample_exponential(lam) with attrs dtype, shape — registered operator, 1 output.
#' @param lam Input lam
#' @param dtype string, optional, default='float32'
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd.sample_exponential` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("sample_exponential", list(...), out,
                             input.names = c("lam"))
}

#' Symbolic form of sample_exponential
#' @export
`mx.symbol.sample_exponential` <- function(...) {
  mx.internal.symbol.create("sample_exponential", list(...))
}

#' _sample_gamma(alpha, beta) with attrs dtype, shape — registered operator, 1 output.
#' @param alpha Input alpha
#' @param beta Input beta
#' @param dtype string, optional, default='float32'
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd.sample_gamma` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("sample_gamma", list(...), out,
                             input.names = c("alpha", "beta"))
}

#' Symbolic form of sample_gamma
#' @export
`mx.symbol.sample_gamma` <- function(...) {
  mx.internal.symbol.create("sample_gamma", list(...))
}

#' Sample from categorical rows (ref: src/operator/random/multisample_op).
#' @param data Input data
#' @param dtype string, optional, default='int32'
#' @param get_prob boolean, optional, default=0
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd.sample_multinomial` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("sample_multinomial", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of sample_multinomial
#' @export
`mx.symbol.sample_multinomial` <- function(...) {
  mx.internal.symbol.create("sample_multinomial", list(...))
}

#' _sample_normal(mu, sigma) with attrs dtype, shape — registered operator, 1 output.
#' @param mu Input mu
#' @param sigma Input sigma
#' @param dtype string, optional, default='float32'
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd.sample_normal` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("sample_normal", list(...), out,
                             input.names = c("mu", "sigma"))
}

#' Symbolic form of sample_normal
#' @export
`mx.symbol.sample_normal` <- function(...) {
  mx.internal.symbol.create("sample_normal", list(...))
}

#' _sample_poisson(lam) with attrs dtype, shape — registered operator, 1 output.
#' @param lam Input lam
#' @param dtype string, optional, default='float32'
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd.sample_poisson` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("sample_poisson", list(...), out,
                             input.names = c("lam"))
}

#' Symbolic form of sample_poisson
#' @export
`mx.symbol.sample_poisson` <- function(...) {
  mx.internal.symbol.create("sample_poisson", list(...))
}

#' _sample_uniform(low, high) with attrs dtype, shape — registered operator, 1 output.
#' @param low Input low
#' @param high Input high
#' @param dtype string, optional, default='float32'
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd.sample_uniform` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("sample_uniform", list(...), out,
                             input.names = c("low", "high"))
}

#' Symbolic form of sample_uniform
#' @export
`mx.symbol.sample_uniform` <- function(...) {
  mx.internal.symbol.create("sample_uniform", list(...))
}

#' scatter_nd(data, indices) with attrs shape — registered operator, 1 output.
#' @param data Input data
#' @param indices Input indices
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd.scatter_nd` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("scatter_nd", list(...), out,
                             input.names = c("data", "indices"))
}

#' Symbolic form of scatter_nd
#' @export
`mx.symbol.scatter_nd` <- function(...) {
  mx.internal.symbol.create("scatter_nd", list(...))
}

#' SequenceLast(data, sequence_length) with attrs axis, use_sequence_length — registered operator, 1 output.
#' @param data Input data
#' @param sequence_length Input sequence_length
#' @param axis int, optional, default='0'
#' @param use_sequence_length boolean, optional, default=0
#' @export
`mx.nd.sequence_last` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("sequence_last", list(...), out,
                             input.names = c("data", "sequence_length"))
}

#' Symbolic form of sequence_last
#' @export
`mx.symbol.sequence_last` <- function(...) {
  mx.internal.symbol.create("sequence_last", list(...))
}

#' SequenceMask(data, sequence_length) with attrs axis, use_sequence_length, value — registered operator, 1 output.
#' @param data Input data
#' @param sequence_length Input sequence_length
#' @param axis int, optional, default='0'
#' @param use_sequence_length boolean, optional, default=0
#' @param value float, optional, default=0
#' @export
`mx.nd.sequence_mask` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("sequence_mask", list(...), out,
                             input.names = c("data", "sequence_length"))
}

#' Symbolic form of sequence_mask
#' @export
`mx.symbol.sequence_mask` <- function(...) {
  mx.internal.symbol.create("sequence_mask", list(...))
}

#' SequenceReverse(data, sequence_length) with attrs axis, use_sequence_length — registered operator, 1 output.
#' @param data Input data
#' @param sequence_length Input sequence_length
#' @param axis int, optional, default='0'
#' @param use_sequence_length boolean, optional, default=0
#' @export
`mx.nd.sequence_reverse` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("sequence_reverse", list(...), out,
                             input.names = c("data", "sequence_length"))
}

#' Symbolic form of sequence_reverse
#' @export
`mx.symbol.sequence_reverse` <- function(...) {
  mx.internal.symbol.create("sequence_reverse", list(...))
}

#' sgd_mom_update(weight, grad, mom) with attrs clip_gradient, lazy_update, lr, momentum, rescale_grad, wd — registered operator, 2 outputs.
#' @param weight Input weight
#' @param grad Input grad
#' @param mom Input mom
#' @param clip_gradient float, optional, default=-1
#' @param lazy_update boolean, optional, default=1
#' @param lr float, optional, default=0.01
#' @param momentum float, optional, default=0
#' @param rescale_grad float, optional, default=1
#' @param wd float, optional, default=0
#' @export
`mx.nd.sgd_mom_update` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("sgd_mom_update", list(...), out,
                             input.names = c("weight", "grad", "mom"))
}

#' Symbolic form of sgd_mom_update
#' @export
`mx.symbol.sgd_mom_update` <- function(...) {
  mx.internal.symbol.create("sgd_mom_update", list(...))
}

#' sgd_update(weight, grad) with attrs clip_gradient, lazy_update, lr, rescale_grad, wd — registered operator, 1 output.
#' @param weight Input weight
#' @param grad Input grad
#' @param clip_gradient float, optional, default=-1
#' @param lazy_update boolean, optional, default=1
#' @param lr float, optional, default=0.01
#' @param rescale_grad float, optional, default=1
#' @param wd float, optional, default=0
#' @export
`mx.nd.sgd_update` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("sgd_update", list(...), out,
                             input.names = c("weight", "grad"))
}

#' Symbolic form of sgd_update
#' @export
`mx.symbol.sgd_update` <- function(...) {
  mx.internal.symbol.create("sgd_update", list(...))
}

#' shuffle(data) — registered operator, 1 output.
#' @param data Input data
#' @export
`mx.nd.shuffle` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("shuffle", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of shuffle
#' @export
`mx.symbol.shuffle` <- function(...) {
  mx.internal.symbol.create("shuffle", list(...))
}

#' Sigmoid activation function.
#' @param x Input x
#' @export
`mx.nd.sigmoid` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("sigmoid", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of sigmoid
#' @export
`mx.symbol.sigmoid` <- function(...) {
  mx.internal.symbol.create("sigmoid", list(...))
}

#' Return an element-wise indication of sign of the input.
#' @param x Input x
#' @export
`mx.nd.sign` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("sign", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of sign
#' @export
`mx.symbol.sign` <- function(...) {
  mx.internal.symbol.create("sign", list(...))
}

#' signsgd_update(weight, grad) with attrs clip_gradient, lr, rescale_grad, wd — registered operator, 1 output.
#' @param weight Input weight
#' @param grad Input grad
#' @param clip_gradient float, optional, default=-1
#' @param lr float, optional, default=0.01
#' @param rescale_grad float, optional, default=1
#' @param wd float, optional, default=0
#' @export
`mx.nd.signsgd_update` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("signsgd_update", list(...), out,
                             input.names = c("weight", "grad"))
}

#' Symbolic form of signsgd_update
#' @export
`mx.symbol.signsgd_update` <- function(...) {
  mx.internal.symbol.create("signsgd_update", list(...))
}

#' signum_update(weight, grad, mom) with attrs clip_gradient, lr, momentum, rescale_grad, wd, wd_lh — registered operator, 2 outputs.
#' @param weight Input weight
#' @param grad Input grad
#' @param mom Input mom
#' @param clip_gradient float, optional, default=-1
#' @param lr float, optional, default=0.01
#' @param momentum float, optional, default=0
#' @param rescale_grad float, optional, default=1
#' @param wd float, optional, default=0
#' @param wd_lh float, optional, default=0
#' @export
`mx.nd.signum_update` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("signum_update", list(...), out,
                             input.names = c("weight", "grad", "mom"))
}

#' Symbolic form of signum_update
#' @export
`mx.symbol.signum_update` <- function(...) {
  mx.internal.symbol.create("signum_update", list(...))
}

#' Compute a trigonometric sine of each element of input.
#' @param x Input x
#' @export
`mx.nd.sin` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("sin", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of sin
#' @export
`mx.symbol.sin` <- function(...) {
  mx.internal.symbol.create("sin", list(...))
}

#' Calculate element-wise hyperbolic sine of input.
#' @param x Input x
#' @export
`mx.nd.sinh` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("sinh", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of sinh
#' @export
`mx.symbol.sinh` <- function(...) {
  mx.internal.symbol.create("sinh", list(...))
}

#' slice(data) with attrs begin, end, step — registered operator, 1 output.
#' @param data Input data
#' @param begin Shape(tuple), optional, default=()
#' @param end Shape(tuple), optional, default=()
#' @param step Shape(tuple), optional, default=()
#' @export
`mx.nd.slice` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("slice", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of slice
#' @export
`mx.symbol.slice` <- function(...) {
  mx.internal.symbol.create("slice", list(...))
}

#' slice_axis(data) with attrs axis, begin, end — registered operator, 1 output.
#' @param data Input data
#' @param axis int, optional, default='0'
#' @param begin int, optional, default='0'
#' @param end string, optional, default='None'
#' @export
`mx.nd.slice_axis` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("slice_axis", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of slice_axis
#' @export
`mx.symbol.slice_axis` <- function(...) {
  mx.internal.symbol.create("slice_axis", list(...))
}

#' SliceChannel(data) with attrs axis, num_outputs, squeeze_axis — registered operator, 1 output.
#' @param data Input data
#' @param axis int, optional, default='1'
#' @param num_outputs int, optional, default='1'
#' @param squeeze_axis boolean, optional, default=0
#' @export
`mx.nd.slice_channel` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("slice_channel", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of slice_channel
#' @export
`mx.symbol.slice_channel` <- function(...) {
  mx.internal.symbol.create("slice_channel", list(...))
}

#' slice_like(data, shape_like) with attrs axes — registered operator, 1 output.
#' @param data Input data
#' @param shape_like Input shape_like
#' @param axes Shape(tuple), optional, default=()
#' @export
`mx.nd.slice_like` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("slice_like", list(...), out,
                             input.names = c("data", "shape_like"))
}

#' Symbolic form of slice_like
#' @export
`mx.symbol.slice_like` <- function(...) {
  mx.internal.symbol.create("slice_like", list(...))
}

#' f(x) = 0.5 (sx)^2/s^2... ref mshadow_op.h smooth_l1_loss: piecewise
#' @param data Input data
#' @param scalar float, optional, default=1
#' @export
`mx.nd.smooth_l1` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("smooth_l1", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of smooth_l1
#' @export
`mx.symbol.smooth_l1` <- function(...) {
  mx.internal.symbol.create("smooth_l1", list(...))
}

#' softmax(data) with attrs axis, length, temperature — registered operator, 1 output.
#' @param data Input data
#' @param axis int, optional, default='-1'
#' @param length string, optional, default='None'
#' @param temperature string, optional, default='None'
#' @export
`mx.nd.softmax` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("softmax", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of softmax
#' @export
`mx.symbol.softmax` <- function(...) {
  mx.internal.symbol.create("softmax", list(...))
}

#' SoftmaxActivation(data) with attrs mode — registered operator, 1 output.
#' @param data Input data
#' @param mode string, optional, default='instance'
#' @export
`mx.nd.softmax_activation` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("softmax_activation", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of softmax_activation
#' @export
`mx.symbol.softmax_activation` <- function(...) {
  mx.internal.symbol.create("softmax_activation", list(...))
}

#' softmax_cross_entropy(data, label) — registered operator, 1 output.
#' @param data Input data
#' @param label Input label
#' @export
`mx.nd.softmax_cross_entropy` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("softmax_cross_entropy", list(...), out,
                             input.names = c("data", "label"))
}

#' Symbolic form of softmax_cross_entropy
#' @export
`mx.symbol.softmax_cross_entropy` <- function(...) {
  mx.internal.symbol.create("softmax_cross_entropy", list(...))
}

#' Fused softmax + CE-grad head (ref: src/operator/softmax_output.cc).
#' @param data Input data
#' @param label Input label
#' @param grad_scale float, optional, default=1
#' @param ignore_label float, optional, default=-1
#' @param multi_output boolean, optional, default=0
#' @param normalization string, optional, default='null'
#' @param out_grad boolean, optional, default=0
#' @param preserve_shape boolean, optional, default=0
#' @param smooth_alpha float, optional, default=0
#' @param use_ignore boolean, optional, default=0
#' @export
`mx.nd.softmax_output` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("softmax_output", list(...), out,
                             input.names = c("data", "label"))
}

#' Symbolic form of softmax_output
#' @export
`mx.symbol.softmax_output` <- function(...) {
  mx.internal.symbol.create("softmax_output", list(...))
}

#' Soft-sign activation function.
#' @param x Input x
#' @export
`mx.nd.softsign` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("softsign", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of softsign
#' @export
`mx.symbol.softsign` <- function(...) {
  mx.internal.symbol.create("softsign", list(...))
}

#' sort(data) with attrs axis, is_ascend — registered operator, 1 output.
#' @param data Input data
#' @param axis int, optional, default='-1'
#' @param is_ascend boolean, optional, default=1
#' @export
`mx.nd.sort` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("sort", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of sort
#' @export
`mx.symbol.sort` <- function(...) {
  mx.internal.symbol.create("sort", list(...))
}

#' space_to_depth(data) with attrs block_size — registered operator, 1 output.
#' @param data Input data
#' @param block_size int, optional, default='1'
#' @export
`mx.nd.space_to_depth` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("space_to_depth", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of space_to_depth
#' @export
`mx.symbol.space_to_depth` <- function(...) {
  mx.internal.symbol.create("space_to_depth", list(...))
}

#' SpatialTransformer(data, loc) with attrs cudnn_off, sampler_type, target_shape, transform_type — registered operator, 1 output.
#' @param data Input data
#' @param loc Input loc
#' @param cudnn_off boolean, optional, default=0
#' @param sampler_type string, optional, default='bilinear'
#' @param target_shape Shape(tuple), optional, default=(0, 0)
#' @param transform_type string, optional, default='affine'
#' @export
`mx.nd.spatial_transformer` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("spatial_transformer", list(...), out,
                             input.names = c("data", "loc"))
}

#' Symbolic form of spatial_transformer
#' @export
`mx.symbol.spatial_transformer` <- function(...) {
  mx.internal.symbol.create("spatial_transformer", list(...))
}

#' SliceChannel(data) with attrs axis, num_outputs, squeeze_axis — registered operator, 1 output.
#' @param data Input data
#' @param axis int, optional, default='1'
#' @param num_outputs int, optional, default='1'
#' @param squeeze_axis boolean, optional, default=0
#' @export
`mx.nd.split` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("split", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of split
#' @export
`mx.symbol.split` <- function(...) {
  mx.internal.symbol.create("split", list(...))
}

#' Calculates element-wise non-negative square root of the input array.
#' @param x Input x
#' @export
`mx.nd.sqrt` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("sqrt", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of sqrt
#' @export
`mx.symbol.sqrt` <- function(...) {
  mx.internal.symbol.create("sqrt", list(...))
}

#' Calculate element-wise square of the input array.
#' @param x Input x
#' @export
`mx.nd.square` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("square", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of square
#' @export
`mx.symbol.square` <- function(...) {
  mx.internal.symbol.create("square", list(...))
}

#' squeeze(data) with attrs axis — registered operator, 1 output.
#' @param data Input data
#' @param axis string, optional, default='None'
#' @export
`mx.nd.squeeze` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("squeeze", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of squeeze
#' @export
`mx.symbol.squeeze` <- function(...) {
  mx.internal.symbol.create("squeeze", list(...))
}

#' stack(...) with attrs axis, num_args — registered operator, 1 output.
#' @param data List of input symbols
#' @param axis int, optional, default='0'
#' @param num_args string, optional, default='None'
#' @export
`mx.nd.stack` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("stack", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of stack
#' @export
`mx.symbol.stack` <- function(...) {
  mx.internal.symbol.create("stack", list(...))
}

#' BlockGrad(data) — registered operator, 1 output.
#' @param data Input data
#' @export
`mx.nd.stop_gradient` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("stop_gradient", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of stop_gradient
#' @export
`mx.symbol.stop_gradient` <- function(...) {
  mx.internal.symbol.create("stop_gradient", list(...))
}

#' sum(data) with attrs _fn, axis, exclude, keepdims — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param axis string, optional, default='None'
#' @param exclude boolean, optional, default=0
#' @param keepdims boolean, optional, default=0
#' @export
`mx.nd.sum` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("sum", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of sum
#' @export
`mx.symbol.sum` <- function(...) {
  mx.internal.symbol.create("sum", list(...))
}

#' sum(data) with attrs _fn, axis, exclude, keepdims — registered operator, 1 output.
#' @param data Input data
#' @param _fn string, optional
#' @param axis string, optional, default='None'
#' @param exclude boolean, optional, default=0
#' @param keepdims boolean, optional, default=0
#' @export
`mx.nd.sum_axis` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("sum_axis", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of sum_axis
#' @export
`mx.symbol.sum_axis` <- function(...) {
  mx.internal.symbol.create("sum_axis", list(...))
}

#' SVMOutput(data, label) with attrs margin, regularization_coefficient, use_linear — registered operator, 1 output.
#' @param data Input data
#' @param label Input label
#' @param margin float, optional, default=1
#' @param regularization_coefficient float, optional, default=1
#' @param use_linear boolean, optional, default=0
#' @export
`mx.nd.svm_output` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("svm_output", list(...), out,
                             input.names = c("data", "label"))
}

#' Symbolic form of svm_output
#' @export
`mx.symbol.svm_output` <- function(...) {
  mx.internal.symbol.create("svm_output", list(...))
}

#' swapaxes(data) with attrs dim1, dim2 — registered operator, 1 output.
#' @param data Input data
#' @param dim1 int, optional, default='0'
#' @param dim2 int, optional, default='0'
#' @export
`mx.nd.swapaxes` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("swapaxes", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of swapaxes
#' @export
`mx.symbol.swapaxes` <- function(...) {
  mx.internal.symbol.create("swapaxes", list(...))
}

#' take(a, indices) with attrs axis, mode — registered operator, 1 output.
#' @param a Input a
#' @param indices Input indices
#' @param axis int, optional, default='0'
#' @param mode string, optional, default='clip'
#' @export
`mx.nd.take` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("take", list(...), out,
                             input.names = c("a", "indices"))
}

#' Symbolic form of take
#' @export
`mx.symbol.take` <- function(...) {
  mx.internal.symbol.create("take", list(...))
}

#' Compute a trigonometric tangent of each element of input.
#' @param x Input x
#' @export
`mx.nd.tan` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("tan", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of tan
#' @export
`mx.symbol.tan` <- function(...) {
  mx.internal.symbol.create("tan", list(...))
}

#' Calculate element-wise hyperbolic tangent of input.
#' @param x Input x
#' @export
`mx.nd.tanh` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("tanh", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of tanh
#' @export
`mx.symbol.tanh` <- function(...) {
  mx.internal.symbol.create("tanh", list(...))
}

#' tile(data) with attrs reps — registered operator, 1 output.
#' @param data Input data
#' @param reps Shape(tuple), optional, default=()
#' @export
`mx.nd.tile` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("tile", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of tile
#' @export
`mx.symbol.tile` <- function(...) {
  mx.internal.symbol.create("tile", list(...))
}

#' topk(data) with attrs axis, dtype, is_ascend, k, ret_typ — registered operator, 1 output.
#' @param data Input data
#' @param axis int, optional, default='-1'
#' @param dtype string, optional, default='float32'
#' @param is_ascend boolean, optional, default=0
#' @param k int, optional, default='1'
#' @param ret_typ string, optional, default='indices'
#' @export
`mx.nd.topk` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("topk", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of topk
#' @export
`mx.symbol.topk` <- function(...) {
  mx.internal.symbol.create("topk", list(...))
}

#' transpose(data) with attrs axes — registered operator, 1 output.
#' @param data Input data
#' @param axes Shape(tuple), optional, default=()
#' @export
`mx.nd.transpose` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("transpose", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of transpose
#' @export
`mx.symbol.transpose` <- function(...) {
  mx.internal.symbol.create("transpose", list(...))
}

#' Round input to the nearest integer towards zero.
#' @param x Input x
#' @export
`mx.nd.trunc` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("trunc", list(...), out,
                             input.names = c("x"))
}

#' Symbolic form of trunc
#' @export
`mx.symbol.trunc` <- function(...) {
  mx.internal.symbol.create("trunc", list(...))
}

#' _random_uniform(...) with attrs ctx, dtype, high, low, shape — registered operator, 1 output.
#' @param ctx string, optional, default='None'
#' @param dtype string, optional, default='float32'
#' @param high float, optional, default=1
#' @param low float, optional, default=0
#' @param shape Shape(tuple), optional, default=()
#' @export
`mx.nd.uniform` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("uniform", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of uniform
#' @export
`mx.symbol.uniform` <- function(...) {
  mx.internal.symbol.create("uniform", list(...))
}

#' UpSampling(...) with attrs multi_input_mode, num_args, num_filter, sample_type, scale, workspace — registered operator, 1 output.
#' @param data List of input symbols
#' @param multi_input_mode string, optional, default='concat'
#' @param num_args int, optional, default='1'
#' @param num_filter int, optional, default='0'
#' @param sample_type string, optional, default='nearest'
#' @param scale int, optional, default='1'
#' @param workspace int, optional, default='512'
#' @export
`mx.nd.up_sampling` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("up_sampling", list(...), out,
                             input.names = NULL)
}

#' Symbolic form of up_sampling
#' @export
`mx.symbol.up_sampling` <- function(...) {
  mx.internal.symbol.create("up_sampling", list(...))
}

#' ref: src/operator/tensor/control_flow_op.cc (where).
#' @param condition Input condition
#' @param x Input x
#' @param y Input y
#' @export
`mx.nd.where` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("where", list(...), out,
                             input.names = c("condition", "x", "y"))
}

#' Symbolic form of where
#' @export
`mx.symbol.where` <- function(...) {
  mx.internal.symbol.create("where", list(...))
}

#' zeros_like(data) — registered operator, 1 output.
#' @param data Input data
#' @export
`mx.nd.zeros_like` <- function(..., out = NULL) {
  mx.internal.nd.op.dispatch("zeros_like", list(...), out,
                             input.names = c("data"))
}

#' Symbolic form of zeros_like
#' @export
`mx.symbol.zeros_like` <- function(...) {
  mx.internal.symbol.create("zeros_like", list(...))
}
