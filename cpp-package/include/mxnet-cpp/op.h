/*
 * Typed operator wrappers — GENERATED by scripts/op_wrapper_generator.py
 * from MXListAllOpNames + MXSymbolGetAtomicSymbolInfo (the reference's
 * OpWrapperGenerator.py pattern). Do not edit by hand; re-run the
 * generator after registering new operators.
 */
#ifndef MXNET_CPP_OP_H_
#define MXNET_CPP_OP_H_

#include <string>
#include <vector>

#include "MxNetCpp.h"

namespace mxnet {
namespace cpp {
namespace op {


/*!
 * \brief Activation(data) with attrs act_type — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param act_type 
 * \return new symbol
 */
inline Symbol Activation(const std::string& symbol_name,
    Symbol data,
    const std::string& act_type = "relu") {
  Operator op_("Activation");
  op_.SetInput("data", data);
  op_.SetParam("act_type", act_type);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief BatchNorm with running-stat update.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param gamma Input gamma
 * \param beta Input beta
 * \param moving_mean Input moving_mean
 * \param moving_var Input moving_var
 * \param __is_train__ 
 * \param axis 
 * \param cudnn_off 
 * \param eps 
 * \param fix_gamma 
 * \param momentum 
 * \param output_mean_var 
 * \param use_global_stats 
 * \return new symbol
 */
inline Symbol BatchNorm(const std::string& symbol_name,
    Symbol data,
    Symbol gamma,
    Symbol beta,
    Symbol moving_mean,
    Symbol moving_var,
    bool __is_train__ = 0,
    int axis = 1,
    bool cudnn_off = 0,
    mx_float eps = static_cast<mx_float>(0.001),
    bool fix_gamma = 1,
    mx_float momentum = static_cast<mx_float>(0.9),
    bool output_mean_var = 0,
    bool use_global_stats = 0) {
  Operator op_("BatchNorm");
  op_.SetInput("data", data);
  op_.SetInput("gamma", gamma);
  op_.SetInput("beta", beta);
  op_.SetInput("moving_mean", moving_mean);
  op_.SetInput("moving_var", moving_var);
  op_.SetParam("__is_train__", __is_train__);
  op_.SetParam("axis", axis);
  op_.SetParam("cudnn_off", cudnn_off);
  op_.SetParam("eps", eps);
  op_.SetParam("fix_gamma", fix_gamma);
  op_.SetParam("momentum", momentum);
  op_.SetParam("output_mean_var", output_mean_var);
  op_.SetParam("use_global_stats", use_global_stats);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief BatchNorm with running-stat update.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param gamma Input gamma
 * \param beta Input beta
 * \param moving_mean Input moving_mean
 * \param moving_var Input moving_var
 * \param __is_train__ 
 * \param axis 
 * \param cudnn_off 
 * \param eps 
 * \param fix_gamma 
 * \param momentum 
 * \param output_mean_var 
 * \param use_global_stats 
 * \return new symbol
 */
inline Symbol BatchNorm_v1(const std::string& symbol_name,
    Symbol data,
    Symbol gamma,
    Symbol beta,
    Symbol moving_mean,
    Symbol moving_var,
    bool __is_train__ = 0,
    int axis = 1,
    bool cudnn_off = 0,
    mx_float eps = static_cast<mx_float>(0.001),
    bool fix_gamma = 1,
    mx_float momentum = static_cast<mx_float>(0.9),
    bool output_mean_var = 0,
    bool use_global_stats = 0) {
  Operator op_("BatchNorm_v1");
  op_.SetInput("data", data);
  op_.SetInput("gamma", gamma);
  op_.SetInput("beta", beta);
  op_.SetInput("moving_mean", moving_mean);
  op_.SetInput("moving_var", moving_var);
  op_.SetParam("__is_train__", __is_train__);
  op_.SetParam("axis", axis);
  op_.SetParam("cudnn_off", cudnn_off);
  op_.SetParam("eps", eps);
  op_.SetParam("fix_gamma", fix_gamma);
  op_.SetParam("momentum", momentum);
  op_.SetParam("output_mean_var", output_mean_var);
  op_.SetParam("use_global_stats", use_global_stats);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief ref: src/operator/bilinear_sampler.cc — sample data at grid coords.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param grid Input grid
 * \param cudnn_off 
 * \return new symbol
 */
inline Symbol BilinearSampler(const std::string& symbol_name,
    Symbol data,
    Symbol grid,
    bool cudnn_off = 0) {
  Operator op_("BilinearSampler");
  op_.SetInput("data", data);
  op_.SetInput("grid", grid);
  op_.SetParam("cudnn_off", cudnn_off);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief BlockGrad(data) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \return new symbol
 */
inline Symbol BlockGrad(const std::string& symbol_name,
    Symbol data) {
  Operator op_("BlockGrad");
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief CTC negative log-likelihood via log-semiring forward scan.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param label Input label
 * \param blank_label 
 * \param data_lengths 
 * \param label_lengths 
 * \param use_data_lengths 
 * \param use_label_lengths 
 * \return new symbol
 */
inline Symbol CTCLoss(const std::string& symbol_name,
    Symbol data,
    Symbol label,
    const std::string& blank_label = "last",
    const std::string& data_lengths = "None",
    const std::string& label_lengths = "None",
    bool use_data_lengths = 0,
    bool use_label_lengths = 0) {
  Operator op_("CTCLoss");
  op_.SetInput("data", data);
  op_.SetInput("label", label);
  op_.SetParam("blank_label", blank_label);
  op_.SetParam("data_lengths", data_lengths);
  op_.SetParam("label_lengths", label_lengths);
  op_.SetParam("use_data_lengths", use_data_lengths);
  op_.SetParam("use_label_lengths", use_label_lengths);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Cast(data) with attrs dtype — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param dtype 
 * \return new symbol
 */
inline Symbol Cast(const std::string& symbol_name,
    Symbol data,
    const std::string& dtype = "float32") {
  Operator op_("Cast");
  op_.SetInput("data", data);
  op_.SetParam("dtype", dtype);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Concat(...) with attrs dim, num_args — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data variable-length input symbol list
 * \return new symbol
 */
inline Symbol Concat(const std::string& symbol_name,
    const std::vector<Symbol>& data,
    int dim = 1) {
  Operator op_("Concat");
  op_.SetParam("num_args", static_cast<int>(data.size()));
  op_.SetParam("dim", dim);
  for (const auto& s : data) op_.AddInput(s);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Convolution(data, weight, bias) with attrs cudnn_off, cudnn_tune, dilate, kernel, layout, no_bias, num_filter, num_group, pad, stride, workspace — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param weight Input weight
 * \param bias Input bias
 * \param cudnn_off 
 * \param cudnn_tune 
 * \param dilate 
 * \param kernel 
 * \param layout 
 * \param no_bias 
 * \param num_filter 
 * \param num_group 
 * \param pad 
 * \param stride 
 * \param workspace 
 * \return new symbol
 */
inline Symbol Convolution(const std::string& symbol_name,
    Symbol data,
    Symbol weight,
    Symbol bias = Symbol(),
    bool cudnn_off = 0,
    const std::string& cudnn_tune = "None",
    Shape dilate = Shape(),
    Shape kernel = Shape(),
    const std::string& layout = "None",
    bool no_bias = 0,
    int num_filter = 1,
    int num_group = 1,
    Shape pad = Shape(),
    Shape stride = Shape(),
    int workspace = 1024) {
  Operator op_("Convolution");
  op_.SetInput("data", data);
  op_.SetInput("weight", weight);
  if (!bias.IsNull()) op_.SetInput("bias", bias);
  op_.SetParam("cudnn_off", cudnn_off);
  op_.SetParam("cudnn_tune", cudnn_tune);
  op_.SetParam("dilate", dilate);
  op_.SetParam("kernel", kernel);
  op_.SetParam("layout", layout);
  op_.SetParam("no_bias", no_bias);
  op_.SetParam("num_filter", num_filter);
  op_.SetParam("num_group", num_group);
  op_.SetParam("pad", pad);
  op_.SetParam("stride", stride);
  op_.SetParam("workspace", workspace);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Convolution(data, weight, bias) with attrs cudnn_off, cudnn_tune, dilate, kernel, layout, no_bias, num_filter, num_group, pad, stride, workspace — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param weight Input weight
 * \param bias Input bias
 * \param cudnn_off 
 * \param cudnn_tune 
 * \param dilate 
 * \param kernel 
 * \param layout 
 * \param no_bias 
 * \param num_filter 
 * \param num_group 
 * \param pad 
 * \param stride 
 * \param workspace 
 * \return new symbol
 */
inline Symbol Convolution_v1(const std::string& symbol_name,
    Symbol data,
    Symbol weight,
    Symbol bias = Symbol(),
    bool cudnn_off = 0,
    const std::string& cudnn_tune = "None",
    Shape dilate = Shape(),
    Shape kernel = Shape(),
    const std::string& layout = "None",
    bool no_bias = 0,
    int num_filter = 1,
    int num_group = 1,
    Shape pad = Shape(),
    Shape stride = Shape(),
    int workspace = 1024) {
  Operator op_("Convolution_v1");
  op_.SetInput("data", data);
  op_.SetInput("weight", weight);
  if (!bias.IsNull()) op_.SetInput("bias", bias);
  op_.SetParam("cudnn_off", cudnn_off);
  op_.SetParam("cudnn_tune", cudnn_tune);
  op_.SetParam("dilate", dilate);
  op_.SetParam("kernel", kernel);
  op_.SetParam("layout", layout);
  op_.SetParam("no_bias", no_bias);
  op_.SetParam("num_filter", num_filter);
  op_.SetParam("num_group", num_group);
  op_.SetParam("pad", pad);
  op_.SetParam("stride", stride);
  op_.SetParam("workspace", workspace);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Cost-volume correlation (ref: src/operator/correlation.cc).
 * \param symbol_name name of the resulting symbol
 * \param data1 Input data1
 * \param data2 Input data2
 * \param is_multiply 
 * \param kernel_size 
 * \param max_displacement 
 * \param pad_size 
 * \param stride1 
 * \param stride2 
 * \return new symbol
 */
inline Symbol Correlation(const std::string& symbol_name,
    Symbol data1,
    Symbol data2,
    bool is_multiply = 1,
    int kernel_size = 1,
    int max_displacement = 1,
    int pad_size = 0,
    int stride1 = 1,
    int stride2 = 1) {
  Operator op_("Correlation");
  op_.SetInput("data1", data1);
  op_.SetInput("data2", data2);
  op_.SetParam("is_multiply", is_multiply);
  op_.SetParam("kernel_size", kernel_size);
  op_.SetParam("max_displacement", max_displacement);
  op_.SetParam("pad_size", pad_size);
  op_.SetParam("stride1", stride1);
  op_.SetParam("stride2", stride2);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Crop(...) with attrs center_crop, h_w, num_args, offset — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data variable-length input symbol list
 * \return new symbol
 */
inline Symbol Crop(const std::string& symbol_name,
    const std::vector<Symbol>& data,
    bool center_crop = 0,
    Shape h_w = Shape(std::vector<mx_uint>{0, 0}),
    Shape offset = Shape(std::vector<mx_uint>{0, 0})) {
  Operator op_("Crop");
  op_.SetParam("num_args", static_cast<int>(data.size()));
  op_.SetParam("center_crop", center_crop);
  op_.SetParam("h_w", h_w);
  op_.SetParam("offset", offset);
  for (const auto& s : data) op_.AddInput(s);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief BatchNorm with running-stat update.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param gamma Input gamma
 * \param beta Input beta
 * \param moving_mean Input moving_mean
 * \param moving_var Input moving_var
 * \param __is_train__ 
 * \param axis 
 * \param cudnn_off 
 * \param eps 
 * \param fix_gamma 
 * \param momentum 
 * \param output_mean_var 
 * \param use_global_stats 
 * \return new symbol
 */
inline Symbol CuDNNBatchNorm(const std::string& symbol_name,
    Symbol data,
    Symbol gamma,
    Symbol beta,
    Symbol moving_mean,
    Symbol moving_var,
    bool __is_train__ = 0,
    int axis = 1,
    bool cudnn_off = 0,
    mx_float eps = static_cast<mx_float>(0.001),
    bool fix_gamma = 1,
    mx_float momentum = static_cast<mx_float>(0.9),
    bool output_mean_var = 0,
    bool use_global_stats = 0) {
  Operator op_("CuDNNBatchNorm");
  op_.SetInput("data", data);
  op_.SetInput("gamma", gamma);
  op_.SetInput("beta", beta);
  op_.SetInput("moving_mean", moving_mean);
  op_.SetInput("moving_var", moving_var);
  op_.SetParam("__is_train__", __is_train__);
  op_.SetParam("axis", axis);
  op_.SetParam("cudnn_off", cudnn_off);
  op_.SetParam("eps", eps);
  op_.SetParam("fix_gamma", fix_gamma);
  op_.SetParam("momentum", momentum);
  op_.SetParam("output_mean_var", output_mean_var);
  op_.SetParam("use_global_stats", use_global_stats);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Apply a registered custom operator (ref: mx.nd.Custom).
 * \param symbol_name name of the resulting symbol
 * \param data variable-length input symbol list
 * \return new symbol
 */
inline Symbol Custom(const std::string& symbol_name,
    const std::vector<Symbol>& data,
    bool __is_train__ = 0,
    const std::string& op_type = "") {
  Operator op_("Custom");
  op_.SetParam("__is_train__", __is_train__);
  op_.SetParam("op_type", op_type);
  for (const auto& s : data) op_.AddInput(s);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Transposed convolution (ref: src/operator/nn/deconvolution.cc).
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param weight Input weight
 * \param bias Input bias
 * \param adj 
 * \param cudnn_off 
 * \param cudnn_tune 
 * \param dilate 
 * \param kernel 
 * \param layout 
 * \param no_bias 
 * \param num_filter 
 * \param num_group 
 * \param pad 
 * \param stride 
 * \param target_shape 
 * \param workspace 
 * \return new symbol
 */
inline Symbol Deconvolution(const std::string& symbol_name,
    Symbol data,
    Symbol weight,
    Symbol bias = Symbol(),
    Shape adj = Shape(),
    bool cudnn_off = 0,
    const std::string& cudnn_tune = "None",
    Shape dilate = Shape(),
    Shape kernel = Shape(),
    const std::string& layout = "None",
    bool no_bias = 1,
    int num_filter = 1,
    int num_group = 1,
    Shape pad = Shape(),
    Shape stride = Shape(),
    Shape target_shape = Shape(),
    int workspace = 1024) {
  Operator op_("Deconvolution");
  op_.SetInput("data", data);
  op_.SetInput("weight", weight);
  if (!bias.IsNull()) op_.SetInput("bias", bias);
  op_.SetParam("adj", adj);
  op_.SetParam("cudnn_off", cudnn_off);
  op_.SetParam("cudnn_tune", cudnn_tune);
  op_.SetParam("dilate", dilate);
  op_.SetParam("kernel", kernel);
  op_.SetParam("layout", layout);
  op_.SetParam("no_bias", no_bias);
  op_.SetParam("num_filter", num_filter);
  op_.SetParam("num_group", num_group);
  op_.SetParam("pad", pad);
  op_.SetParam("stride", stride);
  op_.SetParam("target_shape", target_shape);
  op_.SetParam("workspace", workspace);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Deformable convolution v1 (ref contrib/deformable_convolution-inl.h +
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param offset Input offset
 * \param weight Input weight
 * \param bias Input bias
 * \param dilate 
 * \param kernel 
 * \param layout 
 * \param no_bias 
 * \param num_deformable_group 
 * \param num_filter 
 * \param num_group 
 * \param pad 
 * \param stride 
 * \param workspace 
 * \return new symbol
 */
inline Symbol DeformableConvolution(const std::string& symbol_name,
    Symbol data,
    Symbol offset,
    Symbol weight,
    Symbol bias = Symbol(),
    Shape dilate = Shape(std::vector<mx_uint>{1, 1}),
    Shape kernel = Shape(std::vector<mx_uint>{3, 3}),
    const std::string& layout = "None",
    bool no_bias = 0,
    int num_deformable_group = 1,
    int num_filter = 1,
    int num_group = 1,
    Shape pad = Shape(std::vector<mx_uint>{0, 0}),
    Shape stride = Shape(std::vector<mx_uint>{1, 1}),
    int workspace = 1024) {
  Operator op_("DeformableConvolution");
  op_.SetInput("data", data);
  op_.SetInput("offset", offset);
  op_.SetInput("weight", weight);
  if (!bias.IsNull()) op_.SetInput("bias", bias);
  op_.SetParam("dilate", dilate);
  op_.SetParam("kernel", kernel);
  op_.SetParam("layout", layout);
  op_.SetParam("no_bias", no_bias);
  op_.SetParam("num_deformable_group", num_deformable_group);
  op_.SetParam("num_filter", num_filter);
  op_.SetParam("num_group", num_group);
  op_.SetParam("pad", pad);
  op_.SetParam("stride", stride);
  op_.SetParam("workspace", workspace);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Deformable position-sensitive ROI pooling (ref
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param rois Input rois
 * \param trans Input trans
 * \param group_size 
 * \param no_trans 
 * \param output_dim 
 * \param part_size 
 * \param pooled_size 
 * \param sample_per_part 
 * \param spatial_scale 
 * \param trans_std 
 * \return new symbol
 */
inline Symbol DeformablePSROIPooling(const std::string& symbol_name,
    Symbol data,
    Symbol rois,
    Symbol trans,
    int group_size = 1,
    bool no_trans = 0,
    int output_dim = 1,
    int part_size = 0,
    int pooled_size = 1,
    int sample_per_part = 1,
    mx_float spatial_scale = static_cast<mx_float>(1),
    mx_float trans_std = static_cast<mx_float>(0)) {
  Operator op_("DeformablePSROIPooling");
  op_.SetInput("data", data);
  op_.SetInput("rois", rois);
  op_.SetInput("trans", trans);
  op_.SetParam("group_size", group_size);
  op_.SetParam("no_trans", no_trans);
  op_.SetParam("output_dim", output_dim);
  op_.SetParam("part_size", part_size);
  op_.SetParam("pooled_size", pooled_size);
  op_.SetParam("sample_per_part", sample_per_part);
  op_.SetParam("spatial_scale", spatial_scale);
  op_.SetParam("trans_std", trans_std);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Dropout(data) with attrs __is_train__, axes, mode, p — registered operator, 2 outputs.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param __is_train__ 
 * \param axes 
 * \param mode 
 * \param p 
 * \return new symbol
 */
inline Symbol Dropout(const std::string& symbol_name,
    Symbol data,
    bool __is_train__ = 0,
    Shape axes = Shape(),
    const std::string& mode = "training",
    mx_float p = static_cast<mx_float>(0.5)) {
  Operator op_("Dropout");
  op_.SetInput("data", data);
  op_.SetParam("__is_train__", __is_train__);
  op_.SetParam("axes", axes);
  op_.SetParam("mode", mode);
  op_.SetParam("p", p);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Sum of N tensors in one op (ref src/operator/tensor/elemwise_sum.cc).
 * \param symbol_name name of the resulting symbol
 * \param data variable-length input symbol list
 * \return new symbol
 */
inline Symbol ElementWiseSum(const std::string& symbol_name,
    const std::vector<Symbol>& data) {
  Operator op_("ElementWiseSum");
  for (const auto& s : data) op_.AddInput(s);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Embedding lookup (ref: src/operator/tensor/indexing_op.cc Embedding).
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param weight Input weight
 * \param dtype 
 * \param input_dim 
 * \param output_dim 
 * \param sparse_grad 
 * \return new symbol
 */
inline Symbol Embedding(const std::string& symbol_name,
    Symbol data,
    Symbol weight,
    const std::string& dtype = "float32",
    const std::string& input_dim = "None",
    const std::string& output_dim = "None",
    bool sparse_grad = 0) {
  Operator op_("Embedding");
  op_.SetInput("data", data);
  op_.SetInput("weight", weight);
  op_.SetParam("dtype", dtype);
  op_.SetParam("input_dim", input_dim);
  op_.SetParam("output_dim", output_dim);
  op_.SetParam("sparse_grad", sparse_grad);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Flatten(data) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \return new symbol
 */
inline Symbol Flatten(const std::string& symbol_name,
    Symbol data) {
  Operator op_("Flatten");
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief FullyConnected(data, weight, bias) with attrs flatten, no_bias, num_hidden — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param weight Input weight
 * \param bias Input bias
 * \param flatten 
 * \param no_bias 
 * \param num_hidden 
 * \return new symbol
 */
inline Symbol FullyConnected(const std::string& symbol_name,
    Symbol data,
    Symbol weight,
    Symbol bias = Symbol(),
    bool flatten = 1,
    bool no_bias = 0,
    int num_hidden = 1) {
  Operator op_("FullyConnected");
  op_.SetInput("data", data);
  op_.SetInput("weight", weight);
  if (!bias.IsNull()) op_.SetInput("bias", bias);
  op_.SetParam("flatten", flatten);
  op_.SetParam("no_bias", no_bias);
  op_.SetParam("num_hidden", num_hidden);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Pre-activation bottleneck unit (BN-ReLU-conv ×3 + shortcut) as one
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param conv1_weight Input conv1_weight
 * \param conv2_weight Input conv2_weight
 * \param conv3_weight Input conv3_weight
 * \param bn1_gamma Input bn1_gamma
 * \param bn1_beta Input bn1_beta
 * \param bn2_gamma Input bn2_gamma
 * \param bn2_beta Input bn2_beta
 * \param bn3_gamma Input bn3_gamma
 * \param bn3_beta Input bn3_beta
 * \param bn1_moving_mean Input bn1_moving_mean
 * \param bn1_moving_var Input bn1_moving_var
 * \param bn2_moving_mean Input bn2_moving_mean
 * \param bn2_moving_var Input bn2_moving_var
 * \param bn3_moving_mean Input bn3_moving_mean
 * \param bn3_moving_var Input bn3_moving_var
 * \param sc_weight Input sc_weight
 * \param __is_train__ 
 * \param dim_match 
 * \param eps 
 * \param momentum 
 * \param num_filter 
 * \param stride 
 * \return new symbol
 */
inline Symbol FusedBottleneckUnit(const std::string& symbol_name,
    Symbol data,
    Symbol conv1_weight,
    Symbol conv2_weight,
    Symbol conv3_weight,
    Symbol bn1_gamma,
    Symbol bn1_beta,
    Symbol bn2_gamma,
    Symbol bn2_beta,
    Symbol bn3_gamma,
    Symbol bn3_beta,
    Symbol bn1_moving_mean,
    Symbol bn1_moving_var,
    Symbol bn2_moving_mean,
    Symbol bn2_moving_var,
    Symbol bn3_moving_mean,
    Symbol bn3_moving_var,
    Symbol sc_weight = Symbol(),
    bool __is_train__ = 0,
    bool dim_match = 1,
    mx_float eps = static_cast<mx_float>(2e-05),
    mx_float momentum = static_cast<mx_float>(0.9),
    int num_filter = 1,
    int stride = 1) {
  Operator op_("FusedBottleneckUnit");
  op_.SetInput("data", data);
  op_.SetInput("conv1_weight", conv1_weight);
  op_.SetInput("conv2_weight", conv2_weight);
  op_.SetInput("conv3_weight", conv3_weight);
  op_.SetInput("bn1_gamma", bn1_gamma);
  op_.SetInput("bn1_beta", bn1_beta);
  op_.SetInput("bn2_gamma", bn2_gamma);
  op_.SetInput("bn2_beta", bn2_beta);
  op_.SetInput("bn3_gamma", bn3_gamma);
  op_.SetInput("bn3_beta", bn3_beta);
  op_.SetInput("bn1_moving_mean", bn1_moving_mean);
  op_.SetInput("bn1_moving_var", bn1_moving_var);
  op_.SetInput("bn2_moving_mean", bn2_moving_mean);
  op_.SetInput("bn2_moving_var", bn2_moving_var);
  op_.SetInput("bn3_moving_mean", bn3_moving_mean);
  op_.SetInput("bn3_moving_var", bn3_moving_var);
  if (!sc_weight.IsNull()) op_.SetInput("sc_weight", sc_weight);
  op_.SetParam("__is_train__", __is_train__);
  op_.SetParam("dim_match", dim_match);
  op_.SetParam("eps", eps);
  op_.SetParam("momentum", momentum);
  op_.SetParam("num_filter", num_filter);
  op_.SetParam("stride", stride);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief GridGenerator(data) with attrs target_shape, transform_type — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param target_shape 
 * \param transform_type 
 * \return new symbol
 */
inline Symbol GridGenerator(const std::string& symbol_name,
    Symbol data,
    Shape target_shape = Shape(std::vector<mx_uint>{0, 0}),
    const std::string& transform_type = "affine") {
  Operator op_("GridGenerator");
  op_.SetInput("data", data);
  op_.SetParam("target_shape", target_shape);
  op_.SetParam("transform_type", transform_type);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief IdentityAttachKLSparseReg(data) with attrs momentum, penalty, sparseness_target — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param momentum 
 * \param penalty 
 * \param sparseness_target 
 * \return new symbol
 */
inline Symbol IdentityAttachKLSparseReg(const std::string& symbol_name,
    Symbol data,
    mx_float momentum = static_cast<mx_float>(0.9),
    mx_float penalty = static_cast<mx_float>(0.001),
    mx_float sparseness_target = static_cast<mx_float>(0.1)) {
  Operator op_("IdentityAttachKLSparseReg");
  op_.SetInput("data", data);
  op_.SetParam("momentum", momentum);
  op_.SetParam("penalty", penalty);
  op_.SetParam("sparseness_target", sparseness_target);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief InstanceNorm(data, gamma, beta) with attrs eps — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param gamma Input gamma
 * \param beta Input beta
 * \param eps 
 * \return new symbol
 */
inline Symbol InstanceNorm(const std::string& symbol_name,
    Symbol data,
    Symbol gamma,
    Symbol beta,
    mx_float eps = static_cast<mx_float>(0.001)) {
  Operator op_("InstanceNorm");
  op_.SetInput("data", data);
  op_.SetInput("gamma", gamma);
  op_.SetInput("beta", beta);
  op_.SetParam("eps", eps);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief L2Normalization(data) with attrs eps, mode — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param eps 
 * \param mode 
 * \return new symbol
 */
inline Symbol L2Normalization(const std::string& symbol_name,
    Symbol data,
    mx_float eps = static_cast<mx_float>(1e-10),
    const std::string& mode = "instance") {
  Operator op_("L2Normalization");
  op_.SetInput("data", data);
  op_.SetParam("eps", eps);
  op_.SetParam("mode", mode);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Local response norm across channels (ref: src/operator/lrn.cc).
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param alpha 
 * \param beta 
 * \param knorm 
 * \param nsize 
 * \return new symbol
 */
inline Symbol LRN(const std::string& symbol_name,
    Symbol data,
    mx_float alpha = static_cast<mx_float>(0.0001),
    mx_float beta = static_cast<mx_float>(0.75),
    mx_float knorm = static_cast<mx_float>(2),
    int nsize = 5) {
  Operator op_("LRN");
  op_.SetInput("data", data);
  op_.SetParam("alpha", alpha);
  op_.SetParam("beta", beta);
  op_.SetParam("knorm", knorm);
  op_.SetParam("nsize", nsize);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief LayerNorm(data, gamma, beta) with attrs axis, eps, output_mean_var — registered operator, 3 outputs.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param gamma Input gamma
 * \param beta Input beta
 * \param axis 
 * \param eps 
 * \param output_mean_var 
 * \return new symbol
 */
inline Symbol LayerNorm(const std::string& symbol_name,
    Symbol data,
    Symbol gamma,
    Symbol beta,
    int axis = -1,
    mx_float eps = static_cast<mx_float>(1e-05),
    bool output_mean_var = 0) {
  Operator op_("LayerNorm");
  op_.SetInput("data", data);
  op_.SetInput("gamma", gamma);
  op_.SetInput("beta", beta);
  op_.SetParam("axis", axis);
  op_.SetParam("eps", eps);
  op_.SetParam("output_mean_var", output_mean_var);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief ref: src/operator/leaky_relu.cc — leaky/prelu/elu/selu/rrelu/gelu.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param gamma Input gamma
 * \param __is_train__ 
 * \param act_type 
 * \param lower_bound 
 * \param slope 
 * \param upper_bound 
 * \return new symbol
 */
inline Symbol LeakyReLU(const std::string& symbol_name,
    Symbol data,
    Symbol gamma = Symbol(),
    bool __is_train__ = 0,
    const std::string& act_type = "leaky",
    mx_float lower_bound = static_cast<mx_float>(0.125),
    mx_float slope = static_cast<mx_float>(0.25),
    mx_float upper_bound = static_cast<mx_float>(0.334)) {
  Operator op_("LeakyReLU");
  op_.SetInput("data", data);
  if (!gamma.IsNull()) op_.SetInput("gamma", gamma);
  op_.SetParam("__is_train__", __is_train__);
  op_.SetParam("act_type", act_type);
  op_.SetParam("lower_bound", lower_bound);
  op_.SetParam("slope", slope);
  op_.SetParam("upper_bound", upper_bound);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief LinearRegressionOutput(data, label) with attrs grad_scale — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param label Input label
 * \param grad_scale 
 * \return new symbol
 */
inline Symbol LinearRegressionOutput(const std::string& symbol_name,
    Symbol data,
    Symbol label,
    mx_float grad_scale = static_cast<mx_float>(1)) {
  Operator op_("LinearRegressionOutput");
  op_.SetInput("data", data);
  op_.SetInput("label", label);
  op_.SetParam("grad_scale", grad_scale);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief LogisticRegressionOutput(data, label) with attrs grad_scale — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param label Input label
 * \param grad_scale 
 * \return new symbol
 */
inline Symbol LogisticRegressionOutput(const std::string& symbol_name,
    Symbol data,
    Symbol label,
    mx_float grad_scale = static_cast<mx_float>(1)) {
  Operator op_("LogisticRegressionOutput");
  op_.SetInput("data", data);
  op_.SetInput("label", label);
  op_.SetParam("grad_scale", grad_scale);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief MAERegressionOutput(data, label) with attrs grad_scale — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param label Input label
 * \param grad_scale 
 * \return new symbol
 */
inline Symbol MAERegressionOutput(const std::string& symbol_name,
    Symbol data,
    Symbol label,
    mx_float grad_scale = static_cast<mx_float>(1)) {
  Operator op_("MAERegressionOutput");
  op_.SetInput("data", data);
  op_.SetInput("label", label);
  op_.SetParam("grad_scale", grad_scale);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Loss head: forward identity, backward a constant grad_scale field.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param grad_scale 
 * \param normalization 
 * \param valid_thresh 
 * \return new symbol
 */
inline Symbol MakeLoss(const std::string& symbol_name,
    Symbol data,
    mx_float grad_scale = static_cast<mx_float>(1),
    const std::string& normalization = "null",
    mx_float valid_thresh = static_cast<mx_float>(0)) {
  Operator op_("MakeLoss");
  op_.SetInput("data", data);
  op_.SetParam("grad_scale", grad_scale);
  op_.SetParam("normalization", normalization);
  op_.SetParam("valid_thresh", valid_thresh);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Decode + NMS (ref: multibox_detection.cc). Vectorized XLA NMS.
 * \param symbol_name name of the resulting symbol
 * \param cls_prob Input cls_prob
 * \param loc_pred Input loc_pred
 * \param anchor Input anchor
 * \param background_id 
 * \param clip 
 * \param force_suppress 
 * \param nms_threshold 
 * \param nms_topk 
 * \param threshold 
 * \param variances 
 * \return new symbol
 */
inline Symbol MultiBoxDetection(const std::string& symbol_name,
    Symbol cls_prob,
    Symbol loc_pred,
    Symbol anchor,
    int background_id = 0,
    bool clip = 1,
    bool force_suppress = 0,
    mx_float nms_threshold = static_cast<mx_float>(0.5),
    int nms_topk = -1,
    mx_float threshold = static_cast<mx_float>(0.01),
    const std::string& variances = "(0.1,0.1,0.2,0.2)") {
  Operator op_("MultiBoxDetection");
  op_.SetInput("cls_prob", cls_prob);
  op_.SetInput("loc_pred", loc_pred);
  op_.SetInput("anchor", anchor);
  op_.SetParam("background_id", background_id);
  op_.SetParam("clip", clip);
  op_.SetParam("force_suppress", force_suppress);
  op_.SetParam("nms_threshold", nms_threshold);
  op_.SetParam("nms_topk", nms_topk);
  op_.SetParam("threshold", threshold);
  op_.SetParam("variances", variances);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Anchor-box generation (ref: multibox_prior.cc). Pure XLA.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param clip 
 * \param offsets 
 * \param ratios 
 * \param sizes 
 * \param steps 
 * \return new symbol
 */
inline Symbol MultiBoxPrior(const std::string& symbol_name,
    Symbol data,
    bool clip = 0,
    const std::string& offsets = "(0.5,0.5)",
    const std::string& ratios = "(1.0)",
    const std::string& sizes = "(1.0)",
    const std::string& steps = "(-1.0,-1.0)") {
  Operator op_("MultiBoxPrior");
  op_.SetInput("data", data);
  op_.SetParam("clip", clip);
  op_.SetParam("offsets", offsets);
  op_.SetParam("ratios", ratios);
  op_.SetParam("sizes", sizes);
  op_.SetParam("steps", steps);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Anchor→GT matching + box target encoding (ref: multibox_target.cc).
 * \param symbol_name name of the resulting symbol
 * \param anchor Input anchor
 * \param label Input label
 * \param cls_pred Input cls_pred
 * \param ignore_label 
 * \param minimum_negative_samples 
 * \param negative_mining_ratio 
 * \param negative_mining_thresh 
 * \param overlap_threshold 
 * \param variances 
 * \return new symbol
 */
inline Symbol MultiBoxTarget(const std::string& symbol_name,
    Symbol anchor,
    Symbol label,
    Symbol cls_pred,
    mx_float ignore_label = static_cast<mx_float>(-1),
    int minimum_negative_samples = 0,
    mx_float negative_mining_ratio = static_cast<mx_float>(-1),
    mx_float negative_mining_thresh = static_cast<mx_float>(0.5),
    mx_float overlap_threshold = static_cast<mx_float>(0.5),
    const std::string& variances = "(0.1,0.1,0.2,0.2)") {
  Operator op_("MultiBoxTarget");
  op_.SetInput("anchor", anchor);
  op_.SetInput("label", label);
  op_.SetInput("cls_pred", cls_pred);
  op_.SetParam("ignore_label", ignore_label);
  op_.SetParam("minimum_negative_samples", minimum_negative_samples);
  op_.SetParam("negative_mining_ratio", negative_mining_ratio);
  op_.SetParam("negative_mining_thresh", negative_mining_thresh);
  op_.SetParam("overlap_threshold", overlap_threshold);
  op_.SetParam("variances", variances);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Batched Proposal (ref contrib/multi_proposal.cc) — same math, all
 * \param symbol_name name of the resulting symbol
 * \param cls_prob Input cls_prob
 * \param bbox_pred Input bbox_pred
 * \param im_info Input im_info
 * \param feature_stride 
 * \param iou_loss 
 * \param output_score 
 * \param ratios 
 * \param rpn_min_size 
 * \param rpn_post_nms_top_n 
 * \param rpn_pre_nms_top_n 
 * \param scales 
 * \param threshold 
 * \return new symbol
 */
inline Symbol MultiProposal(const std::string& symbol_name,
    Symbol cls_prob,
    Symbol bbox_pred,
    Symbol im_info,
    int feature_stride = 16,
    bool iou_loss = 0,
    bool output_score = 0,
    const std::string& ratios = "(0.5,1.0,2.0)",
    int rpn_min_size = 16,
    int rpn_post_nms_top_n = 300,
    int rpn_pre_nms_top_n = 6000,
    const std::string& scales = "(4.0,8.0,16.0,32.0)",
    mx_float threshold = static_cast<mx_float>(0.7)) {
  Operator op_("MultiProposal");
  op_.SetInput("cls_prob", cls_prob);
  op_.SetInput("bbox_pred", bbox_pred);
  op_.SetInput("im_info", im_info);
  op_.SetParam("feature_stride", feature_stride);
  op_.SetParam("iou_loss", iou_loss);
  op_.SetParam("output_score", output_score);
  op_.SetParam("ratios", ratios);
  op_.SetParam("rpn_min_size", rpn_min_size);
  op_.SetParam("rpn_post_nms_top_n", rpn_post_nms_top_n);
  op_.SetParam("rpn_pre_nms_top_n", rpn_pre_nms_top_n);
  op_.SetParam("scales", scales);
  op_.SetParam("threshold", threshold);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Position-sensitive ROI average pooling (ref contrib/psroi_pooling-inl.h).
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param rois Input rois
 * \param group_size 
 * \param output_dim 
 * \param pooled_size 
 * \param spatial_scale 
 * \return new symbol
 */
inline Symbol PSROIPooling(const std::string& symbol_name,
    Symbol data,
    Symbol rois,
    int group_size = 0,
    int output_dim = 1,
    int pooled_size = 1,
    mx_float spatial_scale = static_cast<mx_float>(1)) {
  Operator op_("PSROIPooling");
  op_.SetInput("data", data);
  op_.SetInput("rois", rois);
  op_.SetParam("group_size", group_size);
  op_.SetParam("output_dim", output_dim);
  op_.SetParam("pooled_size", pooled_size);
  op_.SetParam("spatial_scale", spatial_scale);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief pad(data) with attrs constant_value, mode, pad_width — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param constant_value 
 * \param mode 
 * \param pad_width 
 * \return new symbol
 */
inline Symbol Pad(const std::string& symbol_name,
    Symbol data,
    mx_float constant_value = static_cast<mx_float>(0),
    const std::string& mode = "constant",
    Shape pad_width = Shape()) {
  Operator op_("Pad");
  op_.SetInput("data", data);
  op_.SetParam("constant_value", constant_value);
  op_.SetParam("mode", mode);
  op_.SetParam("pad_width", pad_width);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Pooling(data) with attrs cudnn_off, global_pool, kernel, pad, pool_type, pooling_convention, stride — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param cudnn_off 
 * \param global_pool 
 * \param kernel 
 * \param pad 
 * \param pool_type 
 * \param pooling_convention 
 * \param stride 
 * \return new symbol
 */
inline Symbol Pooling(const std::string& symbol_name,
    Symbol data,
    bool cudnn_off = 0,
    bool global_pool = 0,
    Shape kernel = Shape(),
    Shape pad = Shape(),
    const std::string& pool_type = "max",
    const std::string& pooling_convention = "valid",
    Shape stride = Shape()) {
  Operator op_("Pooling");
  op_.SetInput("data", data);
  op_.SetParam("cudnn_off", cudnn_off);
  op_.SetParam("global_pool", global_pool);
  op_.SetParam("kernel", kernel);
  op_.SetParam("pad", pad);
  op_.SetParam("pool_type", pool_type);
  op_.SetParam("pooling_convention", pooling_convention);
  op_.SetParam("stride", stride);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Pooling(data) with attrs cudnn_off, global_pool, kernel, pad, pool_type, pooling_convention, stride — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param cudnn_off 
 * \param global_pool 
 * \param kernel 
 * \param pad 
 * \param pool_type 
 * \param pooling_convention 
 * \param stride 
 * \return new symbol
 */
inline Symbol Pooling_v1(const std::string& symbol_name,
    Symbol data,
    bool cudnn_off = 0,
    bool global_pool = 0,
    Shape kernel = Shape(),
    Shape pad = Shape(),
    const std::string& pool_type = "max",
    const std::string& pooling_convention = "valid",
    Shape stride = Shape()) {
  Operator op_("Pooling_v1");
  op_.SetInput("data", data);
  op_.SetParam("cudnn_off", cudnn_off);
  op_.SetParam("global_pool", global_pool);
  op_.SetParam("kernel", kernel);
  op_.SetParam("pad", pad);
  op_.SetParam("pool_type", pool_type);
  op_.SetParam("pooling_convention", pooling_convention);
  op_.SetParam("stride", stride);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief RPN proposal generation (ref src/operator/contrib/proposal.cc).
 * \param symbol_name name of the resulting symbol
 * \param cls_prob Input cls_prob
 * \param bbox_pred Input bbox_pred
 * \param im_info Input im_info
 * \param feature_stride 
 * \param iou_loss 
 * \param output_score 
 * \param ratios 
 * \param rpn_min_size 
 * \param rpn_post_nms_top_n 
 * \param rpn_pre_nms_top_n 
 * \param scales 
 * \param threshold 
 * \return new symbol
 */
inline Symbol Proposal(const std::string& symbol_name,
    Symbol cls_prob,
    Symbol bbox_pred,
    Symbol im_info,
    int feature_stride = 16,
    bool iou_loss = 0,
    bool output_score = 0,
    const std::string& ratios = "(0.5,1.0,2.0)",
    int rpn_min_size = 16,
    int rpn_post_nms_top_n = 300,
    int rpn_pre_nms_top_n = 6000,
    const std::string& scales = "(4.0,8.0,16.0,32.0)",
    mx_float threshold = static_cast<mx_float>(0.7)) {
  Operator op_("Proposal");
  op_.SetInput("cls_prob", cls_prob);
  op_.SetInput("bbox_pred", bbox_pred);
  op_.SetInput("im_info", im_info);
  op_.SetParam("feature_stride", feature_stride);
  op_.SetParam("iou_loss", iou_loss);
  op_.SetParam("output_score", output_score);
  op_.SetParam("ratios", ratios);
  op_.SetParam("rpn_min_size", rpn_min_size);
  op_.SetParam("rpn_post_nms_top_n", rpn_post_nms_top_n);
  op_.SetParam("rpn_pre_nms_top_n", rpn_pre_nms_top_n);
  op_.SetParam("scales", scales);
  op_.SetParam("threshold", threshold);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Fused multi-layer (bi)RNN over the whole sequence.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param parameters Input parameters
 * \param state Input state
 * \param state_cell Input state_cell
 * \param __is_train__ 
 * \param bidirectional 
 * \param mode 
 * \param num_layers 
 * \param p 
 * \param state_outputs 
 * \param state_size 
 * \return new symbol
 */
inline Symbol RNN(const std::string& symbol_name,
    Symbol data,
    Symbol parameters,
    Symbol state,
    Symbol state_cell = Symbol(),
    bool __is_train__ = 0,
    bool bidirectional = 0,
    const std::string& mode = "lstm",
    int num_layers = 1,
    mx_float p = static_cast<mx_float>(0),
    bool state_outputs = 0,
    int state_size = 0) {
  Operator op_("RNN");
  op_.SetInput("data", data);
  op_.SetInput("parameters", parameters);
  op_.SetInput("state", state);
  if (!state_cell.IsNull()) op_.SetInput("state_cell", state_cell);
  op_.SetParam("__is_train__", __is_train__);
  op_.SetParam("bidirectional", bidirectional);
  op_.SetParam("mode", mode);
  op_.SetParam("num_layers", num_layers);
  op_.SetParam("p", p);
  op_.SetParam("state_outputs", state_outputs);
  op_.SetParam("state_size", state_size);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Max pooling over ROI bins (ref src/operator/roi_pooling-inl.h:51-128).
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param rois Input rois
 * \param pooled_size 
 * \param spatial_scale 
 * \return new symbol
 */
inline Symbol ROIPooling(const std::string& symbol_name,
    Symbol data,
    Symbol rois,
    Shape pooled_size = Shape(std::vector<mx_uint>{1, 1}),
    mx_float spatial_scale = static_cast<mx_float>(1)) {
  Operator op_("ROIPooling");
  op_.SetInput("data", data);
  op_.SetInput("rois", rois);
  op_.SetParam("pooled_size", pooled_size);
  op_.SetParam("spatial_scale", spatial_scale);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Reshape(data) with attrs reverse, shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param reverse 
 * \param shape 
 * \return new symbol
 */
inline Symbol Reshape(const std::string& symbol_name,
    Symbol data,
    bool reverse = 0,
    Shape shape = Shape()) {
  Operator op_("Reshape");
  op_.SetInput("data", data);
  op_.SetParam("reverse", reverse);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief SVMOutput(data, label) with attrs margin, regularization_coefficient, use_linear — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param label Input label
 * \param margin 
 * \param regularization_coefficient 
 * \param use_linear 
 * \return new symbol
 */
inline Symbol SVMOutput(const std::string& symbol_name,
    Symbol data,
    Symbol label,
    mx_float margin = static_cast<mx_float>(1),
    mx_float regularization_coefficient = static_cast<mx_float>(1),
    bool use_linear = 0) {
  Operator op_("SVMOutput");
  op_.SetInput("data", data);
  op_.SetInput("label", label);
  op_.SetParam("margin", margin);
  op_.SetParam("regularization_coefficient", regularization_coefficient);
  op_.SetParam("use_linear", use_linear);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief SequenceLast(data, sequence_length) with attrs axis, use_sequence_length — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param sequence_length Input sequence_length
 * \param axis 
 * \param use_sequence_length 
 * \return new symbol
 */
inline Symbol SequenceLast(const std::string& symbol_name,
    Symbol data,
    Symbol sequence_length = Symbol(),
    int axis = 0,
    bool use_sequence_length = 0) {
  Operator op_("SequenceLast");
  op_.SetInput("data", data);
  if (!sequence_length.IsNull()) op_.SetInput("sequence_length", sequence_length);
  op_.SetParam("axis", axis);
  op_.SetParam("use_sequence_length", use_sequence_length);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief SequenceMask(data, sequence_length) with attrs axis, use_sequence_length, value — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param sequence_length Input sequence_length
 * \param axis 
 * \param use_sequence_length 
 * \param value 
 * \return new symbol
 */
inline Symbol SequenceMask(const std::string& symbol_name,
    Symbol data,
    Symbol sequence_length = Symbol(),
    int axis = 0,
    bool use_sequence_length = 0,
    mx_float value = static_cast<mx_float>(0)) {
  Operator op_("SequenceMask");
  op_.SetInput("data", data);
  if (!sequence_length.IsNull()) op_.SetInput("sequence_length", sequence_length);
  op_.SetParam("axis", axis);
  op_.SetParam("use_sequence_length", use_sequence_length);
  op_.SetParam("value", value);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief SequenceReverse(data, sequence_length) with attrs axis, use_sequence_length — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param sequence_length Input sequence_length
 * \param axis 
 * \param use_sequence_length 
 * \return new symbol
 */
inline Symbol SequenceReverse(const std::string& symbol_name,
    Symbol data,
    Symbol sequence_length = Symbol(),
    int axis = 0,
    bool use_sequence_length = 0) {
  Operator op_("SequenceReverse");
  op_.SetInput("data", data);
  if (!sequence_length.IsNull()) op_.SetInput("sequence_length", sequence_length);
  op_.SetParam("axis", axis);
  op_.SetParam("use_sequence_length", use_sequence_length);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief SliceChannel(data) with attrs axis, num_outputs, squeeze_axis — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param axis 
 * \param num_outputs 
 * \param squeeze_axis 
 * \return new symbol
 */
inline Symbol SliceChannel(const std::string& symbol_name,
    Symbol data,
    int axis = 1,
    int num_outputs = 1,
    bool squeeze_axis = 0) {
  Operator op_("SliceChannel");
  op_.SetInput("data", data);
  op_.SetParam("axis", axis);
  op_.SetParam("num_outputs", num_outputs);
  op_.SetParam("squeeze_axis", squeeze_axis);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Fused softmax + CE-grad head (ref: src/operator/softmax_output.cc).
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param label Input label
 * \param grad_scale 
 * \param ignore_label 
 * \param multi_output 
 * \param normalization 
 * \param out_grad 
 * \param preserve_shape 
 * \param smooth_alpha 
 * \param use_ignore 
 * \return new symbol
 */
inline Symbol Softmax(const std::string& symbol_name,
    Symbol data,
    Symbol label,
    mx_float grad_scale = static_cast<mx_float>(1),
    mx_float ignore_label = static_cast<mx_float>(-1),
    bool multi_output = 0,
    const std::string& normalization = "null",
    bool out_grad = 0,
    bool preserve_shape = 0,
    mx_float smooth_alpha = static_cast<mx_float>(0),
    bool use_ignore = 0) {
  Operator op_("Softmax");
  op_.SetInput("data", data);
  op_.SetInput("label", label);
  op_.SetParam("grad_scale", grad_scale);
  op_.SetParam("ignore_label", ignore_label);
  op_.SetParam("multi_output", multi_output);
  op_.SetParam("normalization", normalization);
  op_.SetParam("out_grad", out_grad);
  op_.SetParam("preserve_shape", preserve_shape);
  op_.SetParam("smooth_alpha", smooth_alpha);
  op_.SetParam("use_ignore", use_ignore);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief SoftmaxActivation(data) with attrs mode — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param mode 
 * \return new symbol
 */
inline Symbol SoftmaxActivation(const std::string& symbol_name,
    Symbol data,
    const std::string& mode = "instance") {
  Operator op_("SoftmaxActivation");
  op_.SetInput("data", data);
  op_.SetParam("mode", mode);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Fused softmax + CE-grad head (ref: src/operator/softmax_output.cc).
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param label Input label
 * \param grad_scale 
 * \param ignore_label 
 * \param multi_output 
 * \param normalization 
 * \param out_grad 
 * \param preserve_shape 
 * \param smooth_alpha 
 * \param use_ignore 
 * \return new symbol
 */
inline Symbol SoftmaxOutput(const std::string& symbol_name,
    Symbol data,
    Symbol label,
    mx_float grad_scale = static_cast<mx_float>(1),
    mx_float ignore_label = static_cast<mx_float>(-1),
    bool multi_output = 0,
    const std::string& normalization = "null",
    bool out_grad = 0,
    bool preserve_shape = 0,
    mx_float smooth_alpha = static_cast<mx_float>(0),
    bool use_ignore = 0) {
  Operator op_("SoftmaxOutput");
  op_.SetInput("data", data);
  op_.SetInput("label", label);
  op_.SetParam("grad_scale", grad_scale);
  op_.SetParam("ignore_label", ignore_label);
  op_.SetParam("multi_output", multi_output);
  op_.SetParam("normalization", normalization);
  op_.SetParam("out_grad", out_grad);
  op_.SetParam("preserve_shape", preserve_shape);
  op_.SetParam("smooth_alpha", smooth_alpha);
  op_.SetParam("use_ignore", use_ignore);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief SpatialTransformer(data, loc) with attrs cudnn_off, sampler_type, target_shape, transform_type — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param loc Input loc
 * \param cudnn_off 
 * \param sampler_type 
 * \param target_shape 
 * \param transform_type 
 * \return new symbol
 */
inline Symbol SpatialTransformer(const std::string& symbol_name,
    Symbol data,
    Symbol loc,
    bool cudnn_off = 0,
    const std::string& sampler_type = "bilinear",
    Shape target_shape = Shape(std::vector<mx_uint>{0, 0}),
    const std::string& transform_type = "affine") {
  Operator op_("SpatialTransformer");
  op_.SetInput("data", data);
  op_.SetInput("loc", loc);
  op_.SetParam("cudnn_off", cudnn_off);
  op_.SetParam("sampler_type", sampler_type);
  op_.SetParam("target_shape", target_shape);
  op_.SetParam("transform_type", transform_type);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief swapaxes(data) with attrs dim1, dim2 — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param dim1 
 * \param dim2 
 * \return new symbol
 */
inline Symbol SwapAxis(const std::string& symbol_name,
    Symbol data,
    int dim1 = 0,
    int dim2 = 0) {
  Operator op_("SwapAxis");
  op_.SetInput("data", data);
  op_.SetParam("dim1", dim1);
  op_.SetParam("dim2", dim2);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief UpSampling(...) with attrs multi_input_mode, num_args, num_filter, sample_type, scale, workspace — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data variable-length input symbol list
 * \return new symbol
 */
inline Symbol UpSampling(const std::string& symbol_name,
    const std::vector<Symbol>& data,
    const std::string& multi_input_mode = "concat",
    int num_filter = 0,
    const std::string& sample_type = "nearest",
    int scale = 1,
    int workspace = 512) {
  Operator op_("UpSampling");
  op_.SetParam("num_args", static_cast<int>(data.size()));
  op_.SetParam("multi_input_mode", multi_input_mode);
  op_.SetParam("num_filter", num_filter);
  op_.SetParam("sample_type", sample_type);
  op_.SetParam("scale", scale);
  op_.SetParam("workspace", workspace);
  for (const auto& s : data) op_.AddInput(s);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Convolution with the residual add fused into its epilogue.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param weight Input weight
 * \param residual Input residual
 * \param bias Input bias
 * \param dilate 
 * \param kernel 
 * \param layout 
 * \param no_bias 
 * \param num_filter 
 * \param num_group 
 * \param pad 
 * \param stride 
 * \param workspace 
 * \return new symbol
 */
inline Symbol _ConvResidualAdd(const std::string& symbol_name,
    Symbol data,
    Symbol weight,
    Symbol residual,
    Symbol bias = Symbol(),
    Shape dilate = Shape(),
    Shape kernel = Shape(),
    const std::string& layout = "None",
    bool no_bias = 0,
    int num_filter = 1,
    int num_group = 1,
    Shape pad = Shape(),
    Shape stride = Shape(),
    int workspace = 1024) {
  Operator op_("_ConvResidualAdd");
  op_.SetInput("data", data);
  op_.SetInput("weight", weight);
  op_.SetInput("residual", residual);
  if (!bias.IsNull()) op_.SetInput("bias", bias);
  op_.SetParam("dilate", dilate);
  op_.SetParam("kernel", kernel);
  op_.SetParam("layout", layout);
  op_.SetParam("no_bias", no_bias);
  op_.SetParam("num_filter", num_filter);
  op_.SetParam("num_group", num_group);
  op_.SetParam("pad", pad);
  op_.SetParam("stride", stride);
  op_.SetParam("workspace", workspace);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Placement is XLA/pjit's job (ref PlaceDevice pass inserts these,
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \return new symbol
 */
inline Symbol _CrossDeviceCopy(const std::string& symbol_name,
    Symbol data) {
  Operator op_("_CrossDeviceCopy");
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_div(lhs, rhs) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \return new symbol
 */
inline Symbol _Div(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs) {
  Operator op_("_Div");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _div_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param scalar 
 * \return new symbol
 */
inline Symbol _DivScalar(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    mx_float scalar = static_cast<mx_float>(0)) {
  Operator op_("_DivScalar");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Return element-wise maximum of the input arrays.
 * \param symbol_name name of the resulting symbol
 * \param data variable-length input symbol list
 * \return new symbol
 */
inline Symbol _Maximum(const std::string& symbol_name,
    const std::vector<Symbol>& data,
    const std::string& out = "None",
    const std::string& where = "None") {
  Operator op_("_Maximum");
  op_.SetParam("out", out);
  op_.SetParam("where", where);
  for (const auto& s : data) op_.AddInput(s);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _maximum_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param scalar 
 * \return new symbol
 */
inline Symbol _MaximumScalar(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    mx_float scalar = static_cast<mx_float>(0)) {
  Operator op_("_MaximumScalar");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Return element-wise minimum of the input arrays.
 * \param symbol_name name of the resulting symbol
 * \param data variable-length input symbol list
 * \return new symbol
 */
inline Symbol _Minimum(const std::string& symbol_name,
    const std::vector<Symbol>& data,
    const std::string& out = "None",
    const std::string& where = "None") {
  Operator op_("_Minimum");
  op_.SetParam("out", out);
  op_.SetParam("where", where);
  for (const auto& s : data) op_.AddInput(s);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _minimum_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param scalar 
 * \return new symbol
 */
inline Symbol _MinimumScalar(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    mx_float scalar = static_cast<mx_float>(0)) {
  Operator op_("_MinimumScalar");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_sub(lhs, rhs) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \return new symbol
 */
inline Symbol _Minus(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs) {
  Operator op_("_Minus");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _minus_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param scalar 
 * \return new symbol
 */
inline Symbol _MinusScalar(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    mx_float scalar = static_cast<mx_float>(0)) {
  Operator op_("_MinusScalar");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_mul(lhs, rhs) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \return new symbol
 */
inline Symbol _Mul(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs) {
  Operator op_("_Mul");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _mul_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param scalar 
 * \return new symbol
 */
inline Symbol _MulScalar(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    mx_float scalar = static_cast<mx_float>(0)) {
  Operator op_("_MulScalar");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_add(lhs, rhs) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \return new symbol
 */
inline Symbol _Plus(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs) {
  Operator op_("_Plus");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _plus_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param scalar 
 * \return new symbol
 */
inline Symbol _PlusScalar(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    mx_float scalar = static_cast<mx_float>(0)) {
  Operator op_("_PlusScalar");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_power(lhs, rhs) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \return new symbol
 */
inline Symbol _Power(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs) {
  Operator op_("_Power");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _power_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param scalar 
 * \return new symbol
 */
inline Symbol _PowerScalar(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    mx_float scalar = static_cast<mx_float>(0)) {
  Operator op_("_PowerScalar");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _rdiv_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param scalar 
 * \return new symbol
 */
inline Symbol _RDivScalar(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    mx_float scalar = static_cast<mx_float>(0)) {
  Operator op_("_RDivScalar");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _rminus_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param scalar 
 * \return new symbol
 */
inline Symbol _RMinusScalar(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    mx_float scalar = static_cast<mx_float>(0)) {
  Operator op_("_RMinusScalar");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _rpower_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param scalar 
 * \return new symbol
 */
inline Symbol _RPowerScalar(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    mx_float scalar = static_cast<mx_float>(0)) {
  Operator op_("_RPowerScalar");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_add(lhs, rhs) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \return new symbol
 */
inline Symbol _add(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs) {
  Operator op_("_add");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _arange(...) with attrs ctx, dtype, infer_range, repeat, start, step, stop — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param ctx 
 * \param dtype 
 * \param infer_range 
 * \param repeat 
 * \param start 
 * \param step 
 * \param stop 
 * \return new symbol
 */
inline Symbol _arange(const std::string& symbol_name,
    const std::string& ctx = "None",
    const std::string& dtype = "float32",
    bool infer_range = 0,
    int repeat = 1,
    mx_float start = static_cast<mx_float>(0),
    mx_float step = static_cast<mx_float>(1),
    const std::string& stop = "None") {
  Operator op_("_arange");
  op_.SetParam("ctx", ctx);
  op_.SetParam("dtype", dtype);
  op_.SetParam("infer_range", infer_range);
  op_.SetParam("repeat", repeat);
  op_.SetParam("start", start);
  op_.SetParam("step", step);
  op_.SetParam("stop", stop);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief CTC negative log-likelihood via log-semiring forward scan.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param label Input label
 * \param blank_label 
 * \param data_lengths 
 * \param label_lengths 
 * \param use_data_lengths 
 * \param use_label_lengths 
 * \return new symbol
 */
inline Symbol _contrib_CTCLoss(const std::string& symbol_name,
    Symbol data,
    Symbol label,
    const std::string& blank_label = "last",
    const std::string& data_lengths = "None",
    const std::string& label_lengths = "None",
    bool use_data_lengths = 0,
    bool use_label_lengths = 0) {
  Operator op_("_contrib_CTCLoss");
  op_.SetInput("data", data);
  op_.SetInput("label", label);
  op_.SetParam("blank_label", blank_label);
  op_.SetParam("data_lengths", data_lengths);
  op_.SetParam("label_lengths", label_lengths);
  op_.SetParam("use_data_lengths", use_data_lengths);
  op_.SetParam("use_label_lengths", use_label_lengths);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Deformable convolution v1 (ref contrib/deformable_convolution-inl.h +
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param offset Input offset
 * \param weight Input weight
 * \param bias Input bias
 * \param dilate 
 * \param kernel 
 * \param layout 
 * \param no_bias 
 * \param num_deformable_group 
 * \param num_filter 
 * \param num_group 
 * \param pad 
 * \param stride 
 * \param workspace 
 * \return new symbol
 */
inline Symbol _contrib_DeformableConvolution(const std::string& symbol_name,
    Symbol data,
    Symbol offset,
    Symbol weight,
    Symbol bias = Symbol(),
    Shape dilate = Shape(std::vector<mx_uint>{1, 1}),
    Shape kernel = Shape(std::vector<mx_uint>{3, 3}),
    const std::string& layout = "None",
    bool no_bias = 0,
    int num_deformable_group = 1,
    int num_filter = 1,
    int num_group = 1,
    Shape pad = Shape(std::vector<mx_uint>{0, 0}),
    Shape stride = Shape(std::vector<mx_uint>{1, 1}),
    int workspace = 1024) {
  Operator op_("_contrib_DeformableConvolution");
  op_.SetInput("data", data);
  op_.SetInput("offset", offset);
  op_.SetInput("weight", weight);
  if (!bias.IsNull()) op_.SetInput("bias", bias);
  op_.SetParam("dilate", dilate);
  op_.SetParam("kernel", kernel);
  op_.SetParam("layout", layout);
  op_.SetParam("no_bias", no_bias);
  op_.SetParam("num_deformable_group", num_deformable_group);
  op_.SetParam("num_filter", num_filter);
  op_.SetParam("num_group", num_group);
  op_.SetParam("pad", pad);
  op_.SetParam("stride", stride);
  op_.SetParam("workspace", workspace);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Deformable position-sensitive ROI pooling (ref
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param rois Input rois
 * \param trans Input trans
 * \param group_size 
 * \param no_trans 
 * \param output_dim 
 * \param part_size 
 * \param pooled_size 
 * \param sample_per_part 
 * \param spatial_scale 
 * \param trans_std 
 * \return new symbol
 */
inline Symbol _contrib_DeformablePSROIPooling(const std::string& symbol_name,
    Symbol data,
    Symbol rois,
    Symbol trans,
    int group_size = 1,
    bool no_trans = 0,
    int output_dim = 1,
    int part_size = 0,
    int pooled_size = 1,
    int sample_per_part = 1,
    mx_float spatial_scale = static_cast<mx_float>(1),
    mx_float trans_std = static_cast<mx_float>(0)) {
  Operator op_("_contrib_DeformablePSROIPooling");
  op_.SetInput("data", data);
  op_.SetInput("rois", rois);
  op_.SetInput("trans", trans);
  op_.SetParam("group_size", group_size);
  op_.SetParam("no_trans", no_trans);
  op_.SetParam("output_dim", output_dim);
  op_.SetParam("part_size", part_size);
  op_.SetParam("pooled_size", pooled_size);
  op_.SetParam("sample_per_part", sample_per_part);
  op_.SetParam("spatial_scale", spatial_scale);
  op_.SetParam("trans_std", trans_std);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Decode + NMS (ref: multibox_detection.cc). Vectorized XLA NMS.
 * \param symbol_name name of the resulting symbol
 * \param cls_prob Input cls_prob
 * \param loc_pred Input loc_pred
 * \param anchor Input anchor
 * \param background_id 
 * \param clip 
 * \param force_suppress 
 * \param nms_threshold 
 * \param nms_topk 
 * \param threshold 
 * \param variances 
 * \return new symbol
 */
inline Symbol _contrib_MultiBoxDetection(const std::string& symbol_name,
    Symbol cls_prob,
    Symbol loc_pred,
    Symbol anchor,
    int background_id = 0,
    bool clip = 1,
    bool force_suppress = 0,
    mx_float nms_threshold = static_cast<mx_float>(0.5),
    int nms_topk = -1,
    mx_float threshold = static_cast<mx_float>(0.01),
    const std::string& variances = "(0.1,0.1,0.2,0.2)") {
  Operator op_("_contrib_MultiBoxDetection");
  op_.SetInput("cls_prob", cls_prob);
  op_.SetInput("loc_pred", loc_pred);
  op_.SetInput("anchor", anchor);
  op_.SetParam("background_id", background_id);
  op_.SetParam("clip", clip);
  op_.SetParam("force_suppress", force_suppress);
  op_.SetParam("nms_threshold", nms_threshold);
  op_.SetParam("nms_topk", nms_topk);
  op_.SetParam("threshold", threshold);
  op_.SetParam("variances", variances);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Anchor-box generation (ref: multibox_prior.cc). Pure XLA.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param clip 
 * \param offsets 
 * \param ratios 
 * \param sizes 
 * \param steps 
 * \return new symbol
 */
inline Symbol _contrib_MultiBoxPrior(const std::string& symbol_name,
    Symbol data,
    bool clip = 0,
    const std::string& offsets = "(0.5,0.5)",
    const std::string& ratios = "(1.0)",
    const std::string& sizes = "(1.0)",
    const std::string& steps = "(-1.0,-1.0)") {
  Operator op_("_contrib_MultiBoxPrior");
  op_.SetInput("data", data);
  op_.SetParam("clip", clip);
  op_.SetParam("offsets", offsets);
  op_.SetParam("ratios", ratios);
  op_.SetParam("sizes", sizes);
  op_.SetParam("steps", steps);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Anchor→GT matching + box target encoding (ref: multibox_target.cc).
 * \param symbol_name name of the resulting symbol
 * \param anchor Input anchor
 * \param label Input label
 * \param cls_pred Input cls_pred
 * \param ignore_label 
 * \param minimum_negative_samples 
 * \param negative_mining_ratio 
 * \param negative_mining_thresh 
 * \param overlap_threshold 
 * \param variances 
 * \return new symbol
 */
inline Symbol _contrib_MultiBoxTarget(const std::string& symbol_name,
    Symbol anchor,
    Symbol label,
    Symbol cls_pred,
    mx_float ignore_label = static_cast<mx_float>(-1),
    int minimum_negative_samples = 0,
    mx_float negative_mining_ratio = static_cast<mx_float>(-1),
    mx_float negative_mining_thresh = static_cast<mx_float>(0.5),
    mx_float overlap_threshold = static_cast<mx_float>(0.5),
    const std::string& variances = "(0.1,0.1,0.2,0.2)") {
  Operator op_("_contrib_MultiBoxTarget");
  op_.SetInput("anchor", anchor);
  op_.SetInput("label", label);
  op_.SetInput("cls_pred", cls_pred);
  op_.SetParam("ignore_label", ignore_label);
  op_.SetParam("minimum_negative_samples", minimum_negative_samples);
  op_.SetParam("negative_mining_ratio", negative_mining_ratio);
  op_.SetParam("negative_mining_thresh", negative_mining_thresh);
  op_.SetParam("overlap_threshold", overlap_threshold);
  op_.SetParam("variances", variances);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Batched Proposal (ref contrib/multi_proposal.cc) — same math, all
 * \param symbol_name name of the resulting symbol
 * \param cls_prob Input cls_prob
 * \param bbox_pred Input bbox_pred
 * \param im_info Input im_info
 * \param feature_stride 
 * \param iou_loss 
 * \param output_score 
 * \param ratios 
 * \param rpn_min_size 
 * \param rpn_post_nms_top_n 
 * \param rpn_pre_nms_top_n 
 * \param scales 
 * \param threshold 
 * \return new symbol
 */
inline Symbol _contrib_MultiProposal(const std::string& symbol_name,
    Symbol cls_prob,
    Symbol bbox_pred,
    Symbol im_info,
    int feature_stride = 16,
    bool iou_loss = 0,
    bool output_score = 0,
    const std::string& ratios = "(0.5,1.0,2.0)",
    int rpn_min_size = 16,
    int rpn_post_nms_top_n = 300,
    int rpn_pre_nms_top_n = 6000,
    const std::string& scales = "(4.0,8.0,16.0,32.0)",
    mx_float threshold = static_cast<mx_float>(0.7)) {
  Operator op_("_contrib_MultiProposal");
  op_.SetInput("cls_prob", cls_prob);
  op_.SetInput("bbox_pred", bbox_pred);
  op_.SetInput("im_info", im_info);
  op_.SetParam("feature_stride", feature_stride);
  op_.SetParam("iou_loss", iou_loss);
  op_.SetParam("output_score", output_score);
  op_.SetParam("ratios", ratios);
  op_.SetParam("rpn_min_size", rpn_min_size);
  op_.SetParam("rpn_post_nms_top_n", rpn_post_nms_top_n);
  op_.SetParam("rpn_pre_nms_top_n", rpn_pre_nms_top_n);
  op_.SetParam("scales", scales);
  op_.SetParam("threshold", threshold);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Position-sensitive ROI average pooling (ref contrib/psroi_pooling-inl.h).
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param rois Input rois
 * \param group_size 
 * \param output_dim 
 * \param pooled_size 
 * \param spatial_scale 
 * \return new symbol
 */
inline Symbol _contrib_PSROIPooling(const std::string& symbol_name,
    Symbol data,
    Symbol rois,
    int group_size = 0,
    int output_dim = 1,
    int pooled_size = 1,
    mx_float spatial_scale = static_cast<mx_float>(1)) {
  Operator op_("_contrib_PSROIPooling");
  op_.SetInput("data", data);
  op_.SetInput("rois", rois);
  op_.SetParam("group_size", group_size);
  op_.SetParam("output_dim", output_dim);
  op_.SetParam("pooled_size", pooled_size);
  op_.SetParam("spatial_scale", spatial_scale);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief RPN proposal generation (ref src/operator/contrib/proposal.cc).
 * \param symbol_name name of the resulting symbol
 * \param cls_prob Input cls_prob
 * \param bbox_pred Input bbox_pred
 * \param im_info Input im_info
 * \param feature_stride 
 * \param iou_loss 
 * \param output_score 
 * \param ratios 
 * \param rpn_min_size 
 * \param rpn_post_nms_top_n 
 * \param rpn_pre_nms_top_n 
 * \param scales 
 * \param threshold 
 * \return new symbol
 */
inline Symbol _contrib_Proposal(const std::string& symbol_name,
    Symbol cls_prob,
    Symbol bbox_pred,
    Symbol im_info,
    int feature_stride = 16,
    bool iou_loss = 0,
    bool output_score = 0,
    const std::string& ratios = "(0.5,1.0,2.0)",
    int rpn_min_size = 16,
    int rpn_post_nms_top_n = 300,
    int rpn_pre_nms_top_n = 6000,
    const std::string& scales = "(4.0,8.0,16.0,32.0)",
    mx_float threshold = static_cast<mx_float>(0.7)) {
  Operator op_("_contrib_Proposal");
  op_.SetInput("cls_prob", cls_prob);
  op_.SetInput("bbox_pred", bbox_pred);
  op_.SetInput("im_info", im_info);
  op_.SetParam("feature_stride", feature_stride);
  op_.SetParam("iou_loss", iou_loss);
  op_.SetParam("output_score", output_score);
  op_.SetParam("ratios", ratios);
  op_.SetParam("rpn_min_size", rpn_min_size);
  op_.SetParam("rpn_post_nms_top_n", rpn_post_nms_top_n);
  op_.SetParam("rpn_pre_nms_top_n", rpn_pre_nms_top_n);
  op_.SetParam("scales", scales);
  op_.SetParam("threshold", threshold);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Embedding lookup (ref: src/operator/tensor/indexing_op.cc Embedding).
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param weight Input weight
 * \param dtype 
 * \param input_dim 
 * \param output_dim 
 * \param sparse_grad 
 * \return new symbol
 */
inline Symbol _contrib_SparseEmbedding(const std::string& symbol_name,
    Symbol data,
    Symbol weight,
    const std::string& dtype = "float32",
    const std::string& input_dim = "None",
    const std::string& output_dim = "None",
    bool sparse_grad = 0) {
  Operator op_("_contrib_SparseEmbedding");
  op_.SetInput("data", data);
  op_.SetInput("weight", weight);
  op_.SetParam("dtype", dtype);
  op_.SetParam("input_dim", input_dim);
  op_.SetParam("output_dim", output_dim);
  op_.SetParam("sparse_grad", sparse_grad);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Count-sketch projection (ref: src/operator/contrib/count_sketch.cc).
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param h Input h
 * \param s Input s
 * \param out_dim 
 * \param processing_batch_size 
 * \return new symbol
 */
inline Symbol _contrib_count_sketch(const std::string& symbol_name,
    Symbol data,
    Symbol h,
    Symbol s,
    int out_dim = 0,
    int processing_batch_size = 32) {
  Operator op_("_contrib_count_sketch");
  op_.SetInput("data", data);
  op_.SetInput("h", h);
  op_.SetInput("s", s);
  op_.SetParam("out_dim", out_dim);
  op_.SetParam("processing_batch_size", processing_batch_size);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief CTC negative log-likelihood via log-semiring forward scan.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param label Input label
 * \param blank_label 
 * \param data_lengths 
 * \param label_lengths 
 * \param use_data_lengths 
 * \param use_label_lengths 
 * \return new symbol
 */
inline Symbol _contrib_ctc_loss(const std::string& symbol_name,
    Symbol data,
    Symbol label,
    const std::string& blank_label = "last",
    const std::string& data_lengths = "None",
    const std::string& label_lengths = "None",
    bool use_data_lengths = 0,
    bool use_label_lengths = 0) {
  Operator op_("_contrib_ctc_loss");
  op_.SetInput("data", data);
  op_.SetInput("label", label);
  op_.SetParam("blank_label", blank_label);
  op_.SetParam("data_lengths", data_lengths);
  op_.SetParam("label_lengths", label_lengths);
  op_.SetParam("use_data_lengths", use_data_lengths);
  op_.SetParam("use_label_lengths", use_label_lengths);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _contrib_dequantize(data, min_range, max_range) with attrs out_type — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param min_range Input min_range
 * \param max_range Input max_range
 * \param out_type 
 * \return new symbol
 */
inline Symbol _contrib_dequantize(const std::string& symbol_name,
    Symbol data,
    Symbol min_range,
    Symbol max_range,
    const std::string& out_type = "float32") {
  Operator op_("_contrib_dequantize");
  op_.SetInput("data", data);
  op_.SetInput("min_range", min_range);
  op_.SetInput("max_range", max_range);
  op_.SetParam("out_type", out_type);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _contrib_dequantize_2bit(data) with attrs threshold — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param threshold 
 * \return new symbol
 */
inline Symbol _contrib_dequantize_2bit(const std::string& symbol_name,
    Symbol data,
    mx_float threshold = static_cast<mx_float>(0.5)) {
  Operator op_("_contrib_dequantize_2bit");
  op_.SetInput("data", data);
  op_.SetParam("threshold", threshold);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief ref: src/operator/contrib/fft.cc (cuFFT) → XLA fft. Output packs
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param compute_size 
 * \return new symbol
 */
inline Symbol _contrib_fft(const std::string& symbol_name,
    Symbol data,
    int compute_size = 128) {
  Operator op_("_contrib_fft");
  op_.SetInput("data", data);
  op_.SetParam("compute_size", compute_size);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _contrib_ifft(data) with attrs compute_size — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param compute_size 
 * \return new symbol
 */
inline Symbol _contrib_ifft(const std::string& symbol_name,
    Symbol data,
    int compute_size = 128) {
  Operator op_("_contrib_ifft");
  op_.SetInput("data", data);
  op_.SetParam("compute_size", compute_size);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _contrib_quantize(data, min_range, max_range) with attrs out_type — registered operator, 3 outputs.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param min_range Input min_range
 * \param max_range Input max_range
 * \param out_type 
 * \return new symbol
 */
inline Symbol _contrib_quantize(const std::string& symbol_name,
    Symbol data,
    Symbol min_range,
    Symbol max_range,
    const std::string& out_type = "uint8") {
  Operator op_("_contrib_quantize");
  op_.SetInput("data", data);
  op_.SetInput("min_range", min_range);
  op_.SetInput("max_range", max_range);
  op_.SetParam("out_type", out_type);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _contrib_quantize_2bit(grad, residual) with attrs threshold — registered operator, 2 outputs.
 * \param symbol_name name of the resulting symbol
 * \param grad Input grad
 * \param residual Input residual
 * \param threshold 
 * \return new symbol
 */
inline Symbol _contrib_quantize_2bit(const std::string& symbol_name,
    Symbol grad,
    Symbol residual,
    mx_float threshold = static_cast<mx_float>(0.5)) {
  Operator op_("_contrib_quantize_2bit");
  op_.SetInput("grad", grad);
  op_.SetInput("residual", residual);
  op_.SetParam("threshold", threshold);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _copy(data) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \return new symbol
 */
inline Symbol _copy(const std::string& symbol_name,
    Symbol data) {
  Operator op_("_copy");
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_div(lhs, rhs) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \return new symbol
 */
inline Symbol _div(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs) {
  Operator op_("_div");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _div_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param scalar 
 * \return new symbol
 */
inline Symbol _div_scalar(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    mx_float scalar = static_cast<mx_float>(0)) {
  Operator op_("_div_scalar");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_equal(lhs, rhs) with attrs _fn — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \param _fn 
 * \return new symbol
 */
inline Symbol _equal(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs,
    const std::string& _fn = "None") {
  Operator op_("_equal");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  op_.SetParam("_fn", _fn);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _equal_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param scalar 
 * \return new symbol
 */
inline Symbol _equal_scalar(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    mx_float scalar = static_cast<mx_float>(0)) {
  Operator op_("_equal_scalar");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _eye(...) with attrs M, N, ctx, dtype, k — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param M 
 * \param N 
 * \param ctx 
 * \param dtype 
 * \param k 
 * \return new symbol
 */
inline Symbol _eye(const std::string& symbol_name,
    int M = 0,
    int N = 0,
    const std::string& ctx = "None",
    const std::string& dtype = "float32",
    int k = 0) {
  Operator op_("_eye");
  op_.SetParam("M", M);
  op_.SetParam("N", N);
  op_.SetParam("ctx", ctx);
  op_.SetParam("dtype", dtype);
  op_.SetParam("k", k);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _full(...) with attrs ctx, dtype, shape, value — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param ctx 
 * \param dtype 
 * \param shape 
 * \param value 
 * \return new symbol
 */
inline Symbol _full(const std::string& symbol_name,
    const std::string& ctx = "None",
    const std::string& dtype = "float32",
    Shape shape = Shape(),
    mx_float value = static_cast<mx_float>(0)) {
  Operator op_("_full");
  op_.SetParam("ctx", ctx);
  op_.SetParam("dtype", dtype);
  op_.SetParam("shape", shape);
  op_.SetParam("value", value);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_add(lhs, rhs) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \return new symbol
 */
inline Symbol _grad_add(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs) {
  Operator op_("_grad_add");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_greater(lhs, rhs) with attrs _fn — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \param _fn 
 * \return new symbol
 */
inline Symbol _greater(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs,
    const std::string& _fn = "None") {
  Operator op_("_greater");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  op_.SetParam("_fn", _fn);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_greater_equal(lhs, rhs) with attrs _fn — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \param _fn 
 * \return new symbol
 */
inline Symbol _greater_equal(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs,
    const std::string& _fn = "None") {
  Operator op_("_greater_equal");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  op_.SetParam("_fn", _fn);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _greater_equal_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param scalar 
 * \return new symbol
 */
inline Symbol _greater_equal_scalar(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    mx_float scalar = static_cast<mx_float>(0)) {
  Operator op_("_greater_equal_scalar");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _greater_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param scalar 
 * \return new symbol
 */
inline Symbol _greater_scalar(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    mx_float scalar = static_cast<mx_float>(0)) {
  Operator op_("_greater_scalar");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Return element-wise hypotenuse for the given legs of a right angle triangle.
 * \param symbol_name name of the resulting symbol
 * \param x1 Input x1
 * \param x2 Input x2
 * \return new symbol
 */
inline Symbol _hypot(const std::string& symbol_name,
    Symbol x1,
    Symbol x2) {
  Operator op_("_hypot");
  op_.SetInput("x1", x1);
  op_.SetInput("x2", x2);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _hypot_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param scalar 
 * \return new symbol
 */
inline Symbol _hypot_scalar(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    mx_float scalar = static_cast<mx_float>(0)) {
  Operator op_("_hypot_scalar");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _identity_with_attr_like_rhs(lhs, rhs) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \return new symbol
 */
inline Symbol _identity_with_attr_like_rhs(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs) {
  Operator op_("_identity_with_attr_like_rhs");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Convolution (NCHW x OIHW) on int8 operands; dequantized f32
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param weight Input weight
 * \param wscale Input wscale
 * \param bias Input bias
 * \param dilate 
 * \param kernel 
 * \param no_bias 
 * \param num_filter 
 * \param num_group 
 * \param pad 
 * \param scale 
 * \param stride 
 * \return new symbol
 */
inline Symbol _int8_convolution(const std::string& symbol_name,
    Symbol data,
    Symbol weight,
    Symbol wscale,
    Symbol bias = Symbol(),
    Shape dilate = Shape(),
    Shape kernel = Shape(),
    bool no_bias = 0,
    int num_filter = 1,
    int num_group = 1,
    Shape pad = Shape(),
    mx_float scale = static_cast<mx_float>(1),
    Shape stride = Shape()) {
  Operator op_("_int8_convolution");
  op_.SetInput("data", data);
  op_.SetInput("weight", weight);
  op_.SetInput("wscale", wscale);
  if (!bias.IsNull()) op_.SetInput("bias", bias);
  op_.SetParam("dilate", dilate);
  op_.SetParam("kernel", kernel);
  op_.SetParam("no_bias", no_bias);
  op_.SetParam("num_filter", num_filter);
  op_.SetParam("num_group", num_group);
  op_.SetParam("pad", pad);
  op_.SetParam("scale", scale);
  op_.SetParam("stride", stride);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief FullyConnected on int8 operands; dequantized f32 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param weight Input weight
 * \param wscale Input wscale
 * \param bias Input bias
 * \param flatten 
 * \param no_bias 
 * \param num_hidden 
 * \param scale 
 * \return new symbol
 */
inline Symbol _int8_fully_connected(const std::string& symbol_name,
    Symbol data,
    Symbol weight,
    Symbol wscale,
    Symbol bias = Symbol(),
    bool flatten = 1,
    bool no_bias = 0,
    int num_hidden = 1,
    mx_float scale = static_cast<mx_float>(1)) {
  Operator op_("_int8_fully_connected");
  op_.SetInput("data", data);
  op_.SetInput("weight", weight);
  op_.SetInput("wscale", wscale);
  if (!bias.IsNull()) op_.SetInput("bias", bias);
  op_.SetParam("flatten", flatten);
  op_.SetParam("no_bias", no_bias);
  op_.SetParam("num_hidden", num_hidden);
  op_.SetParam("scale", scale);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_lesser(lhs, rhs) with attrs _fn — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \param _fn 
 * \return new symbol
 */
inline Symbol _lesser(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs,
    const std::string& _fn = "None") {
  Operator op_("_lesser");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  op_.SetParam("_fn", _fn);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_lesser_equal(lhs, rhs) with attrs _fn — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \param _fn 
 * \return new symbol
 */
inline Symbol _lesser_equal(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs,
    const std::string& _fn = "None") {
  Operator op_("_lesser_equal");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  op_.SetParam("_fn", _fn);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _lesser_equal_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param scalar 
 * \return new symbol
 */
inline Symbol _lesser_equal_scalar(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    mx_float scalar = static_cast<mx_float>(0)) {
  Operator op_("_lesser_equal_scalar");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _lesser_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param scalar 
 * \return new symbol
 */
inline Symbol _lesser_scalar(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    mx_float scalar = static_cast<mx_float>(0)) {
  Operator op_("_lesser_scalar");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _linalg_extractdiag(A) with attrs offset — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param A Input A
 * \param offset 
 * \return new symbol
 */
inline Symbol _linalg_extractdiag(const std::string& symbol_name,
    Symbol A,
    int offset = 0) {
  Operator op_("_linalg_extractdiag");
  op_.SetInput("A", A);
  op_.SetParam("offset", offset);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _linalg_extracttrian(A) with attrs lower, offset — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param A Input A
 * \param lower 
 * \param offset 
 * \return new symbol
 */
inline Symbol _linalg_extracttrian(const std::string& symbol_name,
    Symbol A,
    bool lower = 1,
    int offset = 0) {
  Operator op_("_linalg_extracttrian");
  op_.SetInput("A", A);
  op_.SetParam("lower", lower);
  op_.SetParam("offset", offset);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief LQ factorization A = L @ Q with Q orthonormal rows (ref
 * \param symbol_name name of the resulting symbol
 * \param A Input A
 * \return new symbol
 */
inline Symbol _linalg_gelqf(const std::string& symbol_name,
    Symbol A) {
  Operator op_("_linalg_gelqf");
  op_.SetInput("A", A);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _linalg_gemm(A, B, C) with attrs alpha, axis, beta, transpose_a, transpose_b — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param A Input A
 * \param B Input B
 * \param C Input C
 * \param alpha 
 * \param axis 
 * \param beta 
 * \param transpose_a 
 * \param transpose_b 
 * \return new symbol
 */
inline Symbol _linalg_gemm(const std::string& symbol_name,
    Symbol A,
    Symbol B,
    Symbol C,
    mx_float alpha = static_cast<mx_float>(1),
    int axis = -2,
    mx_float beta = static_cast<mx_float>(1),
    bool transpose_a = 0,
    bool transpose_b = 0) {
  Operator op_("_linalg_gemm");
  op_.SetInput("A", A);
  op_.SetInput("B", B);
  op_.SetInput("C", C);
  op_.SetParam("alpha", alpha);
  op_.SetParam("axis", axis);
  op_.SetParam("beta", beta);
  op_.SetParam("transpose_a", transpose_a);
  op_.SetParam("transpose_b", transpose_b);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _linalg_gemm2(A, B) with attrs alpha, axis, transpose_a, transpose_b — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param A Input A
 * \param B Input B
 * \param alpha 
 * \param axis 
 * \param transpose_a 
 * \param transpose_b 
 * \return new symbol
 */
inline Symbol _linalg_gemm2(const std::string& symbol_name,
    Symbol A,
    Symbol B,
    mx_float alpha = static_cast<mx_float>(1),
    int axis = -2,
    bool transpose_a = 0,
    bool transpose_b = 0) {
  Operator op_("_linalg_gemm2");
  op_.SetInput("A", A);
  op_.SetInput("B", B);
  op_.SetParam("alpha", alpha);
  op_.SetParam("axis", axis);
  op_.SetParam("transpose_a", transpose_a);
  op_.SetParam("transpose_b", transpose_b);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _linalg_makediag(A) with attrs offset — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param A Input A
 * \param offset 
 * \return new symbol
 */
inline Symbol _linalg_makediag(const std::string& symbol_name,
    Symbol A,
    int offset = 0) {
  Operator op_("_linalg_makediag");
  op_.SetInput("A", A);
  op_.SetParam("offset", offset);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _linalg_potrf(A) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param A Input A
 * \return new symbol
 */
inline Symbol _linalg_potrf(const std::string& symbol_name,
    Symbol A) {
  Operator op_("_linalg_potrf");
  op_.SetInput("A", A);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _linalg_potri(A) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param A Input A
 * \return new symbol
 */
inline Symbol _linalg_potri(const std::string& symbol_name,
    Symbol A) {
  Operator op_("_linalg_potri");
  op_.SetInput("A", A);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _linalg_sumlogdiag(A) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param A Input A
 * \return new symbol
 */
inline Symbol _linalg_sumlogdiag(const std::string& symbol_name,
    Symbol A) {
  Operator op_("_linalg_sumlogdiag");
  op_.SetInput("A", A);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _linalg_syevd(A) — registered operator, 2 outputs.
 * \param symbol_name name of the resulting symbol
 * \param A Input A
 * \return new symbol
 */
inline Symbol _linalg_syevd(const std::string& symbol_name,
    Symbol A) {
  Operator op_("_linalg_syevd");
  op_.SetInput("A", A);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _linalg_syrk(A) with attrs alpha, transpose — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param A Input A
 * \param alpha 
 * \param transpose 
 * \return new symbol
 */
inline Symbol _linalg_syrk(const std::string& symbol_name,
    Symbol A,
    mx_float alpha = static_cast<mx_float>(1),
    bool transpose = 0) {
  Operator op_("_linalg_syrk");
  op_.SetInput("A", A);
  op_.SetParam("alpha", alpha);
  op_.SetParam("transpose", transpose);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _linalg_trmm(A, B) with attrs alpha, lower, rightside, transpose — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param A Input A
 * \param B Input B
 * \param alpha 
 * \param lower 
 * \param rightside 
 * \param transpose 
 * \return new symbol
 */
inline Symbol _linalg_trmm(const std::string& symbol_name,
    Symbol A,
    Symbol B,
    mx_float alpha = static_cast<mx_float>(1),
    bool lower = 1,
    bool rightside = 0,
    bool transpose = 0) {
  Operator op_("_linalg_trmm");
  op_.SetInput("A", A);
  op_.SetInput("B", B);
  op_.SetParam("alpha", alpha);
  op_.SetParam("lower", lower);
  op_.SetParam("rightside", rightside);
  op_.SetParam("transpose", transpose);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _linalg_trsm(A, B) with attrs alpha, lower, rightside, transpose — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param A Input A
 * \param B Input B
 * \param alpha 
 * \param lower 
 * \param rightside 
 * \param transpose 
 * \return new symbol
 */
inline Symbol _linalg_trsm(const std::string& symbol_name,
    Symbol A,
    Symbol B,
    mx_float alpha = static_cast<mx_float>(1),
    bool lower = 1,
    bool rightside = 0,
    bool transpose = 0) {
  Operator op_("_linalg_trsm");
  op_.SetInput("A", A);
  op_.SetInput("B", B);
  op_.SetParam("alpha", alpha);
  op_.SetParam("lower", lower);
  op_.SetParam("rightside", rightside);
  op_.SetParam("transpose", transpose);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_logical_and(lhs, rhs) with attrs _fn — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \param _fn 
 * \return new symbol
 */
inline Symbol _logical_and(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs,
    const std::string& _fn = "None") {
  Operator op_("_logical_and");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  op_.SetParam("_fn", _fn);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _logical_and_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param scalar 
 * \return new symbol
 */
inline Symbol _logical_and_scalar(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    mx_float scalar = static_cast<mx_float>(0)) {
  Operator op_("_logical_and_scalar");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_logical_or(lhs, rhs) with attrs _fn — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \param _fn 
 * \return new symbol
 */
inline Symbol _logical_or(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs,
    const std::string& _fn = "None") {
  Operator op_("_logical_or");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  op_.SetParam("_fn", _fn);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _logical_or_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param scalar 
 * \return new symbol
 */
inline Symbol _logical_or_scalar(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    mx_float scalar = static_cast<mx_float>(0)) {
  Operator op_("_logical_or_scalar");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_logical_xor(lhs, rhs) with attrs _fn — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \param _fn 
 * \return new symbol
 */
inline Symbol _logical_xor(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs,
    const std::string& _fn = "None") {
  Operator op_("_logical_xor");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  op_.SetParam("_fn", _fn);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _logical_xor_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param scalar 
 * \return new symbol
 */
inline Symbol _logical_xor_scalar(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    mx_float scalar = static_cast<mx_float>(0)) {
  Operator op_("_logical_xor_scalar");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Return element-wise maximum of the input arrays.
 * \param symbol_name name of the resulting symbol
 * \param data variable-length input symbol list
 * \return new symbol
 */
inline Symbol _maximum(const std::string& symbol_name,
    const std::vector<Symbol>& data,
    const std::string& out = "None",
    const std::string& where = "None") {
  Operator op_("_maximum");
  op_.SetParam("out", out);
  op_.SetParam("where", where);
  for (const auto& s : data) op_.AddInput(s);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _maximum_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param scalar 
 * \return new symbol
 */
inline Symbol _maximum_scalar(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    mx_float scalar = static_cast<mx_float>(0)) {
  Operator op_("_maximum_scalar");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Return element-wise minimum of the input arrays.
 * \param symbol_name name of the resulting symbol
 * \param data variable-length input symbol list
 * \return new symbol
 */
inline Symbol _minimum(const std::string& symbol_name,
    const std::vector<Symbol>& data,
    const std::string& out = "None",
    const std::string& where = "None") {
  Operator op_("_minimum");
  op_.SetParam("out", out);
  op_.SetParam("where", where);
  for (const auto& s : data) op_.AddInput(s);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _minimum_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param scalar 
 * \return new symbol
 */
inline Symbol _minimum_scalar(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    mx_float scalar = static_cast<mx_float>(0)) {
  Operator op_("_minimum_scalar");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_sub(lhs, rhs) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \return new symbol
 */
inline Symbol _minus(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs) {
  Operator op_("_minus");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _minus_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param scalar 
 * \return new symbol
 */
inline Symbol _minus_scalar(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    mx_float scalar = static_cast<mx_float>(0)) {
  Operator op_("_minus_scalar");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_mod(lhs, rhs) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \return new symbol
 */
inline Symbol _mod(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs) {
  Operator op_("_mod");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _mod_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param scalar 
 * \return new symbol
 */
inline Symbol _mod_scalar(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    mx_float scalar = static_cast<mx_float>(0)) {
  Operator op_("_mod_scalar");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_mul(lhs, rhs) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \return new symbol
 */
inline Symbol _mul(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs) {
  Operator op_("_mul");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _mul_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param scalar 
 * \return new symbol
 */
inline Symbol _mul_scalar(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    mx_float scalar = static_cast<mx_float>(0)) {
  Operator op_("_mul_scalar");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief negative(data) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \return new symbol
 */
inline Symbol _neg(const std::string& symbol_name,
    Symbol data) {
  Operator op_("_neg");
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_not_equal(lhs, rhs) with attrs _fn — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \param _fn 
 * \return new symbol
 */
inline Symbol _not_equal(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs,
    const std::string& _fn = "None") {
  Operator op_("_not_equal");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  op_.SetParam("_fn", _fn);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _not_equal_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param scalar 
 * \return new symbol
 */
inline Symbol _not_equal_scalar(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    mx_float scalar = static_cast<mx_float>(0)) {
  Operator op_("_not_equal_scalar");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _ones(...) with attrs ctx, dtype, shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param ctx 
 * \param dtype 
 * \param shape 
 * \return new symbol
 */
inline Symbol _ones(const std::string& symbol_name,
    const std::string& ctx = "None",
    const std::string& dtype = "float32",
    Shape shape = Shape()) {
  Operator op_("_ones");
  op_.SetParam("ctx", ctx);
  op_.SetParam("dtype", dtype);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_add(lhs, rhs) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \return new symbol
 */
inline Symbol _plus(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs) {
  Operator op_("_plus");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _plus_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param scalar 
 * \return new symbol
 */
inline Symbol _plus_scalar(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    mx_float scalar = static_cast<mx_float>(0)) {
  Operator op_("_plus_scalar");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_power(lhs, rhs) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \return new symbol
 */
inline Symbol _pow(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs) {
  Operator op_("_pow");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_power(lhs, rhs) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \return new symbol
 */
inline Symbol _power(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs) {
  Operator op_("_power");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _power_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param scalar 
 * \return new symbol
 */
inline Symbol _power_scalar(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    mx_float scalar = static_cast<mx_float>(0)) {
  Operator op_("_power_scalar");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Symmetric int8 quantization: round(clip(x / scale)) in
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param scale 
 * \return new symbol
 */
inline Symbol _quantize_int8(const std::string& symbol_name,
    Symbol data,
    mx_float scale = static_cast<mx_float>(1)) {
  Operator op_("_quantize_int8");
  op_.SetInput("data", data);
  op_.SetParam("scale", scale);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Per-output-channel (axis 0) symmetric int8 weight quantization.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \return new symbol
 */
inline Symbol _quantize_rows_int8(const std::string& symbol_name,
    Symbol data) {
  Operator op_("_quantize_rows_int8");
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _random_exponential(...) with attrs ctx, dtype, lam, shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param ctx 
 * \param dtype 
 * \param lam 
 * \param shape 
 * \return new symbol
 */
inline Symbol _random_exponential(const std::string& symbol_name,
    const std::string& ctx = "None",
    const std::string& dtype = "float32",
    mx_float lam = static_cast<mx_float>(1),
    Shape shape = Shape()) {
  Operator op_("_random_exponential");
  op_.SetParam("ctx", ctx);
  op_.SetParam("dtype", dtype);
  op_.SetParam("lam", lam);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _random_gamma(...) with attrs alpha, beta, ctx, dtype, shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param alpha 
 * \param beta 
 * \param ctx 
 * \param dtype 
 * \param shape 
 * \return new symbol
 */
inline Symbol _random_gamma(const std::string& symbol_name,
    mx_float alpha = static_cast<mx_float>(1),
    mx_float beta = static_cast<mx_float>(1),
    const std::string& ctx = "None",
    const std::string& dtype = "float32",
    Shape shape = Shape()) {
  Operator op_("_random_gamma");
  op_.SetParam("alpha", alpha);
  op_.SetParam("beta", beta);
  op_.SetParam("ctx", ctx);
  op_.SetParam("dtype", dtype);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _random_generalized_negative_binomial(...) with attrs alpha, ctx, dtype, mu, shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param alpha 
 * \param ctx 
 * \param dtype 
 * \param mu 
 * \param shape 
 * \return new symbol
 */
inline Symbol _random_generalized_negative_binomial(const std::string& symbol_name,
    mx_float alpha = static_cast<mx_float>(1),
    const std::string& ctx = "None",
    const std::string& dtype = "float32",
    mx_float mu = static_cast<mx_float>(1),
    Shape shape = Shape()) {
  Operator op_("_random_generalized_negative_binomial");
  op_.SetParam("alpha", alpha);
  op_.SetParam("ctx", ctx);
  op_.SetParam("dtype", dtype);
  op_.SetParam("mu", mu);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _random_negative_binomial(...) with attrs ctx, dtype, k, p, shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param ctx 
 * \param dtype 
 * \param k 
 * \param p 
 * \param shape 
 * \return new symbol
 */
inline Symbol _random_negative_binomial(const std::string& symbol_name,
    const std::string& ctx = "None",
    const std::string& dtype = "float32",
    int k = 1,
    mx_float p = static_cast<mx_float>(1),
    Shape shape = Shape()) {
  Operator op_("_random_negative_binomial");
  op_.SetParam("ctx", ctx);
  op_.SetParam("dtype", dtype);
  op_.SetParam("k", k);
  op_.SetParam("p", p);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _random_normal(...) with attrs ctx, dtype, loc, scale, shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param ctx 
 * \param dtype 
 * \param loc 
 * \param scale 
 * \param shape 
 * \return new symbol
 */
inline Symbol _random_normal(const std::string& symbol_name,
    const std::string& ctx = "None",
    const std::string& dtype = "float32",
    mx_float loc = static_cast<mx_float>(0),
    mx_float scale = static_cast<mx_float>(1),
    Shape shape = Shape()) {
  Operator op_("_random_normal");
  op_.SetParam("ctx", ctx);
  op_.SetParam("dtype", dtype);
  op_.SetParam("loc", loc);
  op_.SetParam("scale", scale);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _random_poisson(...) with attrs ctx, dtype, lam, shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param ctx 
 * \param dtype 
 * \param lam 
 * \param shape 
 * \return new symbol
 */
inline Symbol _random_poisson(const std::string& symbol_name,
    const std::string& ctx = "None",
    const std::string& dtype = "float32",
    mx_float lam = static_cast<mx_float>(1),
    Shape shape = Shape()) {
  Operator op_("_random_poisson");
  op_.SetParam("ctx", ctx);
  op_.SetParam("dtype", dtype);
  op_.SetParam("lam", lam);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _random_randint(...) with attrs ctx, dtype, high, low, shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param ctx 
 * \param dtype 
 * \param high 
 * \param low 
 * \param shape 
 * \return new symbol
 */
inline Symbol _random_randint(const std::string& symbol_name,
    const std::string& ctx = "None",
    const std::string& dtype = "int32",
    int high = 1,
    int low = 0,
    Shape shape = Shape()) {
  Operator op_("_random_randint");
  op_.SetParam("ctx", ctx);
  op_.SetParam("dtype", dtype);
  op_.SetParam("high", high);
  op_.SetParam("low", low);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _random_uniform(...) with attrs ctx, dtype, high, low, shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param ctx 
 * \param dtype 
 * \param high 
 * \param low 
 * \param shape 
 * \return new symbol
 */
inline Symbol _random_uniform(const std::string& symbol_name,
    const std::string& ctx = "None",
    const std::string& dtype = "float32",
    mx_float high = static_cast<mx_float>(1),
    mx_float low = static_cast<mx_float>(0),
    Shape shape = Shape()) {
  Operator op_("_random_uniform");
  op_.SetParam("ctx", ctx);
  op_.SetParam("dtype", dtype);
  op_.SetParam("high", high);
  op_.SetParam("low", low);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _rdiv_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param scalar 
 * \return new symbol
 */
inline Symbol _rdiv_scalar(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    mx_float scalar = static_cast<mx_float>(0)) {
  Operator op_("_rdiv_scalar");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _rminus_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param scalar 
 * \return new symbol
 */
inline Symbol _rminus_scalar(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    mx_float scalar = static_cast<mx_float>(0)) {
  Operator op_("_rminus_scalar");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _rmod_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param scalar 
 * \return new symbol
 */
inline Symbol _rmod_scalar(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    mx_float scalar = static_cast<mx_float>(0)) {
  Operator op_("_rmod_scalar");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _rpower_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param scalar 
 * \return new symbol
 */
inline Symbol _rpower_scalar(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    mx_float scalar = static_cast<mx_float>(0)) {
  Operator op_("_rpower_scalar");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _sample_exponential(lam) with attrs dtype, shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lam Input lam
 * \param dtype 
 * \param shape 
 * \return new symbol
 */
inline Symbol _sample_exponential(const std::string& symbol_name,
    Symbol lam,
    const std::string& dtype = "float32",
    Shape shape = Shape()) {
  Operator op_("_sample_exponential");
  op_.SetInput("lam", lam);
  op_.SetParam("dtype", dtype);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _sample_gamma(alpha, beta) with attrs dtype, shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param alpha Input alpha
 * \param beta Input beta
 * \param dtype 
 * \param shape 
 * \return new symbol
 */
inline Symbol _sample_gamma(const std::string& symbol_name,
    Symbol alpha,
    Symbol beta,
    const std::string& dtype = "float32",
    Shape shape = Shape()) {
  Operator op_("_sample_gamma");
  op_.SetInput("alpha", alpha);
  op_.SetInput("beta", beta);
  op_.SetParam("dtype", dtype);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Sample from categorical rows (ref: src/operator/random/multisample_op).
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param dtype 
 * \param get_prob 
 * \param shape 
 * \return new symbol
 */
inline Symbol _sample_multinomial(const std::string& symbol_name,
    Symbol data,
    const std::string& dtype = "int32",
    bool get_prob = 0,
    Shape shape = Shape()) {
  Operator op_("_sample_multinomial");
  op_.SetInput("data", data);
  op_.SetParam("dtype", dtype);
  op_.SetParam("get_prob", get_prob);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _sample_normal(mu, sigma) with attrs dtype, shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param mu Input mu
 * \param sigma Input sigma
 * \param dtype 
 * \param shape 
 * \return new symbol
 */
inline Symbol _sample_normal(const std::string& symbol_name,
    Symbol mu,
    Symbol sigma,
    const std::string& dtype = "float32",
    Shape shape = Shape()) {
  Operator op_("_sample_normal");
  op_.SetInput("mu", mu);
  op_.SetInput("sigma", sigma);
  op_.SetParam("dtype", dtype);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _sample_poisson(lam) with attrs dtype, shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lam Input lam
 * \param dtype 
 * \param shape 
 * \return new symbol
 */
inline Symbol _sample_poisson(const std::string& symbol_name,
    Symbol lam,
    const std::string& dtype = "float32",
    Shape shape = Shape()) {
  Operator op_("_sample_poisson");
  op_.SetInput("lam", lam);
  op_.SetParam("dtype", dtype);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _sample_uniform(low, high) with attrs dtype, shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param low Input low
 * \param high Input high
 * \param dtype 
 * \param shape 
 * \return new symbol
 */
inline Symbol _sample_uniform(const std::string& symbol_name,
    Symbol low,
    Symbol high,
    const std::string& dtype = "float32",
    Shape shape = Shape()) {
  Operator op_("_sample_uniform");
  op_.SetInput("low", low);
  op_.SetInput("high", high);
  op_.SetParam("dtype", dtype);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _scatter_elemwise_div(lhs, rhs) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \return new symbol
 */
inline Symbol _scatter_elemwise_div(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs) {
  Operator op_("_scatter_elemwise_div");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _minus_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param scalar 
 * \return new symbol
 */
inline Symbol _scatter_minus_scalar(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    mx_float scalar = static_cast<mx_float>(0)) {
  Operator op_("_scatter_minus_scalar");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _plus_scalar(data) with attrs _fn, scalar — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param scalar 
 * \return new symbol
 */
inline Symbol _scatter_plus_scalar(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    mx_float scalar = static_cast<mx_float>(0)) {
  Operator op_("_scatter_plus_scalar");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _scatter_set_nd(lhs, rhs, indices) with attrs shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \param indices Input indices
 * \param shape 
 * \return new symbol
 */
inline Symbol _scatter_set_nd(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs,
    Symbol indices,
    Shape shape = Shape()) {
  Operator op_("_scatter_set_nd");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  op_.SetInput("indices", indices);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief shuffle(data) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \return new symbol
 */
inline Symbol _shuffle(const std::string& symbol_name,
    Symbol data) {
  Operator op_("_shuffle");
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _slice_assign(lhs, rhs) with attrs begin, end, step — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \param begin 
 * \param end 
 * \param step 
 * \return new symbol
 */
inline Symbol _slice_assign(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs,
    Shape begin = Shape(),
    Shape end = Shape(),
    Shape step = Shape()) {
  Operator op_("_slice_assign");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  op_.SetParam("begin", begin);
  op_.SetParam("end", end);
  op_.SetParam("step", step);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _slice_assign_scalar(data) with attrs begin, end, scalar, step — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param begin 
 * \param end 
 * \param scalar 
 * \param step 
 * \return new symbol
 */
inline Symbol _slice_assign_scalar(const std::string& symbol_name,
    Symbol data,
    Shape begin = Shape(),
    Shape end = Shape(),
    mx_float scalar = static_cast<mx_float>(0),
    Shape step = Shape()) {
  Operator op_("_slice_assign_scalar");
  op_.SetInput("data", data);
  op_.SetParam("begin", begin);
  op_.SetParam("end", end);
  op_.SetParam("scalar", scalar);
  op_.SetParam("step", step);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Dense fallback of row-sparse retain: zero all rows not in `indices`
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param indices Input indices
 * \return new symbol
 */
inline Symbol _sparse_retain(const std::string& symbol_name,
    Symbol data,
    Symbol indices) {
  Operator op_("_sparse_retain");
  op_.SetInput("data", data);
  op_.SetInput("indices", indices);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief sum(data**2) — the row-sparse-aware square_sum (ref
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param axis 
 * \param exclude 
 * \param keepdims 
 * \return new symbol
 */
inline Symbol _square_sum(const std::string& symbol_name,
    Symbol data,
    const std::string& axis = "None",
    bool exclude = 0,
    bool keepdims = 0) {
  Operator op_("_square_sum");
  op_.SetInput("data", data);
  op_.SetParam("axis", axis);
  op_.SetParam("exclude", exclude);
  op_.SetParam("keepdims", keepdims);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_sub(lhs, rhs) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \return new symbol
 */
inline Symbol _sub(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs) {
  Operator op_("_sub");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Sum of N tensors in one op (ref src/operator/tensor/elemwise_sum.cc).
 * \param symbol_name name of the resulting symbol
 * \param data variable-length input symbol list
 * \return new symbol
 */
inline Symbol _sum(const std::string& symbol_name,
    const std::vector<Symbol>& data) {
  Operator op_("_sum");
  for (const auto& s : data) op_.AddInput(s);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _zeros(...) with attrs ctx, dtype, shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param ctx 
 * \param dtype 
 * \param shape 
 * \return new symbol
 */
inline Symbol _zeros(const std::string& symbol_name,
    const std::string& ctx = "None",
    const std::string& dtype = "float32",
    Shape shape = Shape()) {
  Operator op_("_zeros");
  op_.SetParam("ctx", ctx);
  op_.SetParam("dtype", dtype);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Alias of :func:`jax.numpy.absolute`.
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol abs(const std::string& symbol_name,
    Symbol x) {
  Operator op_("abs");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Activation(data) with attrs act_type — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param act_type 
 * \return new symbol
 */
inline Symbol activation(const std::string& symbol_name,
    Symbol data,
    const std::string& act_type = "relu") {
  Operator op_("activation");
  op_.SetInput("data", data);
  op_.SetParam("act_type", act_type);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief adam_update(weight, grad, mean, var) with attrs beta1, beta2, clip_gradient, epsilon, lazy_update, lr, rescale_grad, wd — registered operator, 3 outputs.
 * \param symbol_name name of the resulting symbol
 * \param weight Input weight
 * \param grad Input grad
 * \param mean Input mean
 * \param var Input var
 * \param beta1 
 * \param beta2 
 * \param clip_gradient 
 * \param epsilon 
 * \param lazy_update 
 * \param lr 
 * \param rescale_grad 
 * \param wd 
 * \return new symbol
 */
inline Symbol adam_update(const std::string& symbol_name,
    Symbol weight,
    Symbol grad,
    Symbol mean,
    Symbol var,
    mx_float beta1 = static_cast<mx_float>(0.9),
    mx_float beta2 = static_cast<mx_float>(0.999),
    mx_float clip_gradient = static_cast<mx_float>(-1),
    mx_float epsilon = static_cast<mx_float>(1e-08),
    bool lazy_update = 1,
    mx_float lr = static_cast<mx_float>(0.001),
    mx_float rescale_grad = static_cast<mx_float>(1),
    mx_float wd = static_cast<mx_float>(0)) {
  Operator op_("adam_update");
  op_.SetInput("weight", weight);
  op_.SetInput("grad", grad);
  op_.SetInput("mean", mean);
  op_.SetInput("var", var);
  op_.SetParam("beta1", beta1);
  op_.SetParam("beta2", beta2);
  op_.SetParam("clip_gradient", clip_gradient);
  op_.SetParam("epsilon", epsilon);
  op_.SetParam("lazy_update", lazy_update);
  op_.SetParam("lr", lr);
  op_.SetParam("rescale_grad", rescale_grad);
  op_.SetParam("wd", wd);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Sum of N tensors in one op (ref src/operator/tensor/elemwise_sum.cc).
 * \param symbol_name name of the resulting symbol
 * \param data variable-length input symbol list
 * \return new symbol
 */
inline Symbol add_n(const std::string& symbol_name,
    const std::vector<Symbol>& data) {
  Operator op_("add_n");
  for (const auto& s : data) op_.AddInput(s);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Compute element-wise inverse of trigonometric cosine of input.
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol arccos(const std::string& symbol_name,
    Symbol x) {
  Operator op_("arccos");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Calculate element-wise inverse of hyperbolic cosine of input.
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol arccosh(const std::string& symbol_name,
    Symbol x) {
  Operator op_("arccosh");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Compute element-wise inverse of trigonometric sine of input.
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol arcsin(const std::string& symbol_name,
    Symbol x) {
  Operator op_("arcsin");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Calculate element-wise inverse of hyperbolic sine of input.
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol arcsinh(const std::string& symbol_name,
    Symbol x) {
  Operator op_("arcsinh");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Compute element-wise inverse of trigonometric tangent of input.
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol arctan(const std::string& symbol_name,
    Symbol x) {
  Operator op_("arctan");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Calculate element-wise inverse of hyperbolic tangent of input.
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol arctanh(const std::string& symbol_name,
    Symbol x) {
  Operator op_("arctanh");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief argmax(data) with attrs axis, keepdims — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param axis 
 * \param keepdims 
 * \return new symbol
 */
inline Symbol argmax(const std::string& symbol_name,
    Symbol data,
    const std::string& axis = "None",
    bool keepdims = 0) {
  Operator op_("argmax");
  op_.SetInput("data", data);
  op_.SetParam("axis", axis);
  op_.SetParam("keepdims", keepdims);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief argmax_channel(data) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \return new symbol
 */
inline Symbol argmax_channel(const std::string& symbol_name,
    Symbol data) {
  Operator op_("argmax_channel");
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief argmin(data) with attrs axis, keepdims — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param axis 
 * \param keepdims 
 * \return new symbol
 */
inline Symbol argmin(const std::string& symbol_name,
    Symbol data,
    const std::string& axis = "None",
    bool keepdims = 0) {
  Operator op_("argmin");
  op_.SetInput("data", data);
  op_.SetParam("axis", axis);
  op_.SetParam("keepdims", keepdims);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief argsort(data) with attrs axis, dtype, is_ascend — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param axis 
 * \param dtype 
 * \param is_ascend 
 * \return new symbol
 */
inline Symbol argsort(const std::string& symbol_name,
    Symbol data,
    int axis = -1,
    const std::string& dtype = "float32",
    bool is_ascend = 1) {
  Operator op_("argsort");
  op_.SetInput("data", data);
  op_.SetParam("axis", axis);
  op_.SetParam("dtype", dtype);
  op_.SetParam("is_ascend", is_ascend);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief batch_dot(lhs, rhs) with attrs transpose_a, transpose_b — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \param transpose_a 
 * \param transpose_b 
 * \return new symbol
 */
inline Symbol batch_dot(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs,
    bool transpose_a = 0,
    bool transpose_b = 0) {
  Operator op_("batch_dot");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  op_.SetParam("transpose_a", transpose_a);
  op_.SetParam("transpose_b", transpose_b);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief BatchNorm with running-stat update.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param gamma Input gamma
 * \param beta Input beta
 * \param moving_mean Input moving_mean
 * \param moving_var Input moving_var
 * \param __is_train__ 
 * \param axis 
 * \param cudnn_off 
 * \param eps 
 * \param fix_gamma 
 * \param momentum 
 * \param output_mean_var 
 * \param use_global_stats 
 * \return new symbol
 */
inline Symbol batch_norm(const std::string& symbol_name,
    Symbol data,
    Symbol gamma,
    Symbol beta,
    Symbol moving_mean,
    Symbol moving_var,
    bool __is_train__ = 0,
    int axis = 1,
    bool cudnn_off = 0,
    mx_float eps = static_cast<mx_float>(0.001),
    bool fix_gamma = 1,
    mx_float momentum = static_cast<mx_float>(0.9),
    bool output_mean_var = 0,
    bool use_global_stats = 0) {
  Operator op_("batch_norm");
  op_.SetInput("data", data);
  op_.SetInput("gamma", gamma);
  op_.SetInput("beta", beta);
  op_.SetInput("moving_mean", moving_mean);
  op_.SetInput("moving_var", moving_var);
  op_.SetParam("__is_train__", __is_train__);
  op_.SetParam("axis", axis);
  op_.SetParam("cudnn_off", cudnn_off);
  op_.SetParam("eps", eps);
  op_.SetParam("fix_gamma", fix_gamma);
  op_.SetParam("momentum", momentum);
  op_.SetParam("output_mean_var", output_mean_var);
  op_.SetParam("use_global_stats", use_global_stats);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief batch_take(a, indices) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param a Input a
 * \param indices Input indices
 * \return new symbol
 */
inline Symbol batch_take(const std::string& symbol_name,
    Symbol a,
    Symbol indices) {
  Operator op_("batch_take");
  op_.SetInput("a", a);
  op_.SetInput("indices", indices);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief ref: src/operator/bilinear_sampler.cc — sample data at grid coords.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param grid Input grid
 * \param cudnn_off 
 * \return new symbol
 */
inline Symbol bilinear_sampler(const std::string& symbol_name,
    Symbol data,
    Symbol grid,
    bool cudnn_off = 0) {
  Operator op_("bilinear_sampler");
  op_.SetInput("data", data);
  op_.SetInput("grid", grid);
  op_.SetParam("cudnn_off", cudnn_off);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief BlockGrad(data) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \return new symbol
 */
inline Symbol block_grad(const std::string& symbol_name,
    Symbol data) {
  Operator op_("block_grad");
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_add(lhs, rhs) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \return new symbol
 */
inline Symbol broadcast_add(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs) {
  Operator op_("broadcast_add");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_axis(data) with attrs axis, size — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param axis 
 * \param size 
 * \return new symbol
 */
inline Symbol broadcast_axes(const std::string& symbol_name,
    Symbol data,
    Shape axis = Shape(),
    Shape size = Shape()) {
  Operator op_("broadcast_axes");
  op_.SetInput("data", data);
  op_.SetParam("axis", axis);
  op_.SetParam("size", size);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_axis(data) with attrs axis, size — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param axis 
 * \param size 
 * \return new symbol
 */
inline Symbol broadcast_axis(const std::string& symbol_name,
    Symbol data,
    Shape axis = Shape(),
    Shape size = Shape()) {
  Operator op_("broadcast_axis");
  op_.SetInput("data", data);
  op_.SetParam("axis", axis);
  op_.SetParam("size", size);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_div(lhs, rhs) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \return new symbol
 */
inline Symbol broadcast_div(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs) {
  Operator op_("broadcast_div");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_equal(lhs, rhs) with attrs _fn — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \param _fn 
 * \return new symbol
 */
inline Symbol broadcast_equal(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs,
    const std::string& _fn = "None") {
  Operator op_("broadcast_equal");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  op_.SetParam("_fn", _fn);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_greater(lhs, rhs) with attrs _fn — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \param _fn 
 * \return new symbol
 */
inline Symbol broadcast_greater(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs,
    const std::string& _fn = "None") {
  Operator op_("broadcast_greater");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  op_.SetParam("_fn", _fn);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_greater_equal(lhs, rhs) with attrs _fn — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \param _fn 
 * \return new symbol
 */
inline Symbol broadcast_greater_equal(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs,
    const std::string& _fn = "None") {
  Operator op_("broadcast_greater_equal");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  op_.SetParam("_fn", _fn);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Return element-wise hypotenuse for the given legs of a right angle triangle.
 * \param symbol_name name of the resulting symbol
 * \param x1 Input x1
 * \param x2 Input x2
 * \return new symbol
 */
inline Symbol broadcast_hypot(const std::string& symbol_name,
    Symbol x1,
    Symbol x2) {
  Operator op_("broadcast_hypot");
  op_.SetInput("x1", x1);
  op_.SetInput("x2", x2);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_lesser(lhs, rhs) with attrs _fn — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \param _fn 
 * \return new symbol
 */
inline Symbol broadcast_lesser(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs,
    const std::string& _fn = "None") {
  Operator op_("broadcast_lesser");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  op_.SetParam("_fn", _fn);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_lesser_equal(lhs, rhs) with attrs _fn — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \param _fn 
 * \return new symbol
 */
inline Symbol broadcast_lesser_equal(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs,
    const std::string& _fn = "None") {
  Operator op_("broadcast_lesser_equal");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  op_.SetParam("_fn", _fn);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_like(lhs, rhs) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \return new symbol
 */
inline Symbol broadcast_like(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs) {
  Operator op_("broadcast_like");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_logical_and(lhs, rhs) with attrs _fn — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \param _fn 
 * \return new symbol
 */
inline Symbol broadcast_logical_and(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs,
    const std::string& _fn = "None") {
  Operator op_("broadcast_logical_and");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  op_.SetParam("_fn", _fn);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_logical_or(lhs, rhs) with attrs _fn — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \param _fn 
 * \return new symbol
 */
inline Symbol broadcast_logical_or(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs,
    const std::string& _fn = "None") {
  Operator op_("broadcast_logical_or");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  op_.SetParam("_fn", _fn);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_logical_xor(lhs, rhs) with attrs _fn — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \param _fn 
 * \return new symbol
 */
inline Symbol broadcast_logical_xor(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs,
    const std::string& _fn = "None") {
  Operator op_("broadcast_logical_xor");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  op_.SetParam("_fn", _fn);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Return element-wise maximum of the input arrays.
 * \param symbol_name name of the resulting symbol
 * \param data variable-length input symbol list
 * \return new symbol
 */
inline Symbol broadcast_maximum(const std::string& symbol_name,
    const std::vector<Symbol>& data,
    const std::string& out = "None",
    const std::string& where = "None") {
  Operator op_("broadcast_maximum");
  op_.SetParam("out", out);
  op_.SetParam("where", where);
  for (const auto& s : data) op_.AddInput(s);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Return element-wise minimum of the input arrays.
 * \param symbol_name name of the resulting symbol
 * \param data variable-length input symbol list
 * \return new symbol
 */
inline Symbol broadcast_minimum(const std::string& symbol_name,
    const std::vector<Symbol>& data,
    const std::string& out = "None",
    const std::string& where = "None") {
  Operator op_("broadcast_minimum");
  op_.SetParam("out", out);
  op_.SetParam("where", where);
  for (const auto& s : data) op_.AddInput(s);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_mod(lhs, rhs) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \return new symbol
 */
inline Symbol broadcast_mod(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs) {
  Operator op_("broadcast_mod");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_mul(lhs, rhs) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \return new symbol
 */
inline Symbol broadcast_mul(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs) {
  Operator op_("broadcast_mul");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_not_equal(lhs, rhs) with attrs _fn — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \param _fn 
 * \return new symbol
 */
inline Symbol broadcast_not_equal(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs,
    const std::string& _fn = "None") {
  Operator op_("broadcast_not_equal");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  op_.SetParam("_fn", _fn);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_power(lhs, rhs) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \return new symbol
 */
inline Symbol broadcast_power(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs) {
  Operator op_("broadcast_power");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_sub(lhs, rhs) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \return new symbol
 */
inline Symbol broadcast_sub(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs) {
  Operator op_("broadcast_sub");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_to(data) with attrs shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param shape 
 * \return new symbol
 */
inline Symbol broadcast_to(const std::string& symbol_name,
    Symbol data,
    Shape shape = Shape()) {
  Operator op_("broadcast_to");
  op_.SetInput("data", data);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Cast(data) with attrs dtype — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param dtype 
 * \return new symbol
 */
inline Symbol cast(const std::string& symbol_name,
    Symbol data,
    const std::string& dtype = "float32") {
  Operator op_("cast");
  op_.SetInput("data", data);
  op_.SetParam("dtype", dtype);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Dense kernel is identity; stype conversion happens in the NDArray
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param stype 
 * \return new symbol
 */
inline Symbol cast_storage(const std::string& symbol_name,
    Symbol data,
    const std::string& stype = "default") {
  Operator op_("cast_storage");
  op_.SetInput("data", data);
  op_.SetParam("stype", stype);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Calculates element-wise cube root of the input array.
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol cbrt(const std::string& symbol_name,
    Symbol x) {
  Operator op_("cbrt");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Round input to the nearest integer upwards.
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol ceil(const std::string& symbol_name,
    Symbol x) {
  Operator op_("ceil");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief clip(data) with attrs a_max, a_min — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param a_max 
 * \param a_min 
 * \return new symbol
 */
inline Symbol clip(const std::string& symbol_name,
    Symbol data,
    mx_float a_max = static_cast<mx_float>(1),
    mx_float a_min = static_cast<mx_float>(0)) {
  Operator op_("clip");
  op_.SetInput("data", data);
  op_.SetParam("a_max", a_max);
  op_.SetParam("a_min", a_min);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Concat(...) with attrs dim, num_args — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data variable-length input symbol list
 * \return new symbol
 */
inline Symbol concat(const std::string& symbol_name,
    const std::vector<Symbol>& data,
    int dim = 1) {
  Operator op_("concat");
  op_.SetParam("num_args", static_cast<int>(data.size()));
  op_.SetParam("dim", dim);
  for (const auto& s : data) op_.AddInput(s);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Convolution(data, weight, bias) with attrs cudnn_off, cudnn_tune, dilate, kernel, layout, no_bias, num_filter, num_group, pad, stride, workspace — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param weight Input weight
 * \param bias Input bias
 * \param cudnn_off 
 * \param cudnn_tune 
 * \param dilate 
 * \param kernel 
 * \param layout 
 * \param no_bias 
 * \param num_filter 
 * \param num_group 
 * \param pad 
 * \param stride 
 * \param workspace 
 * \return new symbol
 */
inline Symbol convolution(const std::string& symbol_name,
    Symbol data,
    Symbol weight,
    Symbol bias = Symbol(),
    bool cudnn_off = 0,
    const std::string& cudnn_tune = "None",
    Shape dilate = Shape(),
    Shape kernel = Shape(),
    const std::string& layout = "None",
    bool no_bias = 0,
    int num_filter = 1,
    int num_group = 1,
    Shape pad = Shape(),
    Shape stride = Shape(),
    int workspace = 1024) {
  Operator op_("convolution");
  op_.SetInput("data", data);
  op_.SetInput("weight", weight);
  if (!bias.IsNull()) op_.SetInput("bias", bias);
  op_.SetParam("cudnn_off", cudnn_off);
  op_.SetParam("cudnn_tune", cudnn_tune);
  op_.SetParam("dilate", dilate);
  op_.SetParam("kernel", kernel);
  op_.SetParam("layout", layout);
  op_.SetParam("no_bias", no_bias);
  op_.SetParam("num_filter", num_filter);
  op_.SetParam("num_group", num_group);
  op_.SetParam("pad", pad);
  op_.SetParam("stride", stride);
  op_.SetParam("workspace", workspace);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Cost-volume correlation (ref: src/operator/correlation.cc).
 * \param symbol_name name of the resulting symbol
 * \param data1 Input data1
 * \param data2 Input data2
 * \param is_multiply 
 * \param kernel_size 
 * \param max_displacement 
 * \param pad_size 
 * \param stride1 
 * \param stride2 
 * \return new symbol
 */
inline Symbol correlation(const std::string& symbol_name,
    Symbol data1,
    Symbol data2,
    bool is_multiply = 1,
    int kernel_size = 1,
    int max_displacement = 1,
    int pad_size = 0,
    int stride1 = 1,
    int stride2 = 1) {
  Operator op_("correlation");
  op_.SetInput("data1", data1);
  op_.SetInput("data2", data2);
  op_.SetParam("is_multiply", is_multiply);
  op_.SetParam("kernel_size", kernel_size);
  op_.SetParam("max_displacement", max_displacement);
  op_.SetParam("pad_size", pad_size);
  op_.SetParam("stride1", stride1);
  op_.SetParam("stride2", stride2);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Compute a trigonometric cosine of each element of input.
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol cos(const std::string& symbol_name,
    Symbol x) {
  Operator op_("cos");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Calculate element-wise hyperbolic cosine of input.
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol cosh(const std::string& symbol_name,
    Symbol x) {
  Operator op_("cosh");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Crop(...) with attrs center_crop, h_w, num_args, offset — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data variable-length input symbol list
 * \return new symbol
 */
inline Symbol crop(const std::string& symbol_name,
    const std::vector<Symbol>& data,
    bool center_crop = 0,
    Shape h_w = Shape(std::vector<mx_uint>{0, 0}),
    Shape offset = Shape(std::vector<mx_uint>{0, 0})) {
  Operator op_("crop");
  op_.SetParam("num_args", static_cast<int>(data.size()));
  op_.SetParam("center_crop", center_crop);
  op_.SetParam("h_w", h_w);
  op_.SetParam("offset", offset);
  for (const auto& s : data) op_.AddInput(s);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief CTC negative log-likelihood via log-semiring forward scan.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param label Input label
 * \param blank_label 
 * \param data_lengths 
 * \param label_lengths 
 * \param use_data_lengths 
 * \param use_label_lengths 
 * \return new symbol
 */
inline Symbol ctc_loss(const std::string& symbol_name,
    Symbol data,
    Symbol label,
    const std::string& blank_label = "last",
    const std::string& data_lengths = "None",
    const std::string& label_lengths = "None",
    bool use_data_lengths = 0,
    bool use_label_lengths = 0) {
  Operator op_("ctc_loss");
  op_.SetInput("data", data);
  op_.SetInput("label", label);
  op_.SetParam("blank_label", blank_label);
  op_.SetParam("data_lengths", data_lengths);
  op_.SetParam("label_lengths", label_lengths);
  op_.SetParam("use_data_lengths", use_data_lengths);
  op_.SetParam("use_label_lengths", use_label_lengths);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief cumsum(a) with attrs axis, dtype — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param a Input a
 * \param axis 
 * \param dtype 
 * \return new symbol
 */
inline Symbol cumsum(const std::string& symbol_name,
    Symbol a,
    const std::string& axis = "None",
    const std::string& dtype = "None") {
  Operator op_("cumsum");
  op_.SetInput("a", a);
  op_.SetParam("axis", axis);
  op_.SetParam("dtype", dtype);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Transposed convolution (ref: src/operator/nn/deconvolution.cc).
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param weight Input weight
 * \param bias Input bias
 * \param adj 
 * \param cudnn_off 
 * \param cudnn_tune 
 * \param dilate 
 * \param kernel 
 * \param layout 
 * \param no_bias 
 * \param num_filter 
 * \param num_group 
 * \param pad 
 * \param stride 
 * \param target_shape 
 * \param workspace 
 * \return new symbol
 */
inline Symbol deconvolution(const std::string& symbol_name,
    Symbol data,
    Symbol weight,
    Symbol bias = Symbol(),
    Shape adj = Shape(),
    bool cudnn_off = 0,
    const std::string& cudnn_tune = "None",
    Shape dilate = Shape(),
    Shape kernel = Shape(),
    const std::string& layout = "None",
    bool no_bias = 1,
    int num_filter = 1,
    int num_group = 1,
    Shape pad = Shape(),
    Shape stride = Shape(),
    Shape target_shape = Shape(),
    int workspace = 1024) {
  Operator op_("deconvolution");
  op_.SetInput("data", data);
  op_.SetInput("weight", weight);
  if (!bias.IsNull()) op_.SetInput("bias", bias);
  op_.SetParam("adj", adj);
  op_.SetParam("cudnn_off", cudnn_off);
  op_.SetParam("cudnn_tune", cudnn_tune);
  op_.SetParam("dilate", dilate);
  op_.SetParam("kernel", kernel);
  op_.SetParam("layout", layout);
  op_.SetParam("no_bias", no_bias);
  op_.SetParam("num_filter", num_filter);
  op_.SetParam("num_group", num_group);
  op_.SetParam("pad", pad);
  op_.SetParam("stride", stride);
  op_.SetParam("target_shape", target_shape);
  op_.SetParam("workspace", workspace);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Alias of :func:`jax.numpy.rad2deg`
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol degrees(const std::string& symbol_name,
    Symbol x) {
  Operator op_("degrees");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief depth_to_space(data) with attrs block_size — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param block_size 
 * \return new symbol
 */
inline Symbol depth_to_space(const std::string& symbol_name,
    Symbol data,
    int block_size = 1) {
  Operator op_("depth_to_space");
  op_.SetInput("data", data);
  op_.SetParam("block_size", block_size);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief diag(data) with attrs k — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param k 
 * \return new symbol
 */
inline Symbol diag(const std::string& symbol_name,
    Symbol data,
    int k = 0) {
  Operator op_("diag");
  op_.SetInput("data", data);
  op_.SetParam("k", k);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief General tensor dot: contracts last axis of lhs with first of rhs
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \param transpose_a 
 * \param transpose_b 
 * \return new symbol
 */
inline Symbol dot(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs,
    bool transpose_a = 0,
    bool transpose_b = 0) {
  Operator op_("dot");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  op_.SetParam("transpose_a", transpose_a);
  op_.SetParam("transpose_b", transpose_b);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Dropout(data) with attrs __is_train__, axes, mode, p — registered operator, 2 outputs.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param __is_train__ 
 * \param axes 
 * \param mode 
 * \param p 
 * \return new symbol
 */
inline Symbol dropout(const std::string& symbol_name,
    Symbol data,
    bool __is_train__ = 0,
    Shape axes = Shape(),
    const std::string& mode = "training",
    mx_float p = static_cast<mx_float>(0.5)) {
  Operator op_("dropout");
  op_.SetInput("data", data);
  op_.SetParam("__is_train__", __is_train__);
  op_.SetParam("axes", axes);
  op_.SetParam("mode", mode);
  op_.SetParam("p", p);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_add(lhs, rhs) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \return new symbol
 */
inline Symbol elemwise_add(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs) {
  Operator op_("elemwise_add");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_div(lhs, rhs) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \return new symbol
 */
inline Symbol elemwise_div(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs) {
  Operator op_("elemwise_div");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_mul(lhs, rhs) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \return new symbol
 */
inline Symbol elemwise_mul(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs) {
  Operator op_("elemwise_mul");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief broadcast_sub(lhs, rhs) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \return new symbol
 */
inline Symbol elemwise_sub(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs) {
  Operator op_("elemwise_sub");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief The error function
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol erf(const std::string& symbol_name,
    Symbol x) {
  Operator op_("erf");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief The inverse of the error function
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol erfinv(const std::string& symbol_name,
    Symbol x) {
  Operator op_("erfinv");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Calculate element-wise exponential of the input.
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol exp(const std::string& symbol_name,
    Symbol x) {
  Operator op_("exp");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief expand_dims(data) with attrs axis — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param axis 
 * \return new symbol
 */
inline Symbol expand_dims(const std::string& symbol_name,
    Symbol data,
    int axis = 0) {
  Operator op_("expand_dims");
  op_.SetInput("data", data);
  op_.SetParam("axis", axis);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Calculate ``exp(x)-1`` of each element of the input.
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol expm1(const std::string& symbol_name,
    Symbol x) {
  Operator op_("expm1");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Round input to the nearest integer towards zero.
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol fix(const std::string& symbol_name,
    Symbol x) {
  Operator op_("fix");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Flatten(data) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \return new symbol
 */
inline Symbol flatten(const std::string& symbol_name,
    Symbol data) {
  Operator op_("flatten");
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief flip(data) with attrs axis — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param axis 
 * \return new symbol
 */
inline Symbol flip(const std::string& symbol_name,
    Symbol data,
    Shape axis = Shape()) {
  Operator op_("flip");
  op_.SetInput("data", data);
  op_.SetParam("axis", axis);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Round input to the nearest integer downwards.
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol floor(const std::string& symbol_name,
    Symbol x) {
  Operator op_("floor");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief ftrl_update(weight, grad, z, n) with attrs beta, clip_gradient, lamda1, lr, rescale_grad, wd — registered operator, 3 outputs.
 * \param symbol_name name of the resulting symbol
 * \param weight Input weight
 * \param grad Input grad
 * \param z Input z
 * \param n Input n
 * \param beta 
 * \param clip_gradient 
 * \param lamda1 
 * \param lr 
 * \param rescale_grad 
 * \param wd 
 * \return new symbol
 */
inline Symbol ftrl_update(const std::string& symbol_name,
    Symbol weight,
    Symbol grad,
    Symbol z,
    Symbol n,
    mx_float beta = static_cast<mx_float>(1),
    mx_float clip_gradient = static_cast<mx_float>(-1),
    mx_float lamda1 = static_cast<mx_float>(0.01),
    mx_float lr = static_cast<mx_float>(0.1),
    mx_float rescale_grad = static_cast<mx_float>(1),
    mx_float wd = static_cast<mx_float>(0)) {
  Operator op_("ftrl_update");
  op_.SetInput("weight", weight);
  op_.SetInput("grad", grad);
  op_.SetInput("z", z);
  op_.SetInput("n", n);
  op_.SetParam("beta", beta);
  op_.SetParam("clip_gradient", clip_gradient);
  op_.SetParam("lamda1", lamda1);
  op_.SetParam("lr", lr);
  op_.SetParam("rescale_grad", rescale_grad);
  op_.SetParam("wd", wd);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief FullyConnected(data, weight, bias) with attrs flatten, no_bias, num_hidden — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param weight Input weight
 * \param bias Input bias
 * \param flatten 
 * \param no_bias 
 * \param num_hidden 
 * \return new symbol
 */
inline Symbol fully_connected(const std::string& symbol_name,
    Symbol data,
    Symbol weight,
    Symbol bias = Symbol(),
    bool flatten = 1,
    bool no_bias = 0,
    int num_hidden = 1) {
  Operator op_("fully_connected");
  op_.SetInput("data", data);
  op_.SetInput("weight", weight);
  if (!bias.IsNull()) op_.SetInput("bias", bias);
  op_.SetParam("flatten", flatten);
  op_.SetParam("no_bias", no_bias);
  op_.SetParam("num_hidden", num_hidden);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief gamma(data) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \return new symbol
 */
inline Symbol gamma(const std::string& symbol_name,
    Symbol data) {
  Operator op_("gamma");
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Natural log of the absolute value of the gamma function.
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol gammaln(const std::string& symbol_name,
    Symbol x) {
  Operator op_("gammaln");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief gather_nd(data, indices) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param indices Input indices
 * \return new symbol
 */
inline Symbol gather_nd(const std::string& symbol_name,
    Symbol data,
    Symbol indices) {
  Operator op_("gather_nd");
  op_.SetInput("data", data);
  op_.SetInput("indices", indices);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief GridGenerator(data) with attrs target_shape, transform_type — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param target_shape 
 * \param transform_type 
 * \return new symbol
 */
inline Symbol grid_generator(const std::string& symbol_name,
    Symbol data,
    Shape target_shape = Shape(std::vector<mx_uint>{0, 0}),
    const std::string& transform_type = "affine") {
  Operator op_("grid_generator");
  op_.SetInput("data", data);
  op_.SetParam("target_shape", target_shape);
  op_.SetParam("transform_type", transform_type);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _copy(data) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \return new symbol
 */
inline Symbol identity(const std::string& symbol_name,
    Symbol data) {
  Operator op_("identity");
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief IdentityAttachKLSparseReg(data) with attrs momentum, penalty, sparseness_target — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param momentum 
 * \param penalty 
 * \param sparseness_target 
 * \return new symbol
 */
inline Symbol identity_attach_kl_sparse_reg(const std::string& symbol_name,
    Symbol data,
    mx_float momentum = static_cast<mx_float>(0.9),
    mx_float penalty = static_cast<mx_float>(0.001),
    mx_float sparseness_target = static_cast<mx_float>(0.1)) {
  Operator op_("identity_attach_kl_sparse_reg");
  op_.SetInput("data", data);
  op_.SetParam("momentum", momentum);
  op_.SetParam("penalty", penalty);
  op_.SetParam("sparseness_target", sparseness_target);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief InstanceNorm(data, gamma, beta) with attrs eps — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param gamma Input gamma
 * \param beta Input beta
 * \param eps 
 * \return new symbol
 */
inline Symbol instance_norm(const std::string& symbol_name,
    Symbol data,
    Symbol gamma,
    Symbol beta,
    mx_float eps = static_cast<mx_float>(0.001)) {
  Operator op_("instance_norm");
  op_.SetInput("data", data);
  op_.SetInput("gamma", gamma);
  op_.SetInput("beta", beta);
  op_.SetParam("eps", eps);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Column-wise Khatri-Rao product (ref: src/operator/contrib/krprod.h).
 * \param symbol_name name of the resulting symbol
 * \param data variable-length input symbol list
 * \return new symbol
 */
inline Symbol khatri_rao(const std::string& symbol_name,
    const std::vector<Symbol>& data) {
  Operator op_("khatri_rao");
  op_.SetParam("num_args", static_cast<int>(data.size()));
  for (const auto& s : data) op_.AddInput(s);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief L2Normalization(data) with attrs eps, mode — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param eps 
 * \param mode 
 * \return new symbol
 */
inline Symbol l2_normalization(const std::string& symbol_name,
    Symbol data,
    mx_float eps = static_cast<mx_float>(1e-10),
    const std::string& mode = "instance") {
  Operator op_("l2_normalization");
  op_.SetInput("data", data);
  op_.SetParam("eps", eps);
  op_.SetParam("mode", mode);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief LayerNorm(data, gamma, beta) with attrs axis, eps, output_mean_var — registered operator, 3 outputs.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param gamma Input gamma
 * \param beta Input beta
 * \param axis 
 * \param eps 
 * \param output_mean_var 
 * \return new symbol
 */
inline Symbol layer_norm(const std::string& symbol_name,
    Symbol data,
    Symbol gamma,
    Symbol beta,
    int axis = -1,
    mx_float eps = static_cast<mx_float>(1e-05),
    bool output_mean_var = 0) {
  Operator op_("layer_norm");
  op_.SetInput("data", data);
  op_.SetInput("gamma", gamma);
  op_.SetInput("beta", beta);
  op_.SetParam("axis", axis);
  op_.SetParam("eps", eps);
  op_.SetParam("output_mean_var", output_mean_var);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief ref: src/operator/leaky_relu.cc — leaky/prelu/elu/selu/rrelu/gelu.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param gamma Input gamma
 * \param __is_train__ 
 * \param act_type 
 * \param lower_bound 
 * \param slope 
 * \param upper_bound 
 * \return new symbol
 */
inline Symbol leaky_relu(const std::string& symbol_name,
    Symbol data,
    Symbol gamma = Symbol(),
    bool __is_train__ = 0,
    const std::string& act_type = "leaky",
    mx_float lower_bound = static_cast<mx_float>(0.125),
    mx_float slope = static_cast<mx_float>(0.25),
    mx_float upper_bound = static_cast<mx_float>(0.334)) {
  Operator op_("leaky_relu");
  op_.SetInput("data", data);
  if (!gamma.IsNull()) op_.SetInput("gamma", gamma);
  op_.SetParam("__is_train__", __is_train__);
  op_.SetParam("act_type", act_type);
  op_.SetParam("lower_bound", lower_bound);
  op_.SetParam("slope", slope);
  op_.SetParam("upper_bound", upper_bound);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief LinearRegressionOutput(data, label) with attrs grad_scale — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param label Input label
 * \param grad_scale 
 * \return new symbol
 */
inline Symbol linear_regression_output(const std::string& symbol_name,
    Symbol data,
    Symbol label,
    mx_float grad_scale = static_cast<mx_float>(1)) {
  Operator op_("linear_regression_output");
  op_.SetInput("data", data);
  op_.SetInput("label", label);
  op_.SetParam("grad_scale", grad_scale);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Calculate element-wise natural logarithm of the input.
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol log(const std::string& symbol_name,
    Symbol x) {
  Operator op_("log");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Calculates the base-10 logarithm of x element-wise
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol log10(const std::string& symbol_name,
    Symbol x) {
  Operator op_("log10");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Calculates element-wise logarithm of one plus input, ``log(x+1)``.
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol log1p(const std::string& symbol_name,
    Symbol x) {
  Operator op_("log1p");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Calculates the base-2 logarithm of ``x`` element-wise.
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol log2(const std::string& symbol_name,
    Symbol x) {
  Operator op_("log2");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief log_softmax(data) with attrs axis, temperature — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param axis 
 * \param temperature 
 * \return new symbol
 */
inline Symbol log_softmax(const std::string& symbol_name,
    Symbol data,
    int axis = -1,
    const std::string& temperature = "None") {
  Operator op_("log_softmax");
  op_.SetInput("data", data);
  op_.SetParam("axis", axis);
  op_.SetParam("temperature", temperature);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief logical_not(data) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \return new symbol
 */
inline Symbol logical_not(const std::string& symbol_name,
    Symbol data) {
  Operator op_("logical_not");
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief LogisticRegressionOutput(data, label) with attrs grad_scale — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param label Input label
 * \param grad_scale 
 * \return new symbol
 */
inline Symbol logistic_regression_output(const std::string& symbol_name,
    Symbol data,
    Symbol label,
    mx_float grad_scale = static_cast<mx_float>(1)) {
  Operator op_("logistic_regression_output");
  op_.SetInput("data", data);
  op_.SetInput("label", label);
  op_.SetParam("grad_scale", grad_scale);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Local response norm across channels (ref: src/operator/lrn.cc).
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param alpha 
 * \param beta 
 * \param knorm 
 * \param nsize 
 * \return new symbol
 */
inline Symbol lrn(const std::string& symbol_name,
    Symbol data,
    mx_float alpha = static_cast<mx_float>(0.0001),
    mx_float beta = static_cast<mx_float>(0.75),
    mx_float knorm = static_cast<mx_float>(2),
    int nsize = 5) {
  Operator op_("lrn");
  op_.SetInput("data", data);
  op_.SetParam("alpha", alpha);
  op_.SetParam("beta", beta);
  op_.SetParam("knorm", knorm);
  op_.SetParam("nsize", nsize);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief MAERegressionOutput(data, label) with attrs grad_scale — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param label Input label
 * \param grad_scale 
 * \return new symbol
 */
inline Symbol mae_regression_output(const std::string& symbol_name,
    Symbol data,
    Symbol label,
    mx_float grad_scale = static_cast<mx_float>(1)) {
  Operator op_("mae_regression_output");
  op_.SetInput("data", data);
  op_.SetInput("label", label);
  op_.SetParam("grad_scale", grad_scale);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Loss head: forward identity, backward a constant grad_scale field.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param grad_scale 
 * \param normalization 
 * \param valid_thresh 
 * \return new symbol
 */
inline Symbol make_loss(const std::string& symbol_name,
    Symbol data,
    mx_float grad_scale = static_cast<mx_float>(1),
    const std::string& normalization = "null",
    mx_float valid_thresh = static_cast<mx_float>(0)) {
  Operator op_("make_loss");
  op_.SetInput("data", data);
  op_.SetParam("grad_scale", grad_scale);
  op_.SetParam("normalization", normalization);
  op_.SetParam("valid_thresh", valid_thresh);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief max(data) with attrs _fn, axis, exclude, keepdims — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param axis 
 * \param exclude 
 * \param keepdims 
 * \return new symbol
 */
inline Symbol max(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    const std::string& axis = "None",
    bool exclude = 0,
    bool keepdims = 0) {
  Operator op_("max");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("axis", axis);
  op_.SetParam("exclude", exclude);
  op_.SetParam("keepdims", keepdims);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief max(data) with attrs _fn, axis, exclude, keepdims — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param axis 
 * \param exclude 
 * \param keepdims 
 * \return new symbol
 */
inline Symbol max_axis(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    const std::string& axis = "None",
    bool exclude = 0,
    bool keepdims = 0) {
  Operator op_("max_axis");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("axis", axis);
  op_.SetParam("exclude", exclude);
  op_.SetParam("keepdims", keepdims);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief mean(data) with attrs _fn, axis, exclude, keepdims — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param axis 
 * \param exclude 
 * \param keepdims 
 * \return new symbol
 */
inline Symbol mean(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    const std::string& axis = "None",
    bool exclude = 0,
    bool keepdims = 0) {
  Operator op_("mean");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("axis", axis);
  op_.SetParam("exclude", exclude);
  op_.SetParam("keepdims", keepdims);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief min(data) with attrs _fn, axis, exclude, keepdims — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param axis 
 * \param exclude 
 * \param keepdims 
 * \return new symbol
 */
inline Symbol min(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    const std::string& axis = "None",
    bool exclude = 0,
    bool keepdims = 0) {
  Operator op_("min");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("axis", axis);
  op_.SetParam("exclude", exclude);
  op_.SetParam("keepdims", keepdims);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief min(data) with attrs _fn, axis, exclude, keepdims — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param axis 
 * \param exclude 
 * \param keepdims 
 * \return new symbol
 */
inline Symbol min_axis(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    const std::string& axis = "None",
    bool exclude = 0,
    bool keepdims = 0) {
  Operator op_("min_axis");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("axis", axis);
  op_.SetParam("exclude", exclude);
  op_.SetParam("keepdims", keepdims);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief mp_sgd_mom_update(weight, grad, mom, weight32) with attrs clip_gradient, lazy_update, lr, momentum, rescale_grad, wd — registered operator, 3 outputs.
 * \param symbol_name name of the resulting symbol
 * \param weight Input weight
 * \param grad Input grad
 * \param mom Input mom
 * \param weight32 Input weight32
 * \param clip_gradient 
 * \param lazy_update 
 * \param lr 
 * \param momentum 
 * \param rescale_grad 
 * \param wd 
 * \return new symbol
 */
inline Symbol mp_sgd_mom_update(const std::string& symbol_name,
    Symbol weight,
    Symbol grad,
    Symbol mom,
    Symbol weight32,
    mx_float clip_gradient = static_cast<mx_float>(-1),
    bool lazy_update = 1,
    mx_float lr = static_cast<mx_float>(0.01),
    mx_float momentum = static_cast<mx_float>(0),
    mx_float rescale_grad = static_cast<mx_float>(1),
    mx_float wd = static_cast<mx_float>(0)) {
  Operator op_("mp_sgd_mom_update");
  op_.SetInput("weight", weight);
  op_.SetInput("grad", grad);
  op_.SetInput("mom", mom);
  op_.SetInput("weight32", weight32);
  op_.SetParam("clip_gradient", clip_gradient);
  op_.SetParam("lazy_update", lazy_update);
  op_.SetParam("lr", lr);
  op_.SetParam("momentum", momentum);
  op_.SetParam("rescale_grad", rescale_grad);
  op_.SetParam("wd", wd);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Multi-precision SGD: fp32 master weights, low-precision model weights.
 * \param symbol_name name of the resulting symbol
 * \param weight Input weight
 * \param grad Input grad
 * \param weight32 Input weight32
 * \param clip_gradient 
 * \param lazy_update 
 * \param lr 
 * \param rescale_grad 
 * \param wd 
 * \return new symbol
 */
inline Symbol mp_sgd_update(const std::string& symbol_name,
    Symbol weight,
    Symbol grad,
    Symbol weight32,
    mx_float clip_gradient = static_cast<mx_float>(-1),
    bool lazy_update = 1,
    mx_float lr = static_cast<mx_float>(0.01),
    mx_float rescale_grad = static_cast<mx_float>(1),
    mx_float wd = static_cast<mx_float>(0)) {
  Operator op_("mp_sgd_update");
  op_.SetInput("weight", weight);
  op_.SetInput("grad", grad);
  op_.SetInput("weight32", weight32);
  op_.SetParam("clip_gradient", clip_gradient);
  op_.SetParam("lazy_update", lazy_update);
  op_.SetParam("lr", lr);
  op_.SetParam("rescale_grad", rescale_grad);
  op_.SetParam("wd", wd);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief nanprod(data) with attrs _fn, axis, exclude, keepdims — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param axis 
 * \param exclude 
 * \param keepdims 
 * \return new symbol
 */
inline Symbol nanprod(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    const std::string& axis = "None",
    bool exclude = 0,
    bool keepdims = 0) {
  Operator op_("nanprod");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("axis", axis);
  op_.SetParam("exclude", exclude);
  op_.SetParam("keepdims", keepdims);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief nansum(data) with attrs _fn, axis, exclude, keepdims — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param axis 
 * \param exclude 
 * \param keepdims 
 * \return new symbol
 */
inline Symbol nansum(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    const std::string& axis = "None",
    bool exclude = 0,
    bool keepdims = 0) {
  Operator op_("nansum");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("axis", axis);
  op_.SetParam("exclude", exclude);
  op_.SetParam("keepdims", keepdims);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief negative(data) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \return new symbol
 */
inline Symbol negative(const std::string& symbol_name,
    Symbol data) {
  Operator op_("negative");
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief norm(data) with attrs axis, keepdims, ord — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param axis 
 * \param keepdims 
 * \param ord 
 * \return new symbol
 */
inline Symbol norm(const std::string& symbol_name,
    Symbol data,
    const std::string& axis = "None",
    bool keepdims = 0,
    int ord = 2) {
  Operator op_("norm");
  op_.SetInput("data", data);
  op_.SetParam("axis", axis);
  op_.SetParam("keepdims", keepdims);
  op_.SetParam("ord", ord);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _random_normal(...) with attrs ctx, dtype, loc, scale, shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param ctx 
 * \param dtype 
 * \param loc 
 * \param scale 
 * \param shape 
 * \return new symbol
 */
inline Symbol normal(const std::string& symbol_name,
    const std::string& ctx = "None",
    const std::string& dtype = "float32",
    mx_float loc = static_cast<mx_float>(0),
    mx_float scale = static_cast<mx_float>(1),
    Shape shape = Shape()) {
  Operator op_("normal");
  op_.SetParam("ctx", ctx);
  op_.SetParam("dtype", dtype);
  op_.SetParam("loc", loc);
  op_.SetParam("scale", scale);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief one_hot(indices) with attrs depth, dtype, off_value, on_value — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param indices Input indices
 * \param depth 
 * \param dtype 
 * \param off_value 
 * \param on_value 
 * \return new symbol
 */
inline Symbol one_hot(const std::string& symbol_name,
    Symbol indices,
    int depth = 0,
    const std::string& dtype = "float32",
    mx_float off_value = static_cast<mx_float>(0),
    mx_float on_value = static_cast<mx_float>(1)) {
  Operator op_("one_hot");
  op_.SetInput("indices", indices);
  op_.SetParam("depth", depth);
  op_.SetParam("dtype", dtype);
  op_.SetParam("off_value", off_value);
  op_.SetParam("on_value", on_value);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief ones_like(data) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \return new symbol
 */
inline Symbol ones_like(const std::string& symbol_name,
    Symbol data) {
  Operator op_("ones_like");
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief pad(data) with attrs constant_value, mode, pad_width — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param constant_value 
 * \param mode 
 * \param pad_width 
 * \return new symbol
 */
inline Symbol pad(const std::string& symbol_name,
    Symbol data,
    mx_float constant_value = static_cast<mx_float>(0),
    const std::string& mode = "constant",
    Shape pad_width = Shape()) {
  Operator op_("pad");
  op_.SetInput("data", data);
  op_.SetParam("constant_value", constant_value);
  op_.SetParam("mode", mode);
  op_.SetParam("pad_width", pad_width);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Pick per-row elements by index (ref: src/operator/tensor/broadcast_reduce_op_index.cc).
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param index Input index
 * \param axis 
 * \param keepdims 
 * \param mode 
 * \return new symbol
 */
inline Symbol pick(const std::string& symbol_name,
    Symbol data,
    Symbol index,
    int axis = -1,
    bool keepdims = 0,
    const std::string& mode = "clip") {
  Operator op_("pick");
  op_.SetInput("data", data);
  op_.SetInput("index", index);
  op_.SetParam("axis", axis);
  op_.SetParam("keepdims", keepdims);
  op_.SetParam("mode", mode);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Pooling(data) with attrs cudnn_off, global_pool, kernel, pad, pool_type, pooling_convention, stride — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param cudnn_off 
 * \param global_pool 
 * \param kernel 
 * \param pad 
 * \param pool_type 
 * \param pooling_convention 
 * \param stride 
 * \return new symbol
 */
inline Symbol pooling(const std::string& symbol_name,
    Symbol data,
    bool cudnn_off = 0,
    bool global_pool = 0,
    Shape kernel = Shape(),
    Shape pad = Shape(),
    const std::string& pool_type = "max",
    const std::string& pooling_convention = "valid",
    Shape stride = Shape()) {
  Operator op_("pooling");
  op_.SetInput("data", data);
  op_.SetParam("cudnn_off", cudnn_off);
  op_.SetParam("global_pool", global_pool);
  op_.SetParam("kernel", kernel);
  op_.SetParam("pad", pad);
  op_.SetParam("pool_type", pool_type);
  op_.SetParam("pooling_convention", pooling_convention);
  op_.SetParam("stride", stride);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief prod(data) with attrs _fn, axis, exclude, keepdims — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param axis 
 * \param exclude 
 * \param keepdims 
 * \return new symbol
 */
inline Symbol prod(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    const std::string& axis = "None",
    bool exclude = 0,
    bool keepdims = 0) {
  Operator op_("prod");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("axis", axis);
  op_.SetParam("exclude", exclude);
  op_.SetParam("keepdims", keepdims);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Alias of :func:`jax.numpy.deg2rad`
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol radians(const std::string& symbol_name,
    Symbol x) {
  Operator op_("radians");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _random_exponential(...) with attrs ctx, dtype, lam, shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param ctx 
 * \param dtype 
 * \param lam 
 * \param shape 
 * \return new symbol
 */
inline Symbol random_exponential(const std::string& symbol_name,
    const std::string& ctx = "None",
    const std::string& dtype = "float32",
    mx_float lam = static_cast<mx_float>(1),
    Shape shape = Shape()) {
  Operator op_("random_exponential");
  op_.SetParam("ctx", ctx);
  op_.SetParam("dtype", dtype);
  op_.SetParam("lam", lam);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _random_gamma(...) with attrs alpha, beta, ctx, dtype, shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param alpha 
 * \param beta 
 * \param ctx 
 * \param dtype 
 * \param shape 
 * \return new symbol
 */
inline Symbol random_gamma(const std::string& symbol_name,
    mx_float alpha = static_cast<mx_float>(1),
    mx_float beta = static_cast<mx_float>(1),
    const std::string& ctx = "None",
    const std::string& dtype = "float32",
    Shape shape = Shape()) {
  Operator op_("random_gamma");
  op_.SetParam("alpha", alpha);
  op_.SetParam("beta", beta);
  op_.SetParam("ctx", ctx);
  op_.SetParam("dtype", dtype);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _random_generalized_negative_binomial(...) with attrs alpha, ctx, dtype, mu, shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param alpha 
 * \param ctx 
 * \param dtype 
 * \param mu 
 * \param shape 
 * \return new symbol
 */
inline Symbol random_generalized_negative_binomial(const std::string& symbol_name,
    mx_float alpha = static_cast<mx_float>(1),
    const std::string& ctx = "None",
    const std::string& dtype = "float32",
    mx_float mu = static_cast<mx_float>(1),
    Shape shape = Shape()) {
  Operator op_("random_generalized_negative_binomial");
  op_.SetParam("alpha", alpha);
  op_.SetParam("ctx", ctx);
  op_.SetParam("dtype", dtype);
  op_.SetParam("mu", mu);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _random_negative_binomial(...) with attrs ctx, dtype, k, p, shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param ctx 
 * \param dtype 
 * \param k 
 * \param p 
 * \param shape 
 * \return new symbol
 */
inline Symbol random_negative_binomial(const std::string& symbol_name,
    const std::string& ctx = "None",
    const std::string& dtype = "float32",
    int k = 1,
    mx_float p = static_cast<mx_float>(1),
    Shape shape = Shape()) {
  Operator op_("random_negative_binomial");
  op_.SetParam("ctx", ctx);
  op_.SetParam("dtype", dtype);
  op_.SetParam("k", k);
  op_.SetParam("p", p);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _random_normal(...) with attrs ctx, dtype, loc, scale, shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param ctx 
 * \param dtype 
 * \param loc 
 * \param scale 
 * \param shape 
 * \return new symbol
 */
inline Symbol random_normal(const std::string& symbol_name,
    const std::string& ctx = "None",
    const std::string& dtype = "float32",
    mx_float loc = static_cast<mx_float>(0),
    mx_float scale = static_cast<mx_float>(1),
    Shape shape = Shape()) {
  Operator op_("random_normal");
  op_.SetParam("ctx", ctx);
  op_.SetParam("dtype", dtype);
  op_.SetParam("loc", loc);
  op_.SetParam("scale", scale);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _random_poisson(...) with attrs ctx, dtype, lam, shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param ctx 
 * \param dtype 
 * \param lam 
 * \param shape 
 * \return new symbol
 */
inline Symbol random_poisson(const std::string& symbol_name,
    const std::string& ctx = "None",
    const std::string& dtype = "float32",
    mx_float lam = static_cast<mx_float>(1),
    Shape shape = Shape()) {
  Operator op_("random_poisson");
  op_.SetParam("ctx", ctx);
  op_.SetParam("dtype", dtype);
  op_.SetParam("lam", lam);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _random_randint(...) with attrs ctx, dtype, high, low, shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param ctx 
 * \param dtype 
 * \param high 
 * \param low 
 * \param shape 
 * \return new symbol
 */
inline Symbol random_randint(const std::string& symbol_name,
    const std::string& ctx = "None",
    const std::string& dtype = "int32",
    int high = 1,
    int low = 0,
    Shape shape = Shape()) {
  Operator op_("random_randint");
  op_.SetParam("ctx", ctx);
  op_.SetParam("dtype", dtype);
  op_.SetParam("high", high);
  op_.SetParam("low", low);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _random_uniform(...) with attrs ctx, dtype, high, low, shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param ctx 
 * \param dtype 
 * \param high 
 * \param low 
 * \param shape 
 * \return new symbol
 */
inline Symbol random_uniform(const std::string& symbol_name,
    const std::string& ctx = "None",
    const std::string& dtype = "float32",
    mx_float high = static_cast<mx_float>(1),
    mx_float low = static_cast<mx_float>(0),
    Shape shape = Shape()) {
  Operator op_("random_uniform");
  op_.SetParam("ctx", ctx);
  op_.SetParam("dtype", dtype);
  op_.SetParam("high", high);
  op_.SetParam("low", low);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief rcbrt(data) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \return new symbol
 */
inline Symbol rcbrt(const std::string& symbol_name,
    Symbol data) {
  Operator op_("rcbrt");
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief reciprocal(data) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \return new symbol
 */
inline Symbol reciprocal(const std::string& symbol_name,
    Symbol data) {
  Operator op_("reciprocal");
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief relu(data) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \return new symbol
 */
inline Symbol relu(const std::string& symbol_name,
    Symbol data) {
  Operator op_("relu");
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief repeat(data) with attrs axis, repeats — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param axis 
 * \param repeats 
 * \return new symbol
 */
inline Symbol repeat(const std::string& symbol_name,
    Symbol data,
    const std::string& axis = "None",
    int repeats = 1) {
  Operator op_("repeat");
  op_.SetInput("data", data);
  op_.SetParam("axis", axis);
  op_.SetParam("repeats", repeats);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Reshape(data) with attrs reverse, shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param reverse 
 * \param shape 
 * \return new symbol
 */
inline Symbol reshape(const std::string& symbol_name,
    Symbol data,
    bool reverse = 0,
    Shape shape = Shape()) {
  Operator op_("reshape");
  op_.SetInput("data", data);
  op_.SetParam("reverse", reverse);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief reshape_like(lhs, rhs) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lhs Input lhs
 * \param rhs Input rhs
 * \return new symbol
 */
inline Symbol reshape_like(const std::string& symbol_name,
    Symbol lhs,
    Symbol rhs) {
  Operator op_("reshape_like");
  op_.SetInput("lhs", lhs);
  op_.SetInput("rhs", rhs);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief flip(data) with attrs axis — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param axis 
 * \return new symbol
 */
inline Symbol reverse(const std::string& symbol_name,
    Symbol data,
    Shape axis = Shape()) {
  Operator op_("reverse");
  op_.SetInput("data", data);
  op_.SetParam("axis", axis);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Rounds the elements of x to the nearest integer
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol rint(const std::string& symbol_name,
    Symbol x) {
  Operator op_("rint");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief rmsprop_update(weight, grad, n) with attrs clip_gradient, clip_weights, epsilon, gamma1, lr, rescale_grad, wd — registered operator, 2 outputs.
 * \param symbol_name name of the resulting symbol
 * \param weight Input weight
 * \param grad Input grad
 * \param n Input n
 * \param clip_gradient 
 * \param clip_weights 
 * \param epsilon 
 * \param gamma1 
 * \param lr 
 * \param rescale_grad 
 * \param wd 
 * \return new symbol
 */
inline Symbol rmsprop_update(const std::string& symbol_name,
    Symbol weight,
    Symbol grad,
    Symbol n,
    mx_float clip_gradient = static_cast<mx_float>(-1),
    mx_float clip_weights = static_cast<mx_float>(-1),
    mx_float epsilon = static_cast<mx_float>(1e-08),
    mx_float gamma1 = static_cast<mx_float>(0.9),
    mx_float lr = static_cast<mx_float>(0.001),
    mx_float rescale_grad = static_cast<mx_float>(1),
    mx_float wd = static_cast<mx_float>(0)) {
  Operator op_("rmsprop_update");
  op_.SetInput("weight", weight);
  op_.SetInput("grad", grad);
  op_.SetInput("n", n);
  op_.SetParam("clip_gradient", clip_gradient);
  op_.SetParam("clip_weights", clip_weights);
  op_.SetParam("epsilon", epsilon);
  op_.SetParam("gamma1", gamma1);
  op_.SetParam("lr", lr);
  op_.SetParam("rescale_grad", rescale_grad);
  op_.SetParam("wd", wd);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief rmspropalex_update(weight, grad, n, g_state, delta) with attrs clip_gradient, clip_weights, epsilon, gamma1, gamma2, lr, rescale_grad, wd — registered operator, 4 outputs.
 * \param symbol_name name of the resulting symbol
 * \param weight Input weight
 * \param grad Input grad
 * \param n Input n
 * \param g_state Input g_state
 * \param delta Input delta
 * \param clip_gradient 
 * \param clip_weights 
 * \param epsilon 
 * \param gamma1 
 * \param gamma2 
 * \param lr 
 * \param rescale_grad 
 * \param wd 
 * \return new symbol
 */
inline Symbol rmspropalex_update(const std::string& symbol_name,
    Symbol weight,
    Symbol grad,
    Symbol n,
    Symbol g_state,
    Symbol delta,
    mx_float clip_gradient = static_cast<mx_float>(-1),
    mx_float clip_weights = static_cast<mx_float>(-1),
    mx_float epsilon = static_cast<mx_float>(1e-08),
    mx_float gamma1 = static_cast<mx_float>(0.95),
    mx_float gamma2 = static_cast<mx_float>(0.9),
    mx_float lr = static_cast<mx_float>(0.001),
    mx_float rescale_grad = static_cast<mx_float>(1),
    mx_float wd = static_cast<mx_float>(0)) {
  Operator op_("rmspropalex_update");
  op_.SetInput("weight", weight);
  op_.SetInput("grad", grad);
  op_.SetInput("n", n);
  op_.SetInput("g_state", g_state);
  op_.SetInput("delta", delta);
  op_.SetParam("clip_gradient", clip_gradient);
  op_.SetParam("clip_weights", clip_weights);
  op_.SetParam("epsilon", epsilon);
  op_.SetParam("gamma1", gamma1);
  op_.SetParam("gamma2", gamma2);
  op_.SetParam("lr", lr);
  op_.SetParam("rescale_grad", rescale_grad);
  op_.SetParam("wd", wd);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Fused multi-layer (bi)RNN over the whole sequence.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param parameters Input parameters
 * \param state Input state
 * \param state_cell Input state_cell
 * \param __is_train__ 
 * \param bidirectional 
 * \param mode 
 * \param num_layers 
 * \param p 
 * \param state_outputs 
 * \param state_size 
 * \return new symbol
 */
inline Symbol rnn(const std::string& symbol_name,
    Symbol data,
    Symbol parameters,
    Symbol state,
    Symbol state_cell = Symbol(),
    bool __is_train__ = 0,
    bool bidirectional = 0,
    const std::string& mode = "lstm",
    int num_layers = 1,
    mx_float p = static_cast<mx_float>(0),
    bool state_outputs = 0,
    int state_size = 0) {
  Operator op_("rnn");
  op_.SetInput("data", data);
  op_.SetInput("parameters", parameters);
  op_.SetInput("state", state);
  if (!state_cell.IsNull()) op_.SetInput("state_cell", state_cell);
  op_.SetParam("__is_train__", __is_train__);
  op_.SetParam("bidirectional", bidirectional);
  op_.SetParam("mode", mode);
  op_.SetParam("num_layers", num_layers);
  op_.SetParam("p", p);
  op_.SetParam("state_outputs", state_outputs);
  op_.SetParam("state_size", state_size);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Round input evenly to the given number of decimals.
 * \param symbol_name name of the resulting symbol
 * \param a Input a
 * \param decimals 
 * \param out 
 * \return new symbol
 */
inline Symbol round(const std::string& symbol_name,
    Symbol a,
    int decimals = 0,
    const std::string& out = "None") {
  Operator op_("round");
  op_.SetInput("a", a);
  op_.SetParam("decimals", decimals);
  op_.SetParam("out", out);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief rsqrt(data) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \return new symbol
 */
inline Symbol rsqrt(const std::string& symbol_name,
    Symbol data) {
  Operator op_("rsqrt");
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _sample_exponential(lam) with attrs dtype, shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lam Input lam
 * \param dtype 
 * \param shape 
 * \return new symbol
 */
inline Symbol sample_exponential(const std::string& symbol_name,
    Symbol lam,
    const std::string& dtype = "float32",
    Shape shape = Shape()) {
  Operator op_("sample_exponential");
  op_.SetInput("lam", lam);
  op_.SetParam("dtype", dtype);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _sample_gamma(alpha, beta) with attrs dtype, shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param alpha Input alpha
 * \param beta Input beta
 * \param dtype 
 * \param shape 
 * \return new symbol
 */
inline Symbol sample_gamma(const std::string& symbol_name,
    Symbol alpha,
    Symbol beta,
    const std::string& dtype = "float32",
    Shape shape = Shape()) {
  Operator op_("sample_gamma");
  op_.SetInput("alpha", alpha);
  op_.SetInput("beta", beta);
  op_.SetParam("dtype", dtype);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Sample from categorical rows (ref: src/operator/random/multisample_op).
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param dtype 
 * \param get_prob 
 * \param shape 
 * \return new symbol
 */
inline Symbol sample_multinomial(const std::string& symbol_name,
    Symbol data,
    const std::string& dtype = "int32",
    bool get_prob = 0,
    Shape shape = Shape()) {
  Operator op_("sample_multinomial");
  op_.SetInput("data", data);
  op_.SetParam("dtype", dtype);
  op_.SetParam("get_prob", get_prob);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _sample_normal(mu, sigma) with attrs dtype, shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param mu Input mu
 * \param sigma Input sigma
 * \param dtype 
 * \param shape 
 * \return new symbol
 */
inline Symbol sample_normal(const std::string& symbol_name,
    Symbol mu,
    Symbol sigma,
    const std::string& dtype = "float32",
    Shape shape = Shape()) {
  Operator op_("sample_normal");
  op_.SetInput("mu", mu);
  op_.SetInput("sigma", sigma);
  op_.SetParam("dtype", dtype);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _sample_poisson(lam) with attrs dtype, shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param lam Input lam
 * \param dtype 
 * \param shape 
 * \return new symbol
 */
inline Symbol sample_poisson(const std::string& symbol_name,
    Symbol lam,
    const std::string& dtype = "float32",
    Shape shape = Shape()) {
  Operator op_("sample_poisson");
  op_.SetInput("lam", lam);
  op_.SetParam("dtype", dtype);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _sample_uniform(low, high) with attrs dtype, shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param low Input low
 * \param high Input high
 * \param dtype 
 * \param shape 
 * \return new symbol
 */
inline Symbol sample_uniform(const std::string& symbol_name,
    Symbol low,
    Symbol high,
    const std::string& dtype = "float32",
    Shape shape = Shape()) {
  Operator op_("sample_uniform");
  op_.SetInput("low", low);
  op_.SetInput("high", high);
  op_.SetParam("dtype", dtype);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief scatter_nd(data, indices) with attrs shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param indices Input indices
 * \param shape 
 * \return new symbol
 */
inline Symbol scatter_nd(const std::string& symbol_name,
    Symbol data,
    Symbol indices,
    Shape shape = Shape()) {
  Operator op_("scatter_nd");
  op_.SetInput("data", data);
  op_.SetInput("indices", indices);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief SequenceLast(data, sequence_length) with attrs axis, use_sequence_length — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param sequence_length Input sequence_length
 * \param axis 
 * \param use_sequence_length 
 * \return new symbol
 */
inline Symbol sequence_last(const std::string& symbol_name,
    Symbol data,
    Symbol sequence_length = Symbol(),
    int axis = 0,
    bool use_sequence_length = 0) {
  Operator op_("sequence_last");
  op_.SetInput("data", data);
  if (!sequence_length.IsNull()) op_.SetInput("sequence_length", sequence_length);
  op_.SetParam("axis", axis);
  op_.SetParam("use_sequence_length", use_sequence_length);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief SequenceMask(data, sequence_length) with attrs axis, use_sequence_length, value — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param sequence_length Input sequence_length
 * \param axis 
 * \param use_sequence_length 
 * \param value 
 * \return new symbol
 */
inline Symbol sequence_mask(const std::string& symbol_name,
    Symbol data,
    Symbol sequence_length = Symbol(),
    int axis = 0,
    bool use_sequence_length = 0,
    mx_float value = static_cast<mx_float>(0)) {
  Operator op_("sequence_mask");
  op_.SetInput("data", data);
  if (!sequence_length.IsNull()) op_.SetInput("sequence_length", sequence_length);
  op_.SetParam("axis", axis);
  op_.SetParam("use_sequence_length", use_sequence_length);
  op_.SetParam("value", value);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief SequenceReverse(data, sequence_length) with attrs axis, use_sequence_length — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param sequence_length Input sequence_length
 * \param axis 
 * \param use_sequence_length 
 * \return new symbol
 */
inline Symbol sequence_reverse(const std::string& symbol_name,
    Symbol data,
    Symbol sequence_length = Symbol(),
    int axis = 0,
    bool use_sequence_length = 0) {
  Operator op_("sequence_reverse");
  op_.SetInput("data", data);
  if (!sequence_length.IsNull()) op_.SetInput("sequence_length", sequence_length);
  op_.SetParam("axis", axis);
  op_.SetParam("use_sequence_length", use_sequence_length);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief sgd_mom_update(weight, grad, mom) with attrs clip_gradient, lazy_update, lr, momentum, rescale_grad, wd — registered operator, 2 outputs.
 * \param symbol_name name of the resulting symbol
 * \param weight Input weight
 * \param grad Input grad
 * \param mom Input mom
 * \param clip_gradient 
 * \param lazy_update 
 * \param lr 
 * \param momentum 
 * \param rescale_grad 
 * \param wd 
 * \return new symbol
 */
inline Symbol sgd_mom_update(const std::string& symbol_name,
    Symbol weight,
    Symbol grad,
    Symbol mom,
    mx_float clip_gradient = static_cast<mx_float>(-1),
    bool lazy_update = 1,
    mx_float lr = static_cast<mx_float>(0.01),
    mx_float momentum = static_cast<mx_float>(0),
    mx_float rescale_grad = static_cast<mx_float>(1),
    mx_float wd = static_cast<mx_float>(0)) {
  Operator op_("sgd_mom_update");
  op_.SetInput("weight", weight);
  op_.SetInput("grad", grad);
  op_.SetInput("mom", mom);
  op_.SetParam("clip_gradient", clip_gradient);
  op_.SetParam("lazy_update", lazy_update);
  op_.SetParam("lr", lr);
  op_.SetParam("momentum", momentum);
  op_.SetParam("rescale_grad", rescale_grad);
  op_.SetParam("wd", wd);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief sgd_update(weight, grad) with attrs clip_gradient, lazy_update, lr, rescale_grad, wd — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param weight Input weight
 * \param grad Input grad
 * \param clip_gradient 
 * \param lazy_update 
 * \param lr 
 * \param rescale_grad 
 * \param wd 
 * \return new symbol
 */
inline Symbol sgd_update(const std::string& symbol_name,
    Symbol weight,
    Symbol grad,
    mx_float clip_gradient = static_cast<mx_float>(-1),
    bool lazy_update = 1,
    mx_float lr = static_cast<mx_float>(0.01),
    mx_float rescale_grad = static_cast<mx_float>(1),
    mx_float wd = static_cast<mx_float>(0)) {
  Operator op_("sgd_update");
  op_.SetInput("weight", weight);
  op_.SetInput("grad", grad);
  op_.SetParam("clip_gradient", clip_gradient);
  op_.SetParam("lazy_update", lazy_update);
  op_.SetParam("lr", lr);
  op_.SetParam("rescale_grad", rescale_grad);
  op_.SetParam("wd", wd);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief shuffle(data) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \return new symbol
 */
inline Symbol shuffle(const std::string& symbol_name,
    Symbol data) {
  Operator op_("shuffle");
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Sigmoid activation function.
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol sigmoid(const std::string& symbol_name,
    Symbol x) {
  Operator op_("sigmoid");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Return an element-wise indication of sign of the input.
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol sign(const std::string& symbol_name,
    Symbol x) {
  Operator op_("sign");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief signsgd_update(weight, grad) with attrs clip_gradient, lr, rescale_grad, wd — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param weight Input weight
 * \param grad Input grad
 * \param clip_gradient 
 * \param lr 
 * \param rescale_grad 
 * \param wd 
 * \return new symbol
 */
inline Symbol signsgd_update(const std::string& symbol_name,
    Symbol weight,
    Symbol grad,
    mx_float clip_gradient = static_cast<mx_float>(-1),
    mx_float lr = static_cast<mx_float>(0.01),
    mx_float rescale_grad = static_cast<mx_float>(1),
    mx_float wd = static_cast<mx_float>(0)) {
  Operator op_("signsgd_update");
  op_.SetInput("weight", weight);
  op_.SetInput("grad", grad);
  op_.SetParam("clip_gradient", clip_gradient);
  op_.SetParam("lr", lr);
  op_.SetParam("rescale_grad", rescale_grad);
  op_.SetParam("wd", wd);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief signum_update(weight, grad, mom) with attrs clip_gradient, lr, momentum, rescale_grad, wd, wd_lh — registered operator, 2 outputs.
 * \param symbol_name name of the resulting symbol
 * \param weight Input weight
 * \param grad Input grad
 * \param mom Input mom
 * \param clip_gradient 
 * \param lr 
 * \param momentum 
 * \param rescale_grad 
 * \param wd 
 * \param wd_lh 
 * \return new symbol
 */
inline Symbol signum_update(const std::string& symbol_name,
    Symbol weight,
    Symbol grad,
    Symbol mom,
    mx_float clip_gradient = static_cast<mx_float>(-1),
    mx_float lr = static_cast<mx_float>(0.01),
    mx_float momentum = static_cast<mx_float>(0),
    mx_float rescale_grad = static_cast<mx_float>(1),
    mx_float wd = static_cast<mx_float>(0),
    mx_float wd_lh = static_cast<mx_float>(0)) {
  Operator op_("signum_update");
  op_.SetInput("weight", weight);
  op_.SetInput("grad", grad);
  op_.SetInput("mom", mom);
  op_.SetParam("clip_gradient", clip_gradient);
  op_.SetParam("lr", lr);
  op_.SetParam("momentum", momentum);
  op_.SetParam("rescale_grad", rescale_grad);
  op_.SetParam("wd", wd);
  op_.SetParam("wd_lh", wd_lh);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Compute a trigonometric sine of each element of input.
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol sin(const std::string& symbol_name,
    Symbol x) {
  Operator op_("sin");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Calculate element-wise hyperbolic sine of input.
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol sinh(const std::string& symbol_name,
    Symbol x) {
  Operator op_("sinh");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief slice(data) with attrs begin, end, step — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param begin 
 * \param end 
 * \param step 
 * \return new symbol
 */
inline Symbol slice(const std::string& symbol_name,
    Symbol data,
    Shape begin = Shape(),
    Shape end = Shape(),
    Shape step = Shape()) {
  Operator op_("slice");
  op_.SetInput("data", data);
  op_.SetParam("begin", begin);
  op_.SetParam("end", end);
  op_.SetParam("step", step);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief slice_axis(data) with attrs axis, begin, end — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param axis 
 * \param begin 
 * \param end 
 * \return new symbol
 */
inline Symbol slice_axis(const std::string& symbol_name,
    Symbol data,
    int axis = 0,
    int begin = 0,
    const std::string& end = "None") {
  Operator op_("slice_axis");
  op_.SetInput("data", data);
  op_.SetParam("axis", axis);
  op_.SetParam("begin", begin);
  op_.SetParam("end", end);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief SliceChannel(data) with attrs axis, num_outputs, squeeze_axis — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param axis 
 * \param num_outputs 
 * \param squeeze_axis 
 * \return new symbol
 */
inline Symbol slice_channel(const std::string& symbol_name,
    Symbol data,
    int axis = 1,
    int num_outputs = 1,
    bool squeeze_axis = 0) {
  Operator op_("slice_channel");
  op_.SetInput("data", data);
  op_.SetParam("axis", axis);
  op_.SetParam("num_outputs", num_outputs);
  op_.SetParam("squeeze_axis", squeeze_axis);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief slice_like(data, shape_like) with attrs axes — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param shape_like Input shape_like
 * \param axes 
 * \return new symbol
 */
inline Symbol slice_like(const std::string& symbol_name,
    Symbol data,
    Symbol shape_like,
    Shape axes = Shape()) {
  Operator op_("slice_like");
  op_.SetInput("data", data);
  op_.SetInput("shape_like", shape_like);
  op_.SetParam("axes", axes);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief f(x) = 0.5 (sx)^2/s^2... ref mshadow_op.h smooth_l1_loss: piecewise
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param scalar 
 * \return new symbol
 */
inline Symbol smooth_l1(const std::string& symbol_name,
    Symbol data,
    mx_float scalar = static_cast<mx_float>(1)) {
  Operator op_("smooth_l1");
  op_.SetInput("data", data);
  op_.SetParam("scalar", scalar);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief softmax(data) with attrs axis, length, temperature — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param axis 
 * \param length 
 * \param temperature 
 * \return new symbol
 */
inline Symbol softmax(const std::string& symbol_name,
    Symbol data,
    int axis = -1,
    const std::string& length = "None",
    const std::string& temperature = "None") {
  Operator op_("softmax");
  op_.SetInput("data", data);
  op_.SetParam("axis", axis);
  op_.SetParam("length", length);
  op_.SetParam("temperature", temperature);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief SoftmaxActivation(data) with attrs mode — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param mode 
 * \return new symbol
 */
inline Symbol softmax_activation(const std::string& symbol_name,
    Symbol data,
    const std::string& mode = "instance") {
  Operator op_("softmax_activation");
  op_.SetInput("data", data);
  op_.SetParam("mode", mode);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief softmax_cross_entropy(data, label) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param label Input label
 * \return new symbol
 */
inline Symbol softmax_cross_entropy(const std::string& symbol_name,
    Symbol data,
    Symbol label) {
  Operator op_("softmax_cross_entropy");
  op_.SetInput("data", data);
  op_.SetInput("label", label);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Fused softmax + CE-grad head (ref: src/operator/softmax_output.cc).
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param label Input label
 * \param grad_scale 
 * \param ignore_label 
 * \param multi_output 
 * \param normalization 
 * \param out_grad 
 * \param preserve_shape 
 * \param smooth_alpha 
 * \param use_ignore 
 * \return new symbol
 */
inline Symbol softmax_output(const std::string& symbol_name,
    Symbol data,
    Symbol label,
    mx_float grad_scale = static_cast<mx_float>(1),
    mx_float ignore_label = static_cast<mx_float>(-1),
    bool multi_output = 0,
    const std::string& normalization = "null",
    bool out_grad = 0,
    bool preserve_shape = 0,
    mx_float smooth_alpha = static_cast<mx_float>(0),
    bool use_ignore = 0) {
  Operator op_("softmax_output");
  op_.SetInput("data", data);
  op_.SetInput("label", label);
  op_.SetParam("grad_scale", grad_scale);
  op_.SetParam("ignore_label", ignore_label);
  op_.SetParam("multi_output", multi_output);
  op_.SetParam("normalization", normalization);
  op_.SetParam("out_grad", out_grad);
  op_.SetParam("preserve_shape", preserve_shape);
  op_.SetParam("smooth_alpha", smooth_alpha);
  op_.SetParam("use_ignore", use_ignore);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Soft-sign activation function.
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol softsign(const std::string& symbol_name,
    Symbol x) {
  Operator op_("softsign");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief sort(data) with attrs axis, is_ascend — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param axis 
 * \param is_ascend 
 * \return new symbol
 */
inline Symbol sort(const std::string& symbol_name,
    Symbol data,
    int axis = -1,
    bool is_ascend = 1) {
  Operator op_("sort");
  op_.SetInput("data", data);
  op_.SetParam("axis", axis);
  op_.SetParam("is_ascend", is_ascend);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief space_to_depth(data) with attrs block_size — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param block_size 
 * \return new symbol
 */
inline Symbol space_to_depth(const std::string& symbol_name,
    Symbol data,
    int block_size = 1) {
  Operator op_("space_to_depth");
  op_.SetInput("data", data);
  op_.SetParam("block_size", block_size);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief SpatialTransformer(data, loc) with attrs cudnn_off, sampler_type, target_shape, transform_type — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param loc Input loc
 * \param cudnn_off 
 * \param sampler_type 
 * \param target_shape 
 * \param transform_type 
 * \return new symbol
 */
inline Symbol spatial_transformer(const std::string& symbol_name,
    Symbol data,
    Symbol loc,
    bool cudnn_off = 0,
    const std::string& sampler_type = "bilinear",
    Shape target_shape = Shape(std::vector<mx_uint>{0, 0}),
    const std::string& transform_type = "affine") {
  Operator op_("spatial_transformer");
  op_.SetInput("data", data);
  op_.SetInput("loc", loc);
  op_.SetParam("cudnn_off", cudnn_off);
  op_.SetParam("sampler_type", sampler_type);
  op_.SetParam("target_shape", target_shape);
  op_.SetParam("transform_type", transform_type);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief SliceChannel(data) with attrs axis, num_outputs, squeeze_axis — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param axis 
 * \param num_outputs 
 * \param squeeze_axis 
 * \return new symbol
 */
inline Symbol split(const std::string& symbol_name,
    Symbol data,
    int axis = 1,
    int num_outputs = 1,
    bool squeeze_axis = 0) {
  Operator op_("split");
  op_.SetInput("data", data);
  op_.SetParam("axis", axis);
  op_.SetParam("num_outputs", num_outputs);
  op_.SetParam("squeeze_axis", squeeze_axis);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Calculates element-wise non-negative square root of the input array.
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol sqrt(const std::string& symbol_name,
    Symbol x) {
  Operator op_("sqrt");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Calculate element-wise square of the input array.
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol square(const std::string& symbol_name,
    Symbol x) {
  Operator op_("square");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief squeeze(data) with attrs axis — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param axis 
 * \return new symbol
 */
inline Symbol squeeze(const std::string& symbol_name,
    Symbol data,
    const std::string& axis = "None") {
  Operator op_("squeeze");
  op_.SetInput("data", data);
  op_.SetParam("axis", axis);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief stack(...) with attrs axis, num_args — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data variable-length input symbol list
 * \return new symbol
 */
inline Symbol stack(const std::string& symbol_name,
    const std::vector<Symbol>& data,
    int axis = 0) {
  Operator op_("stack");
  op_.SetParam("num_args", static_cast<int>(data.size()));
  op_.SetParam("axis", axis);
  for (const auto& s : data) op_.AddInput(s);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief BlockGrad(data) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \return new symbol
 */
inline Symbol stop_gradient(const std::string& symbol_name,
    Symbol data) {
  Operator op_("stop_gradient");
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief sum(data) with attrs _fn, axis, exclude, keepdims — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param axis 
 * \param exclude 
 * \param keepdims 
 * \return new symbol
 */
inline Symbol sum(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    const std::string& axis = "None",
    bool exclude = 0,
    bool keepdims = 0) {
  Operator op_("sum");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("axis", axis);
  op_.SetParam("exclude", exclude);
  op_.SetParam("keepdims", keepdims);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief sum(data) with attrs _fn, axis, exclude, keepdims — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param _fn 
 * \param axis 
 * \param exclude 
 * \param keepdims 
 * \return new symbol
 */
inline Symbol sum_axis(const std::string& symbol_name,
    Symbol data,
    const std::string& _fn = "None",
    const std::string& axis = "None",
    bool exclude = 0,
    bool keepdims = 0) {
  Operator op_("sum_axis");
  op_.SetInput("data", data);
  op_.SetParam("_fn", _fn);
  op_.SetParam("axis", axis);
  op_.SetParam("exclude", exclude);
  op_.SetParam("keepdims", keepdims);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief SVMOutput(data, label) with attrs margin, regularization_coefficient, use_linear — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param label Input label
 * \param margin 
 * \param regularization_coefficient 
 * \param use_linear 
 * \return new symbol
 */
inline Symbol svm_output(const std::string& symbol_name,
    Symbol data,
    Symbol label,
    mx_float margin = static_cast<mx_float>(1),
    mx_float regularization_coefficient = static_cast<mx_float>(1),
    bool use_linear = 0) {
  Operator op_("svm_output");
  op_.SetInput("data", data);
  op_.SetInput("label", label);
  op_.SetParam("margin", margin);
  op_.SetParam("regularization_coefficient", regularization_coefficient);
  op_.SetParam("use_linear", use_linear);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief swapaxes(data) with attrs dim1, dim2 — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param dim1 
 * \param dim2 
 * \return new symbol
 */
inline Symbol swapaxes(const std::string& symbol_name,
    Symbol data,
    int dim1 = 0,
    int dim2 = 0) {
  Operator op_("swapaxes");
  op_.SetInput("data", data);
  op_.SetParam("dim1", dim1);
  op_.SetParam("dim2", dim2);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief take(a, indices) with attrs axis, mode — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param a Input a
 * \param indices Input indices
 * \param axis 
 * \param mode 
 * \return new symbol
 */
inline Symbol take(const std::string& symbol_name,
    Symbol a,
    Symbol indices,
    int axis = 0,
    const std::string& mode = "clip") {
  Operator op_("take");
  op_.SetInput("a", a);
  op_.SetInput("indices", indices);
  op_.SetParam("axis", axis);
  op_.SetParam("mode", mode);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Compute a trigonometric tangent of each element of input.
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol tan(const std::string& symbol_name,
    Symbol x) {
  Operator op_("tan");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Calculate element-wise hyperbolic tangent of input.
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol tanh(const std::string& symbol_name,
    Symbol x) {
  Operator op_("tanh");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief tile(data) with attrs reps — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param reps 
 * \return new symbol
 */
inline Symbol tile(const std::string& symbol_name,
    Symbol data,
    Shape reps = Shape()) {
  Operator op_("tile");
  op_.SetInput("data", data);
  op_.SetParam("reps", reps);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief topk(data) with attrs axis, dtype, is_ascend, k, ret_typ — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param axis 
 * \param dtype 
 * \param is_ascend 
 * \param k 
 * \param ret_typ 
 * \return new symbol
 */
inline Symbol topk(const std::string& symbol_name,
    Symbol data,
    int axis = -1,
    const std::string& dtype = "float32",
    bool is_ascend = 0,
    int k = 1,
    const std::string& ret_typ = "indices") {
  Operator op_("topk");
  op_.SetInput("data", data);
  op_.SetParam("axis", axis);
  op_.SetParam("dtype", dtype);
  op_.SetParam("is_ascend", is_ascend);
  op_.SetParam("k", k);
  op_.SetParam("ret_typ", ret_typ);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief transpose(data) with attrs axes — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \param axes 
 * \return new symbol
 */
inline Symbol transpose(const std::string& symbol_name,
    Symbol data,
    Shape axes = Shape()) {
  Operator op_("transpose");
  op_.SetInput("data", data);
  op_.SetParam("axes", axes);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief Round input to the nearest integer towards zero.
 * \param symbol_name name of the resulting symbol
 * \param x Input x
 * \return new symbol
 */
inline Symbol trunc(const std::string& symbol_name,
    Symbol x) {
  Operator op_("trunc");
  op_.SetInput("x", x);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief _random_uniform(...) with attrs ctx, dtype, high, low, shape — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param ctx 
 * \param dtype 
 * \param high 
 * \param low 
 * \param shape 
 * \return new symbol
 */
inline Symbol uniform(const std::string& symbol_name,
    const std::string& ctx = "None",
    const std::string& dtype = "float32",
    mx_float high = static_cast<mx_float>(1),
    mx_float low = static_cast<mx_float>(0),
    Shape shape = Shape()) {
  Operator op_("uniform");
  op_.SetParam("ctx", ctx);
  op_.SetParam("dtype", dtype);
  op_.SetParam("high", high);
  op_.SetParam("low", low);
  op_.SetParam("shape", shape);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief UpSampling(...) with attrs multi_input_mode, num_args, num_filter, sample_type, scale, workspace — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data variable-length input symbol list
 * \return new symbol
 */
inline Symbol up_sampling(const std::string& symbol_name,
    const std::vector<Symbol>& data,
    const std::string& multi_input_mode = "concat",
    int num_filter = 0,
    const std::string& sample_type = "nearest",
    int scale = 1,
    int workspace = 512) {
  Operator op_("up_sampling");
  op_.SetParam("num_args", static_cast<int>(data.size()));
  op_.SetParam("multi_input_mode", multi_input_mode);
  op_.SetParam("num_filter", num_filter);
  op_.SetParam("sample_type", sample_type);
  op_.SetParam("scale", scale);
  op_.SetParam("workspace", workspace);
  for (const auto& s : data) op_.AddInput(s);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief ref: src/operator/tensor/control_flow_op.cc (where).
 * \param symbol_name name of the resulting symbol
 * \param condition Input condition
 * \param x Input x
 * \param y Input y
 * \return new symbol
 */
inline Symbol where(const std::string& symbol_name,
    Symbol condition,
    Symbol x,
    Symbol y) {
  Operator op_("where");
  op_.SetInput("condition", condition);
  op_.SetInput("x", x);
  op_.SetInput("y", y);
  return op_.CreateSymbol(symbol_name);
}

/*!
 * \brief zeros_like(data) — registered operator, 1 output.
 * \param symbol_name name of the resulting symbol
 * \param data Input data
 * \return new symbol
 */
inline Symbol zeros_like(const std::string& symbol_name,
    Symbol data) {
  Operator op_("zeros_like");
  op_.SetInput("data", data);
  return op_.CreateSymbol(symbol_name);
}

}  // namespace op
}  // namespace cpp
}  // namespace mxnet

#endif  // MXNET_CPP_OP_H_
