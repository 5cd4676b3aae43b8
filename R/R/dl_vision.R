# Auto-generated by synapseml_tpu.codegen from synapseml_tpu.dl.vision.
# Each function constructs the Python stage via reticulate;
# fit/transform accept data.frames (converted to Tables).

#' DeepVisionClassifier
#' Base of every stage. Subclasses are constructible from kwargs alone plus
#' @param additionalLayersToTrain Number of trailing backbone blocks to unfreeze besides the head (-1 = all)
#' @param backbone Backbone name (resnet18/34/50/101, tiny)
#' @param batchSize Training batch size
#' @param dropoutAUX compat no-op (torchvision aux dropout)
#' @param imageCol Input image column
#' @param imageSize Resize target (square); 0 = as-is
#' @param labelCol The name of the label column
#' @param learningRate Learning rate
#' @param maxEpochs Training epochs
#' @param optimizer adam/adamw/sgd/momentum
#' @param precision float32 or bfloat16 compute
#' @param predictionCol The name of the prediction column
#' @param pretrainedPath Local .msgpack/.npz checkpoint of backbone params
#' @param seed Random seed
#' @param smallImages CIFAR-style stem (3x3 conv, no max-pool)
#' @param stepFn Step hook: fn(step_idx, loss, params, batch_stats, opt_state) after every accepted training step, device arrays as they are; params/opt_state are donated to the next step, so copy inside the call what is kept (FlaxTrainer.fit step_fn)
#' @param storePrefixPath compat no-op (horovod store)
#' @param validationFraction Holdout fraction for val metrics
#' @export
ml_deep_vision_classifier <- function(...) {
  mod <- reticulate::import("synapseml_tpu.dl.vision")
  mod$DeepVisionClassifier(...)
}

#' DeepVisionModel
#' A fitted Transformer produced by an Estimator.
#' @param backbone Backbone name (for reload)
#' @param imageCol Input image column
#' @param imageSize Resize target (square); 0 = as-is
#' @param precision float32 or bfloat16 compute
#' @param predictionCol The name of the prediction column
#' @param smallImages CIFAR-style stem
#' @export
ml_deep_vision_model <- function(...) {
  mod <- reticulate::import("synapseml_tpu.dl.vision")
  mod$DeepVisionModel(...)
}
