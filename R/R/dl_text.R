# Auto-generated by synapseml_tpu.codegen from synapseml_tpu.dl.text.
# Each function constructs the Python stage via reticulate;
# fit/transform accept data.frames (converted to Tables).

#' DeepTextClassifier
#' Base of every stage. Subclasses are constructible from kwargs alone plus
#' @param architecture A layer-type-list backbone in place of the dense encoder, by the source's own config.json keys. nemotron_h: hybrid_override_pattern (one of M Mamba-2, E experts, * causal attention for each of numLayers), mamba_num_heads, mamba_head_dim, n_groups, ssm_state_size, conv_kernel, chunk_size, num_key_value_heads, head_dim, n_routed_experts (the router's width), num_experts_per_tok, moe_intermediate_size, moe_shared_expert_intermediate_size, routed_scaling_factor, norm_eps. lfm2_moe: layer_types (conv or full_attention for each of numLayers), num_dense_layers, conv_L_cache, intermediate_size, num_key_value_heads, rope_parameters, num_experts (the router's width), num_experts_per_tok, moe_intermediate_size, use_expert_bias, routed_scaling_factor, norm_eps. held_experts lists the expert ids this chip holds; expert_bias_std seeds an untrained expert_bias (dl/hybrid.py). hiddenSize, numLayers, numHeads, vocabSize and maxTokenLen apply as they do to the encoder
#' @param batchSize Training batch size
#' @param checkpoint Local HuggingFace Flax checkpoint dir (optional)
#' @param hiddenSize Hidden width
#' @param labelCol The name of the label column
#' @param learningRate Learning rate
#' @param maxEpochs Training epochs
#' @param maxTokenLen Truncation length
#' @param numHeads Attention heads
#' @param numLayers Encoder layers
#' @param optimizer adam/adamw/sgd/momentum
#' @param precision float32 or bfloat16 compute
#' @param predictionCol The name of the prediction column
#' @param seed Random seed
#' @param seqAttention Sequence-attention variant: auto (perfmodel-routed) / ring / ulysses
#' @param seqAxisSize Devices on the 'seq' mesh axis (0 = all local devices)
#' @param seqParallel Shard attention over a mesh 'seq' axis (mask-free attention; attention dropout disabled)
#' @param stepFn Step hook: fn(step_idx, loss, params, batch_stats, opt_state) after every accepted training step, device arrays as they are; params/opt_state are donated to the next step, so copy inside the call what is kept (FlaxTrainer.fit step_fn)
#' @param textCol Input text column
#' @param vocabSize Hash-bucket vocabulary size
#' @export
ml_deep_text_classifier <- function(...) {
  mod <- reticulate::import("synapseml_tpu.dl.text")
  mod$DeepTextClassifier(...)
}

#' DeepTextModel
#' A fitted Transformer produced by an Estimator.
#' @param architecture The layer-type-list backbone the model was trained with (DeepTextClassifier.architecture); unset for the dense encoder
#' @param hiddenSize Hidden width
#' @param maxTokenLen Truncation length
#' @param numHeads Attention heads
#' @param numLayers Encoder layers
#' @param predictionCol The name of the prediction column
#' @param seqParallel Model was trained mask-free for seq sharding
#' @param textCol Input text column
#' @param vocabSize Hash-bucket vocabulary size
#' @export
ml_deep_text_model <- function(...) {
  mod <- reticulate::import("synapseml_tpu.dl.text")
  mod$DeepTextModel(...)
}
