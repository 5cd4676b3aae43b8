"""DeepTextClassifier / DeepTextModel — transformer text fine-tuning.

Parity target: deep-learning/src/main/python/synapse/ml/dl/DeepTextClassifier.py
(HuggingFace checkpoint + tokenizer under the Horovod TorchEstimator, default
max_token_len=128). This framework ships a native Flax transformer encoder with
a deterministic feature-hashing tokenizer so training works with zero downloads;
a local HuggingFace Flax checkpoint directory can be supplied instead via
``checkpoint`` when available.

The encoder leaves a mesh axis free for sequence sharding (SURVEY §5.7 stance:
the reference truncates at max_token_len and has no sequence parallelism; the
attention here is ring-shardable via parallel/ring_attention when sequences
outgrow one chip).
"""

from __future__ import annotations

import re
import zlib
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..core import Estimator, HasLabelCol, HasPredictionCol, Model, Param, Table
from .trainer import FlaxTrainer, TrainConfig

_TOKEN_RE = re.compile(r"[a-z0-9']+")
PAD_ID = 0
CLS_ID = 1
_RESERVED = 2


def hash_tokenize(texts, vocab_size: int, max_len: int) -> np.ndarray:
    """Deterministic hash-trick tokenizer (crc32 buckets): lowercase word split →
    bucket ids; [CLS] prepended; zero-padded. The text analog of VW's hashing
    featurizer — no vocabulary artifact to download or ship."""
    out = np.zeros((len(texts), max_len), np.int32)
    out[:, 0] = CLS_ID
    usable = vocab_size - _RESERVED
    for i, t in enumerate(texts):
        toks = _TOKEN_RE.findall(str(t).lower())[: max_len - 1]
        for j, tok in enumerate(toks):
            out[i, j + 1] = _RESERVED + (zlib.crc32(tok.encode()) % usable)
    return out


class TransformerEncoder(nn.Module):
    """``mask_free=True`` drops the PAD attention mask (PAD embeddings are
    learned instead — the TransformerLayerUnit trade) so the attention is
    seq-shardable: inside a ``dl.backbones.seq_attention_scope`` it routes
    through ring/Ulysses, and outside one (predict) the unmasked default
    computes the same values. The param tree is identical either way."""

    vocab_size: int = 32768
    num_layers: int = 4
    num_heads: int = 8
    hidden: int = 256
    mlp_ratio: int = 4
    max_len: int = 128
    num_classes: int = 2
    dropout: float = 0.1
    dtype: Any = jnp.float32
    mask_free: bool = False

    @nn.compact
    def __call__(self, ids, train: bool = True):
        from .backbones import seq_attention_fn

        mask = (ids != PAD_ID)
        x = nn.Embed(self.vocab_size, self.hidden, dtype=self.dtype, name="tok_embed")(ids)
        pos = self.param("pos_embed", nn.initializers.normal(0.02),
                         (self.max_len, self.hidden))
        x = x + pos[None, : ids.shape[1]].astype(self.dtype)
        attn_mask = (None if self.mask_free
                     else mask[:, None, None, :] & mask[:, None, :, None])
        seq_fn = seq_attention_fn() if self.mask_free else None
        for i in range(self.num_layers):
            y = nn.LayerNorm(dtype=self.dtype)(x)
            y = nn.MultiHeadDotProductAttention(
                num_heads=self.num_heads, dtype=self.dtype,
                dropout_rate=self.dropout, deterministic=not train,
                name=f"attn_{i}",
                **({"attention_fn": seq_fn} if seq_fn is not None else {}),
            )(y, y, mask=attn_mask)
            x = x + y
            y = nn.LayerNorm(dtype=self.dtype)(x)
            y = nn.Dense(self.hidden * self.mlp_ratio, dtype=self.dtype)(y)
            y = nn.gelu(y)
            y = nn.Dense(self.hidden, dtype=self.dtype)(y)
            x = x + y
        x = nn.LayerNorm(dtype=self.dtype)(x)
        cls = x[:, 0]                      # [CLS] pooling
        return nn.Dense(self.num_classes, dtype=jnp.float32, name="head")(cls)


class DeepTextClassifier(Estimator, HasLabelCol, HasPredictionCol):
    checkpoint = Param("checkpoint", "Local HuggingFace Flax checkpoint dir (optional)", str)
    textCol = Param("textCol", "Input text column", str, "text")
    maxTokenLen = Param("maxTokenLen", "Truncation length", int, 128)
    batchSize = Param("batchSize", "Training batch size", int, 16)
    maxEpochs = Param("maxEpochs", "Training epochs", int, 1)
    learningRate = Param("learningRate", "Learning rate", float, 1e-4)
    optimizer = Param("optimizer", "adam/adamw/sgd/momentum", str, "adamw")
    vocabSize = Param("vocabSize", "Hash-bucket vocabulary size", int, 32768)
    numLayers = Param("numLayers", "Encoder layers", int, 4)
    numHeads = Param("numHeads", "Attention heads", int, 8)
    hiddenSize = Param("hiddenSize", "Hidden width", int, 256)
    precision = Param("precision", "float32 or bfloat16 compute", str, "float32")
    seed = Param("seed", "Random seed", int, 0)
    seqParallel = Param(
        "seqParallel", "Shard attention over a mesh 'seq' axis (mask-free "
        "attention; attention dropout disabled)", bool, False)
    seqAxisSize = Param(
        "seqAxisSize", "Devices on the 'seq' mesh axis (0 = all local "
        "devices)", int, 0)
    seqAttention = Param(
        "seqAttention", "Sequence-attention variant: auto (perfmodel-routed) "
        "/ ring / ulysses", str, "auto")
    architecture = Param(
        "architecture", "A layer-type-list backbone in place of the dense "
        "encoder, by the source's own config.json keys: "
        "hybrid_override_pattern (one of M Mamba-2, E experts, * causal "
        "attention for each of numLayers), mamba_num_heads, mamba_head_dim, "
        "n_groups, ssm_state_size, conv_kernel, chunk_size, "
        "num_key_value_heads, head_dim, n_routed_experts (the router's "
        "width), num_experts_per_tok, moe_intermediate_size, "
        "moe_shared_expert_intermediate_size, routed_scaling_factor, "
        "norm_eps; held_experts lists the expert ids this chip holds "
        "(dl/hybrid.py). hiddenSize, numLayers, numHeads, vocabSize and "
        "maxTokenLen apply as they do to the encoder", dict)
    stepFn = Param(
        "stepFn", "Step hook: fn(step_idx, loss, params, batch_stats, "
        "opt_state) after every accepted training step, device arrays as "
        "they are; params/opt_state are donated to the next step, so copy "
        "inside the call what is kept (FlaxTrainer.fit step_fn)",
        is_complex=True)

    def _fit(self, df: Table) -> "DeepTextModel":
        texts = list(df[self.getTextCol()])
        labels_raw = np.asarray(df[self.getLabelCol()])
        classes, y = np.unique(labels_raw, return_inverse=True)

        if self.get("checkpoint"):
            return self._fit_hf(texts, y, classes)

        ids = hash_tokenize(texts, self.getVocabSize(), self.getMaxTokenLen())
        seq_on = bool(self.getSeqParallel())
        mesh = None
        if seq_on:
            from ..parallel.mesh import make_mesh

            devs = jax.devices()
            sp = self.getSeqAxisSize() or len(devs)
            dp = max(1, len(devs) // sp)
            mesh = make_mesh({"data": dp, "seq": sp}, devices=devs[: dp * sp])
        dtype = (jnp.bfloat16 if self.getPrecision() == "bfloat16"
                 else jnp.float32)
        if self.get("architecture"):
            if seq_on:
                raise NotImplementedError(
                    "seqParallel with an architecture: the layer-type-list "
                    "backbone has no sequence-sharded mixers")
            model = _backbone(self, len(classes), dtype)
        else:
            model = TransformerEncoder(
                vocab_size=self.getVocabSize(), num_layers=self.getNumLayers(),
                num_heads=self.getNumHeads(), hidden=self.getHiddenSize(),
                max_len=self.getMaxTokenLen(), num_classes=len(classes),
                dtype=dtype, mask_free=seq_on, dropout=0.0 if seq_on else 0.1)
        cfg = TrainConfig(batch_size=self.getBatchSize(), max_epochs=self.getMaxEpochs(),
                          learning_rate=self.getLearningRate(), optimizer=self.getOptimizer(),
                          compute_dtype=self.getPrecision(), seed=self.getSeed(),
                          seq_parallel=seq_on, seq_attention=self.getSeqAttention())
        trainer = FlaxTrainer(model, cfg, mesh=mesh)
        if self.get("architecture"):
            # its parameters do not depend on the length: two positions
            trainer.init(ids[:1, :2], jit=True)
        trainer.fit(ids, y, log_fn=lambda ep: self._log_base("epoch", ep),
                    step_fn=self.get("stepFn"))
        self._log_base("trainingMeasures", trainer.stats["measures"])

        m = DeepTextModel(trainer=trainer, classes=classes)
        m.set("seqParallel", seq_on)
        m.set("vocabSize", self.getVocabSize())
        m.set("maxTokenLen", self.getMaxTokenLen())
        m.set("numLayers", self.getNumLayers())
        m.set("numHeads", self.getNumHeads())
        m.set("hiddenSize", self.getHiddenSize())
        for p in ("textCol", "predictionCol", "architecture"):
            if self.isSet(p):
                m.set(p, self.get(p))
        return m

    def _fit_hf(self, texts, y, classes):
        """Fine-tune a local HuggingFace Flax checkpoint (BERT-class) — the
        reference's DeepTextClassifier path (deep-learning/.../
        DeepTextClassifier.py fine-tunes HF checkpoints under Horovod). The
        checkpoint dir must exist locally (config + flax weights + tokenizer);
        weight acquisition is an environment concern — the reference downloads
        from the hub at fit time, this environment has no egress."""
        import optax

        dtype = (jnp.bfloat16 if self.getPrecision() == "bfloat16"
                 else jnp.float32)
        tok, hf = _load_hf(self.get("checkpoint"), len(classes), dtype=dtype)
        enc = tok(list(map(str, texts)), truncation=True,
                  padding="max_length", max_length=self.getMaxTokenLen(),
                  return_tensors="np")
        ids = enc["input_ids"].astype(np.int32)
        attn = enc["attention_mask"].astype(np.int32)
        labels = np.asarray(y, np.int32)

        lr = self.getLearningRate()
        opt = {"adam": optax.adam, "adamw": optax.adamw, "sgd": optax.sgd,
               "momentum": lambda r: optax.sgd(r, momentum=0.9)}[
            self.getOptimizer()](lr)
        params = hf.params
        opt_state = opt.init(params)
        rng = jax.random.PRNGKey(self.getSeed())

        @jax.jit
        def step(params, opt_state, ids_b, attn_b, y_b, w_b, key):
            def loss_fn(p):
                logits = hf(input_ids=ids_b, attention_mask=attn_b, params=p,
                            dropout_rng=key, train=True).logits
                onehot = jax.nn.one_hot(y_b, logits.shape[-1])
                nll = -jnp.sum(jax.nn.log_softmax(logits) * onehot, axis=-1)
                # w_b masks out pad rows of a trailing partial batch
                return jnp.sum(nll * w_b) / jnp.maximum(jnp.sum(w_b), 1.0)

            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, opt_state = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        n = len(ids)
        bs = min(self.getBatchSize(), n)  # small datasets train on all rows
        order_rng = np.random.default_rng(self.getSeed())
        loss = None
        ones = np.ones(bs, np.float32)
        for epoch in range(self.getMaxEpochs()):
            order = order_rng.permutation(n)
            for s in range(0, n, bs):
                sel = order[s:s + bs]
                w_b = ones
                if len(sel) < bs:
                    # pad the trailing partial batch (keeps one jit shape) and
                    # zero-weight the pad rows so every row trains each epoch
                    w_b = np.zeros(bs, np.float32)
                    w_b[: len(sel)] = 1.0
                    sel = np.concatenate([sel, order[: bs - len(sel)]])
                rng, key = jax.random.split(rng)
                params, opt_state, loss = step(
                    params, opt_state, ids[sel], attn[sel], labels[sel], w_b,
                    key)
            self._log_base("epoch", {"epoch": epoch,
                                     "loss": float(loss) if loss is not None
                                     else None})
        hf.params = params

        m = DeepTextModel(classes=classes, hfModel=hf, hfTokenizer=tok)
        m.set("maxTokenLen", self.getMaxTokenLen())
        for p in ("textCol", "predictionCol"):
            if self.isSet(p):
                m.set(p, self.get(p))
        return m


class DeepTextModel(Model, HasPredictionCol):
    textCol = Param("textCol", "Input text column", str, "text")
    maxTokenLen = Param("maxTokenLen", "Truncation length", int, 128)
    vocabSize = Param("vocabSize", "Hash-bucket vocabulary size", int, 32768)
    numLayers = Param("numLayers", "Encoder layers", int, 4)
    numHeads = Param("numHeads", "Attention heads", int, 8)
    hiddenSize = Param("hiddenSize", "Hidden width", int, 256)
    seqParallel = Param(
        "seqParallel", "Model was trained mask-free for seq sharding", bool,
        False)
    architecture = Param(
        "architecture", "The layer-type-list backbone the model was trained "
        "with (DeepTextClassifier.architecture); unset for the dense encoder",
        dict)

    # class-level defaults: instances materialized by PipelineStage.load
    # bypass __init__
    trainer: Optional[FlaxTrainer] = None
    classes: Optional[np.ndarray] = None
    hf_model = None
    hf_tokenizer = None

    def __init__(self, trainer: Optional[FlaxTrainer] = None,
                 classes: Optional[np.ndarray] = None, hfModel=None,
                 hfTokenizer=None, **kwargs):
        super().__init__(**kwargs)
        self.trainer = trainer
        self.classes = classes
        self.hf_model = hfModel
        self.hf_tokenizer = hfTokenizer

    def _transform(self, df: Table) -> Table:
        from .trainer import softmax_np

        texts = list(df[self.getTextCol()])
        if self.hf_model is not None:
            enc = self.hf_tokenizer(
                list(map(str, texts)), truncation=True, padding="max_length",
                max_length=self.getMaxTokenLen(), return_tensors="np")
            logits = np.asarray(self.hf_model(
                input_ids=enc["input_ids"].astype(np.int32),
                attention_mask=enc["attention_mask"].astype(np.int32),
                train=False).logits)
        else:
            ids = hash_tokenize(texts, self.getVocabSize(),
                                self.getMaxTokenLen())
            logits = self.trainer.predict_logits(ids)
        pred = np.asarray(self.classes)[logits.argmax(-1)]
        out = df.with_column(self.getPredictionCol(), pred)
        return out.with_column("probability", softmax_np(logits))

    def _save_extra(self, path: str) -> None:
        import os

        from flax.serialization import to_bytes

        np.save(os.path.join(path, "classes.npy"), np.asarray(self.classes))
        if self.hf_model is not None:
            hf_dir = os.path.join(path, "hf_checkpoint")
            self.hf_model.save_pretrained(hf_dir)
            self.hf_tokenizer.save_pretrained(hf_dir)
            return
        with open(os.path.join(path, "params.msgpack"), "wb") as f:
            f.write(to_bytes({"params": self.trainer.params}))

    def _load_extra(self, path: str) -> None:
        import os

        from flax.serialization import from_bytes

        self.classes = np.load(os.path.join(path, "classes.npy"), allow_pickle=True)
        hf_dir = os.path.join(path, "hf_checkpoint")
        if os.path.isdir(hf_dir):
            self.hf_tokenizer, self.hf_model = _load_hf(hf_dir,
                                                        len(self.classes))
            self.trainer = None
            return
        if self.get("architecture"):
            model = _backbone(self, len(self.classes), jnp.float32)
        else:
            model = TransformerEncoder(
                vocab_size=self.getVocabSize(), num_layers=self.getNumLayers(),
                num_heads=self.getNumHeads(), hidden=self.getHiddenSize(),
                max_len=self.getMaxTokenLen(), num_classes=len(self.classes),
                mask_free=bool(self.getSeqParallel()))
        trainer = FlaxTrainer(model, TrainConfig())
        trainer.init(np.zeros((1, self.getMaxTokenLen()), np.int32),
                     jit=bool(self.get("architecture")))
        with open(os.path.join(path, "params.msgpack"), "rb") as f:
            blob = from_bytes({"params": trainer.params}, f.read())
        trainer.load_params(blob["params"])
        self.trainer = trainer


def _backbone(stage, num_classes: int, dtype):
    """The backbone ``stage.architecture`` describes (estimator or model)."""
    from .hybrid import HybridArch, HybridBackbone

    arch = HybridArch.from_source(
        stage.get("architecture"), hidden=stage.getHiddenSize(),
        layers=stage.getNumLayers(), heads=stage.getNumHeads(),
        vocab=stage.getVocabSize())
    return HybridBackbone(arch, num_classes=num_classes, dtype=dtype)


def _load_hf(checkpoint: str, num_labels: int, dtype=None):
    """(tokenizer, FlaxAutoModelForSequenceClassification) from a LOCAL
    checkpoint dir; raises a clear error when absent (zero-egress env)."""
    import os

    if not checkpoint or not os.path.isdir(checkpoint):
        raise FileNotFoundError(
            f"HuggingFace checkpoint dir {checkpoint!r} not found; this "
            "environment cannot download from the hub — provide a local dir "
            "with config.json, flax weights, and tokenizer files")
    from transformers import (AutoTokenizer,
                              FlaxAutoModelForSequenceClassification)

    tok = AutoTokenizer.from_pretrained(checkpoint)
    hf = FlaxAutoModelForSequenceClassification.from_pretrained(
        checkpoint, num_labels=num_labels)
    if dtype == jnp.bfloat16:
        hf.params = hf.to_bf16(hf.params)
    return tok, hf
