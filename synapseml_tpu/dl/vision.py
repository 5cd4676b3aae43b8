"""DeepVisionClassifier / DeepVisionModel — Flax fine-tuning estimators.

Parity target: deep-learning/src/main/python/synapse/ml/dl/DeepVisionClassifier.py
(Horovod TorchEstimator subclass, torchvision backbone with swapped head and
optional layer freezing, per-executor NCCL DDP) and DeepVisionModel.py (per-row
predict_fn). Here: a Flax backbone (dl/backbones.py), one jitted train step with
the batch sharded over the ``data`` mesh axis (gradient psum compiled by XLA —
the Horovod-allreduce replacement), and batched inference.

``additionalLayersToTrain`` mirrors the reference semantics
(LitDeepVisionModel.py:56-110): head always trains; that many trailing backbone
blocks are unfrozen in addition; -1 trains everything.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from ..core import Estimator, HasLabelCol, HasPredictionCol, Model, Param, Table
from .backbones import make_backbone
from .trainer import FlaxTrainer, TrainConfig

IMAGENET_MEAN = np.array([0.485, 0.456, 0.406], np.float32)
IMAGENET_STD = np.array([0.229, 0.224, 0.225], np.float32)


def _resolve_images(col, image_size: Optional[int]) -> np.ndarray:
    """Column → (N, H, W, C) float32 in [0,1]. Accepts a 4-D numeric array
    column, an object column of HWC arrays, or a column of file paths."""
    arr = np.asarray(col)
    if arr.dtype == object:
        first = arr[0]
        if isinstance(first, (str, bytes)):
            from ..ops.image import decode_image_files

            arr = decode_image_files(list(arr), image_size)
        else:
            imgs = [np.asarray(a) for a in arr]
            if image_size:
                imgs = [_resize_host(im, image_size) for im in imgs]
            elif len({im.shape for im in imgs}) > 1:
                raise ValueError(
                    "image column contains arrays of differing shapes; set imageSize "
                    "to resize them to a common size")
            arr = np.stack(imgs)
    elif image_size and arr.ndim >= 3 and arr.shape[1] != image_size:
        arr = np.stack([_resize_host(im, image_size) for im in arr])
    if arr.ndim == 3:
        arr = arr[..., None]
    if arr.dtype == np.uint8:
        arr = arr.astype(np.float32) / 255.0
    return np.ascontiguousarray(arr, np.float32)


def _resize_host(img: np.ndarray, size: int) -> np.ndarray:
    """Bilinear resize of one HWC (or HW) image on host via jax.image (CPU)."""
    import jax

    if img.shape[:2] == (size, size):
        return img
    shape = (size, size) + img.shape[2:]
    out = jax.image.resize(img.astype(np.float32), shape, method="bilinear")
    return np.asarray(out)


def _normalize(images: np.ndarray) -> np.ndarray:
    if images.shape[-1] == 3:
        return (images - IMAGENET_MEAN) / IMAGENET_STD
    return images


class DeepVisionClassifier(Estimator, HasLabelCol, HasPredictionCol):
    backbone = Param("backbone", "Backbone name (resnet18/34/50/101, tiny)", str, "resnet50")
    additionalLayersToTrain = Param(
        "additionalLayersToTrain",
        "Number of trailing backbone blocks to unfreeze besides the head (-1 = all)",
        int, 2)
    batchSize = Param("batchSize", "Training batch size", int, 16)
    maxEpochs = Param("maxEpochs", "Training epochs", int, 1)
    learningRate = Param("learningRate", "Learning rate", float, 1e-3)
    optimizer = Param("optimizer", "adam/adamw/sgd/momentum", str, "adam")
    imageCol = Param("imageCol", "Input image column", str, "image")
    imageSize = Param("imageSize", "Resize target (square); 0 = as-is", int, 0)
    dropoutAUX = Param("dropoutAUX", "compat no-op (torchvision aux dropout)", float, 0.01)
    storePrefixPath = Param("storePrefixPath", "compat no-op (horovod store)", str)
    precision = Param("precision", "float32 or bfloat16 compute", str, "float32")
    seed = Param("seed", "Random seed", int, 0)
    pretrainedPath = Param("pretrainedPath", "Local .msgpack/.npz checkpoint of backbone params", str)
    validationFraction = Param("validationFraction", "Holdout fraction for val metrics", float, 0.0)
    smallImages = Param("smallImages", "CIFAR-style stem (3x3 conv, no max-pool)", bool, False)
    stepFn = Param(
        "stepFn", "Step hook: fn(step_idx, loss, params, batch_stats, "
        "opt_state) after every accepted training step, device arrays as "
        "they are; params/opt_state are donated to the next step, so copy "
        "inside the call what is kept (FlaxTrainer.fit step_fn)",
        is_complex=True)

    def _fit(self, df: Table) -> "DeepVisionModel":
        images = _resolve_images(df[self.getImageCol()], self.getImageSize() or None)
        labels_raw = np.asarray(df[self.getLabelCol()])
        classes, y = np.unique(labels_raw, return_inverse=True)   # any dtype, incl. strings
        num_classes = len(classes)

        model = make_backbone(self.getBackbone(), num_classes,
                              dtype=jnp.bfloat16 if self.getPrecision() == "bfloat16" else jnp.float32,
                              small_images=self.getSmallImages())
        X = _normalize(images)

        freeze_regex = self._freeze_regex(model, X)
        cfg = TrainConfig(batch_size=self.getBatchSize(), max_epochs=self.getMaxEpochs(),
                          learning_rate=self.getLearningRate(), optimizer=self.getOptimizer(),
                          freeze_regex=freeze_regex,
                          compute_dtype=self.getPrecision(), seed=self.getSeed())
        trainer = FlaxTrainer(model, cfg)
        trainer.init(X[:1])
        if self.get("pretrainedPath"):
            trainer.load_params(*_load_checkpoint(self.get("pretrainedPath"), trainer))

        valid = None
        vf = self.getValidationFraction()
        if vf > 0:
            # shuffled holdout — a sorted input table must not yield a
            # single-class validation split
            perm = np.random.default_rng(self.getSeed()).permutation(len(X))
            nv = max(int(len(X) * vf), 1)
            valid = (X[perm[:nv]], y[perm[:nv]])
            X, y = X[perm[nv:]], y[perm[nv:]]
        trainer.fit(X, y, valid=valid, log_fn=lambda ep: self._log_base("epoch", ep),
                    step_fn=self.get("stepFn"))
        self._log_base("trainingMeasures", trainer.stats["measures"])

        m = DeepVisionModel(trainer=trainer, classes=classes)
        m.set("backbone", self.getBackbone())
        m.set("smallImages", self.getSmallImages())
        m.set("precision", self.getPrecision())
        m._input_shape = list(X.shape[1:])
        for p in ("imageCol", "predictionCol", "imageSize"):
            if self.isSet(p):
                m.set(p, self.get(p))
        return m

    def _freeze_regex(self, model, X) -> Optional[str]:
        k = self.getAdditionalLayersToTrain()
        if k < 0:
            return None
        # requesting more unfrozen layers than exist means "train everything"
        import jax

        variables = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                                      jnp.zeros_like(jnp.asarray(X[:1])),
                                                      train=False))
        top = list(variables["params"].keys())
        # flax returns dict keys alphabetically (Block_10 < Block_2); order by
        # the numeric suffix so "trailing k blocks" means network order
        import re as _re

        def _block_order(name):
            m = _re.search(r"(\d+)$", name)
            return int(m.group(1)) if m else -1

        blocks = sorted([t for t in top if "Block" in t], key=_block_order)
        if not blocks or k >= len(blocks):
            return None   # blockless backbone, or unfreeze request covers all blocks
        trainable = set(blocks[len(blocks) - k:] if k else [])
        trainable.add("head")
        frozen = [t for t in top if t not in trainable]
        if not frozen:
            return None
        return r"^(" + "|".join(frozen) + r")/"


class DeepVisionModel(Model, HasPredictionCol):
    imageCol = Param("imageCol", "Input image column", str, "image")
    imageSize = Param("imageSize", "Resize target (square); 0 = as-is", int, 0)
    backbone = Param("backbone", "Backbone name (for reload)", str, "resnet50")
    smallImages = Param("smallImages", "CIFAR-style stem", bool, False)
    precision = Param("precision", "float32 or bfloat16 compute", str, "float32")

    def __init__(self, trainer: Optional[FlaxTrainer] = None,
                 classes: Optional[np.ndarray] = None, **kwargs):
        super().__init__(**kwargs)
        self.trainer = trainer
        self.classes = classes
        self._input_shape = None

    def _transform(self, df: Table) -> Table:
        from .trainer import softmax_np

        X = _normalize(_resolve_images(df[self.getImageCol()], self.getImageSize() or None))
        logits = self.trainer.predict_logits(X)
        pred = self.classes[logits.argmax(-1)] if self.classes is not None else logits.argmax(-1)
        if np.issubdtype(np.asarray(pred).dtype, np.number):
            pred = np.asarray(pred, np.float64)
        out = df.with_column(self.getPredictionCol(), pred)
        return out.with_column("probability", softmax_np(logits))

    def _save_extra(self, path: str) -> None:
        import json
        import os

        from flax.serialization import to_bytes

        with open(os.path.join(path, "params.msgpack"), "wb") as f:
            f.write(to_bytes({"params": self.trainer.params,
                              "batch_stats": self.trainer.batch_stats}))
        np.save(os.path.join(path, "classes.npy"), self.classes)
        with open(os.path.join(path, "arch.json"), "w") as f:
            json.dump({"input_shape": self._input_shape}, f)

    def _load_extra(self, path: str) -> None:
        import json
        import os

        from flax.serialization import from_bytes

        self.classes = np.load(os.path.join(path, "classes.npy"), allow_pickle=True)
        with open(os.path.join(path, "arch.json")) as f:
            self._input_shape = json.load(f)["input_shape"]
        model = make_backbone(self.getBackbone(), len(self.classes),
                              dtype=jnp.bfloat16 if self.getPrecision() == "bfloat16" else jnp.float32,
                              small_images=self.getSmallImages())
        trainer = FlaxTrainer(model, TrainConfig(compute_dtype=self.getPrecision()))
        trainer.init(np.zeros([1] + list(self._input_shape), np.float32))
        with open(os.path.join(path, "params.msgpack"), "rb") as f:
            blob = from_bytes({"params": trainer.params,
                               "batch_stats": trainer.batch_stats}, f.read())
        trainer.load_params(blob["params"], blob.get("batch_stats"))
        self.trainer = trainer


def _load_checkpoint(path: str, trainer: FlaxTrainer):
    from flax.serialization import from_bytes

    with open(path, "rb") as f:
        blob = from_bytes({"params": trainer.params, "batch_stats": trainer.batch_stats},
                          f.read())
    return blob["params"], blob.get("batch_stats")
