"""ResNet — the BOHB-search workhorse family (BASELINE.md config #2).

Parity target: the reference zoo's VGG/DenseNet-style TF CNN templates
(SURVEY.md §2 "Model zoo") and benchmark config #2 ("ResNet-50 / ImageNet
with BOHB search across a TPU slice"). TPU-first design notes:

- Convolutions lower straight onto the MXU via XLA; there is no Pallas
  kernel here on purpose — conv+BN+relu is XLA's best-fused path already.
- BatchNorm statistics are **globally correct under data parallelism for
  free**: the batch axis is sharded over the mesh's ``data`` axis and the
  train step is jitted over the mesh, so GSPMD turns the batch-mean
  reductions into cross-device collectives (no hand-written psum, unlike
  torch's SyncBatchNorm).
- Mixed precision: params and BN stats stay f32; compute dtype is bf16 by
  knob (MXU-native).
- Small-image inputs (CIFAR/FashionMNIST-scale) get a 3x3/stride-1 stem
  with no max-pool; ImageNet-scale inputs the classic 7x7/stride-2 stem.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Tuple

import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from rafiki_tpu.constants import TaskType
from rafiki_tpu.model import (CategoricalKnob, FixedKnob, FloatKnob,
                              KnobConfig, PolicyKnob)
from rafiki_tpu.models._cnn_base import BatchNormCNNTemplate

#: variant name -> (stage sizes, use bottleneck blocks)
VARIANTS: Dict[str, Tuple[Tuple[int, ...], bool]] = {
    "resnet18": ((2, 2, 2, 2), False),
    "resnet34": ((3, 4, 6, 3), False),
    "resnet50": ((3, 4, 6, 3), True),
    "resnet101": ((3, 4, 23, 3), True),
}


class _Block(nn.Module):
    """Basic residual block: 3x3 conv ×2."""

    filters: int
    strides: int
    dtype: Any

    @nn.compact
    def __call__(self, x, train: bool):
        norm = partial(nn.BatchNorm, use_running_average=not train,
                       momentum=0.9, dtype=self.dtype)
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        residual = x
        y = conv(self.filters, (3, 3), (self.strides, self.strides))(x)
        y = norm()(y)
        y = nn.relu(y)
        y = conv(self.filters, (3, 3))(y)
        # zero-init final BN scale: residual branch starts as identity
        y = norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = conv(self.filters, (1, 1),
                            (self.strides, self.strides),
                            name="shortcut")(residual)
            residual = norm(name="shortcut_bn")(residual)
        return nn.relu(residual + y)


class _Bottleneck(nn.Module):
    """Bottleneck residual block: 1x1 → 3x3 → 1x1 (4× expansion)."""

    filters: int
    strides: int
    dtype: Any

    @nn.compact
    def __call__(self, x, train: bool):
        norm = partial(nn.BatchNorm, use_running_average=not train,
                       momentum=0.9, dtype=self.dtype)
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        residual = x
        y = conv(self.filters, (1, 1))(x)
        y = norm()(y)
        y = nn.relu(y)
        # stride on the 3x3 (the "v1.5" placement — better accuracy than
        # striding the first 1x1)
        y = conv(self.filters, (3, 3), (self.strides, self.strides))(y)
        y = norm()(y)
        y = nn.relu(y)
        y = conv(self.filters * 4, (1, 1))(y)
        y = norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = conv(self.filters * 4, (1, 1),
                            (self.strides, self.strides),
                            name="shortcut")(residual)
            residual = norm(name="shortcut_bn")(residual)
        return nn.relu(residual + y)


class ResNet(nn.Module):
    """ResNet over (B, H, W, C) images.

    ``resnet50`` = stage_sizes (3,4,6,3) with bottleneck=True, width=64.
    """

    stage_sizes: Sequence[int] = (3, 4, 6, 3)
    bottleneck: bool = True
    width: int = 64
    n_classes: int = 1000
    small_inputs: bool = False  # CIFAR-style stem
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        norm = partial(nn.BatchNorm, use_running_average=not train,
                       momentum=0.9, dtype=self.dtype)
        x = x.astype(self.dtype)
        if self.small_inputs:
            x = nn.Conv(self.width, (3, 3), use_bias=False,
                        dtype=self.dtype, name="stem")(x)
        else:
            x = nn.Conv(self.width, (7, 7), (2, 2), use_bias=False,
                        dtype=self.dtype, name="stem")(x)
        x = norm(name="stem_bn")(x)
        x = nn.relu(x)
        if not self.small_inputs:
            x = nn.max_pool(x, (3, 3), (2, 2), padding="SAME")
        block: Callable[..., Any] = _Bottleneck if self.bottleneck else _Block
        for i, n_blocks in enumerate(self.stage_sizes):
            filters = self.width * (2 ** i)
            for j in range(n_blocks):
                strides = 2 if i > 0 and j == 0 else 1
                x = block(filters, strides, self.dtype,
                          name=f"stage{i}_block{j}")(x, train=train)
        x = jnp.mean(x, axis=(1, 2))  # global average pool
        return nn.Dense(self.n_classes, dtype=jnp.float32, name="head")(
            x.astype(jnp.float32))


class ResNetClassifier(BatchNormCNNTemplate):
    """ResNet template: image classification, DP over the trial sub-mesh,
    SGD-momentum with cosine decay (shared BatchNorm-CNN recipe —
    ``models/_cnn_base.py``)."""

    @staticmethod
    def get_knob_config() -> KnobConfig:
        return {
            "max_epochs": FixedKnob(5),
            "variant": CategoricalKnob(list(VARIANTS),
                                       shape_relevant=True),
            "width_mult": CategoricalKnob([0.25, 0.5, 1.0],
                                          shape_relevant=True),
            # traceable: continuous optimizer knobs are gang-lane-ready
            # (they never fork the compiled program); the BatchNorm CNN
            # recipe still trains per-trial until a gang spec lands, but
            # the trial scheduler already buckets on the structural
            # knobs only
            "learning_rate": FloatKnob(1e-3, 1.0, is_exp=True,
                                       traceable=True),
            "weight_decay": FloatKnob(1e-5, 1e-2, is_exp=True,
                                      traceable=True),
            "batch_size": CategoricalKnob([32, 64, 128, 256],
                                          shape_relevant=True),
            "bf16": CategoricalKnob([True, False]),
            "quick_train": PolicyKnob("QUICK_TRAIN"),
            "share_params": PolicyKnob("SHARE_PARAMS"),
        }

    def _module(self) -> ResNet:
        assert self._n_classes is not None and self._image_shape is not None
        stages, bottleneck = VARIANTS[str(self.knobs["variant"])]
        width = max(8, int(64 * float(self.knobs["width_mult"])))
        small = min(self._image_shape[0], self._image_shape[1]) < 64
        dtype = jnp.bfloat16 if self.knobs.get("bf16", True) else jnp.float32
        return ResNet(stage_sizes=stages, bottleneck=bottleneck, width=width,
                      n_classes=int(self._n_classes), small_inputs=small,
                      dtype=dtype)


if __name__ == "__main__":  # reference-style self-test block
    import tempfile

    from rafiki_tpu.utils.platform import apply_platform_env

    apply_platform_env()  # the shared compile cache

    from rafiki_tpu.data import generate_image_classification_dataset
    from rafiki_tpu.model import test_model_class

    with tempfile.TemporaryDirectory() as d:
        train_p = f"{d}/train.npz"
        val_p = f"{d}/val.npz"
        generate_image_classification_dataset(train_p, 256, seed=0)
        ds = generate_image_classification_dataset(val_p, 64, seed=1)
        preds = test_model_class(
            ResNetClassifier, TaskType.IMAGE_CLASSIFICATION, train_p, val_p,
            queries=[ds.images[0]],
            knobs={"variant": "resnet18", "width_mult": 0.25,
                   "batch_size": 32, "max_epochs": 5, "learning_rate": 0.1,
                   "weight_decay": 1e-4, "bf16": False,
                   "quick_train": False, "share_params": False})
        print("prediction:", int(np.argmax(preds[0])))
