"""DenseNet-style CNN family — dense connectivity via channel concat.

Parity target: SURVEY.md §2 "Model zoo" ("TF VGG/DenseNet-style CNNs").
DenseNet-BC shape: dense blocks where every layer consumes the concat of
ALL previous feature maps (growth rate k per layer), 1×1 bottlenecks
(4k) before each 3×3, and compression-0.5 transitions (1×1 conv +
2×2 avg-pool) between blocks. TPU notes: the concats are pure layout —
XLA fuses them into the conv input reads — and convs lower straight
onto the MXU; bf16 compute with f32 params/BN stats like the other
image families; global-average-pool head; DP over the trial sub-mesh.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Sequence

import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from rafiki_tpu.constants import TaskType
from rafiki_tpu.model import (CategoricalKnob, FixedKnob, FloatKnob,
                              KnobConfig, PolicyKnob)
from rafiki_tpu.models._cnn_base import BatchNormCNNTemplate

#: layers per dense block
VARIANTS: Dict[str, Sequence[int]] = {
    "densenet-s": (2, 4, 4),
    "densenet-m": (4, 8, 8),
}


class _DenseLayer(nn.Module):
    growth: int
    dtype: Any

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool) -> jnp.ndarray:
        norm = partial(nn.BatchNorm, use_running_average=not train,
                       momentum=0.9, dtype=self.dtype)
        # BC bottleneck: BN-relu-1x1(4k) then BN-relu-3x3(k)
        y = nn.relu(norm()(x))
        y = nn.Conv(4 * self.growth, (1, 1), use_bias=False,
                    dtype=self.dtype)(y)
        y = nn.relu(norm()(y))
        y = nn.Conv(self.growth, (3, 3), padding="SAME", use_bias=False,
                    dtype=self.dtype)(y)
        return jnp.concatenate([x, y], axis=-1)  # dense connectivity


class DenseNet(nn.Module):
    """Dense blocks + compression transitions over (B, H, W, C)."""

    block_sizes: Sequence[int]
    growth: int
    n_classes: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool = False) -> jnp.ndarray:
        norm = partial(nn.BatchNorm, use_running_average=not train,
                       momentum=0.9, dtype=self.dtype)
        x = x.astype(self.dtype)
        x = nn.Conv(2 * self.growth, (3, 3), padding="SAME",
                    use_bias=False, dtype=self.dtype, name="stem")(x)
        for b, n_layers in enumerate(self.block_sizes):
            for _ in range(n_layers):
                x = _DenseLayer(self.growth, self.dtype)(x, train)
            if b < len(self.block_sizes) - 1:
                # transition: BN-relu, 1x1 compression 0.5, 2x2 avg-pool
                x = nn.relu(norm()(x))
                x = nn.Conv(max(self.growth, x.shape[-1] // 2), (1, 1),
                            use_bias=False, dtype=self.dtype)(x)
                if min(x.shape[1], x.shape[2]) >= 2:
                    x = nn.avg_pool(x, (2, 2), strides=(2, 2))
        x = nn.relu(norm()(x))
        x = jnp.mean(x, axis=(1, 2))  # GAP head
        return nn.Dense(self.n_classes, dtype=jnp.float32,
                        name="head")(x.astype(jnp.float32))


class DenseNetClassifier(BatchNormCNNTemplate):
    """DenseNet template: image classification, DP over the trial
    sub-mesh, SGD-momentum + cosine (shared BatchNorm-CNN recipe —
    ``models/_cnn_base.py``)."""

    @staticmethod
    def get_knob_config() -> KnobConfig:
        return {
            "max_epochs": FixedKnob(5),
            "variant": CategoricalKnob(list(VARIANTS),
                                       shape_relevant=True),
            "growth": CategoricalKnob([8, 12, 24], shape_relevant=True),
            "learning_rate": FloatKnob(1e-3, 1.0, is_exp=True),
            "weight_decay": FloatKnob(1e-5, 1e-2, is_exp=True),
            "batch_size": CategoricalKnob([32, 64, 128, 256],
                                          shape_relevant=True),
            "bf16": CategoricalKnob([True, False]),
            "quick_train": PolicyKnob("QUICK_TRAIN"),
            "share_params": PolicyKnob("SHARE_PARAMS"),
        }

    def _module(self) -> DenseNet:
        assert self._n_classes is not None
        dtype = jnp.bfloat16 if self.knobs.get("bf16", True) else jnp.float32
        return DenseNet(block_sizes=VARIANTS[str(self.knobs["variant"])],
                        growth=int(self.knobs["growth"]),
                        n_classes=int(self._n_classes), dtype=dtype)


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
            DenseNetClassifier, TaskType.IMAGE_CLASSIFICATION, train_p,
            val_p, queries=[ds.images[0]],
            knobs={"variant": "densenet-s", "growth": 12,
                   "batch_size": 32, "max_epochs": 5, "learning_rate": 0.05,
                   "weight_decay": 1e-4, "bf16": False,
                   "quick_train": False, "share_params": False})
        print("prediction:", int(np.argmax(preds[0])))
