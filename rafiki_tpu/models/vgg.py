"""VGG-style CNN family — the reference zoo's second CNN shape.

Parity target: SURVEY.md §2 "Model zoo" lists "TF VGG/DenseNet-style
CNNs" next to the feed-forward and ResNet families; this is the
TPU-native VGG: plain 3×3 conv stacks (+BatchNorm — the VGG-BN variant,
which actually trains without tricks) with stage-wise max-pool, a
global-average-pool head instead of VGG's 3 giant FC layers (GAP keeps
the net resolution-agnostic and drops ~90% of the parameters for free),
bf16 compute with f32 params/BN stats, data-parallel over the trial's
sub-mesh via NamedSharding. Convs are XLA's business — they lower
straight onto the MXU; no hand kernels needed here.
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

#: convs per stage (each stage ends in 2x2 max-pool); channel width
#: doubles per stage from `width` up to 8x, VGG-style
VARIANTS: Dict[str, Sequence[int]] = {
    "vgg11": (1, 1, 2, 2, 2),
    "vgg13": (2, 2, 2, 2, 2),
    "vgg16": (2, 2, 3, 3, 3),
}


class VGG(nn.Module):
    """Conv stacks over (B, H, W, C); logits head on global avg pool."""

    stage_sizes: Sequence[int]
    width: int
    n_classes: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool = False) -> jnp.ndarray:
        norm = partial(nn.BatchNorm, use_running_average=not train,
                       momentum=0.9, dtype=self.dtype)
        x = x.astype(self.dtype)
        for stage, n_convs in enumerate(self.stage_sizes):
            ch = min(self.width * (2 ** stage), self.width * 8)
            for _ in range(n_convs):
                x = nn.Conv(ch, (3, 3), padding="SAME", use_bias=False,
                            dtype=self.dtype)(x)
                x = nn.relu(norm()(x))
            if min(x.shape[1], x.shape[2]) >= 2:  # never pool below 1px
                x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = jnp.mean(x, axis=(1, 2))  # GAP: resolution-agnostic head
        return nn.Dense(self.n_classes, dtype=jnp.float32,
                        name="head")(x.astype(jnp.float32))


class VGGClassifier(BatchNormCNNTemplate):
    """VGG template: image classification, DP over the trial sub-mesh,
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
            "learning_rate": FloatKnob(1e-3, 1.0, is_exp=True),
            "weight_decay": FloatKnob(1e-5, 1e-2, is_exp=True),
            "batch_size": CategoricalKnob([32, 64, 128, 256],
                                          shape_relevant=True),
            "bf16": CategoricalKnob([True, False]),
            "quick_train": PolicyKnob("QUICK_TRAIN"),
            "share_params": PolicyKnob("SHARE_PARAMS"),
        }

    def _module(self) -> VGG:
        assert self._n_classes is not None
        width = max(8, int(64 * float(self.knobs["width_mult"])))
        dtype = jnp.bfloat16 if self.knobs.get("bf16", True) else jnp.float32
        return VGG(stage_sizes=VARIANTS[str(self.knobs["variant"])],
                   width=width, n_classes=int(self._n_classes), dtype=dtype)


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
            VGGClassifier, TaskType.IMAGE_CLASSIFICATION, train_p, val_p,
            queries=[ds.images[0]],
            knobs={"variant": "vgg11", "width_mult": 0.25,
                   "batch_size": 32, "max_epochs": 5, "learning_rate": 0.05,
                   "weight_decay": 1e-4, "bf16": False,
                   "quick_train": False, "share_params": False})
        print("prediction:", int(np.argmax(preds[0])))
