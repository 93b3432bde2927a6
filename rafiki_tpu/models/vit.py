"""ViT — the north-star model family (BASELINE.md config #3).

The flax module runs its two hot ops through the Pallas kernels
(``rafiki_tpu.ops``): patch embedding as the fused MXU matmul and
attention as flash attention with online softmax. The ``ViTBase16`` template
wraps it in the model contract with data-parallel training over the
trial's TPU sub-mesh (gradients all-reduced by XLA via NamedSharding —
SURVEY.md §2.2 "data-parallel over ICI").
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn

from rafiki_tpu.constants import TaskType
from rafiki_tpu.data import batch_iterator, \
    load_image_classification_dataset
from rafiki_tpu.model import (BaseModel, CategoricalKnob, FixedKnob,
                              FloatKnob, IntegerKnob, KnobConfig, PolicyKnob,
                              TrainContext, bucketed_forward, conform_images,
                              same_tree_shapes, train_epoch)
from rafiki_tpu.ops.attention import flash_attention
from rafiki_tpu.ops.patch_embed import patch_embed
from rafiki_tpu.parallel.sharding import (batch_sharding, make_mesh,
                                          replicated)


class _Attention(nn.Module):
    n_heads: int
    dtype: Any = None

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        b, s, d = x.shape
        dh = d // self.n_heads
        qkv = nn.Dense(3 * d, dtype=self.dtype, name="qkv")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(t):
            return t.reshape(b, s, self.n_heads, dh).transpose(0, 2, 1, 3)

        o = flash_attention(heads(q), heads(k), heads(v))
        o = o.transpose(0, 2, 1, 3).reshape(b, s, d)
        return nn.Dense(d, dtype=self.dtype, name="proj")(o)


class _Block(nn.Module):
    n_heads: int
    mlp_dim: int
    dtype: Any = None

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        # LayerNorms reduce in f32 (dtype=None) for stability; the matmuls
        # — where the MXU time is — run in ``dtype`` (bf16 on TPU: f32
        # matmuls lower to multi-pass bf16 on the MXU at ~1/3 the rate)
        x = x + _Attention(self.n_heads, self.dtype,
                           name="attn")(nn.LayerNorm()(x))
        y = nn.LayerNorm()(x)
        y = nn.Dense(self.mlp_dim, dtype=self.dtype)(y)
        y = nn.gelu(y)
        y = nn.Dense(x.shape[-1], dtype=self.dtype)(y)
        return x + y


class _PatchEmbed(nn.Module):
    """Pallas-fused patch projection as a flax layer."""

    patch_size: int
    hidden_dim: int
    dtype: Any = None

    @nn.compact
    def __call__(self, images: jnp.ndarray) -> jnp.ndarray:
        p = self.patch_size
        c = images.shape[-1]
        w = self.param("kernel", nn.initializers.lecun_normal(),
                       (p * p * c, self.hidden_dim))
        b = self.param("bias", nn.initializers.zeros, (self.hidden_dim,))
        if self.dtype is not None:
            images, w, b = (images.astype(self.dtype), w.astype(self.dtype),
                            b.astype(self.dtype))
        return patch_embed(images, w, b, p)


class ViT(nn.Module):
    """Vision Transformer over (B, H, W, C) images.

    ViT-B/16 = patch_size=16, hidden_dim=768, depth=12, n_heads=12,
    mlp_dim=3072.
    """

    patch_size: int = 16
    hidden_dim: int = 768
    depth: int = 12
    n_heads: int = 12
    mlp_dim: int = 3072
    n_classes: int = 1000
    # compute dtype for the matmul-heavy layers (params always f32).
    # None = promote (f32 compute); templates pass bf16 on TPU, where f32
    # matmuls cost ~3x on the MXU.
    dtype: Any = None
    # gradient checkpointing per transformer block: drop block-internal
    # activations in the forward and recompute them in the backward —
    # trades ~1/3 more FLOPs for O(depth) less activation HBM, buying
    # the larger train batches that raise MXU utilization. Identical
    # math (same params, same outputs, same grads).
    remat: bool = False

    @nn.compact
    def __call__(self, images: jnp.ndarray) -> jnp.ndarray:
        x = _PatchEmbed(self.patch_size, self.hidden_dim, self.dtype,
                        name="patch_embed")(images)
        b, n, d = x.shape
        cls = self.param("cls", nn.initializers.zeros, (1, 1, d))
        x = jnp.concatenate(
            [jnp.broadcast_to(cls, (b, 1, d)).astype(x.dtype), x], axis=1)
        pos = self.param("pos_embed",
                         nn.initializers.normal(0.02), (1, n + 1, d))
        x = x + pos.astype(x.dtype)
        block_cls = nn.remat(_Block) if self.remat else _Block
        for i in range(self.depth):
            x = block_cls(self.n_heads, self.mlp_dim, self.dtype,
                          name=f"block_{i}")(x)
        x = nn.LayerNorm(name="final_norm")(x)
        return nn.Dense(self.n_classes, name="head")(x[:, 0])


class ViTBase16(BaseModel):
    """ViT template: image classification with DP over the trial sub-mesh."""

    TASKS = (TaskType.IMAGE_CLASSIFICATION,)

    @staticmethod
    def get_knob_config() -> KnobConfig:
        return {
            "max_epochs": FixedKnob(5),
            "patch_size": CategoricalKnob([4, 7, 14, 16],
                                          shape_relevant=True),
            # every hidden_dim is divisible by every n_heads choice, so the
            # tuner's (hidden_dim, n_heads) point is exactly the model built
            # (no silent head-count remapping to pollute the search history)
            "hidden_dim": CategoricalKnob([96, 192, 384, 768],
                                          shape_relevant=True),
            "depth": IntegerKnob(2, 12, shape_relevant=True),
            "n_heads": CategoricalKnob([4, 8, 12], shape_relevant=True),
            "learning_rate": FloatKnob(1e-5, 1e-2, is_exp=True),
            "weight_decay": FloatKnob(1e-5, 1e-1, is_exp=True),
            "warmup_frac": FloatKnob(0.0, 0.3),
            "batch_size": CategoricalKnob([16, 32, 64, 128],
                                          shape_relevant=True),
            "bf16": CategoricalKnob([True, False]),
            # gradient checkpointing: bigger batches for ~1/3 extra
            # FLOPs — the knob the tuner flips when batch_size is HBM-
            # bound on TPU (identical math either way)
            "remat": FixedKnob(False),
            "quick_train": PolicyKnob("QUICK_TRAIN"),
            "share_params": PolicyKnob("SHARE_PARAMS"),
        }

    def __init__(self, **knobs: Any) -> None:
        super().__init__(**knobs)
        self._params: Optional[Any] = None
        self._n_classes: Optional[int] = None
        self._image_shape: Optional[Sequence[int]] = None
        self._fwd: Optional[Any] = None  # cached jitted forward
        #: input-normalization contract the ACTIVE params were trained
        #: under; fresh trains use v2, load_parameters adopts the
        #: checkpoint's version so old params keep serving correctly
        self._prep_version: int = 2

    # ---- internals ----
    def _module(self) -> ViT:
        k = self.knobs
        hd = int(k["hidden_dim"])
        heads = int(k["n_heads"])
        if hd % heads:
            raise ValueError(f"hidden_dim={hd} not divisible by "
                             f"n_heads={heads}")
        # compute dtype follows the bf16 knob: params stay f32, matmuls
        # run bf16 on the MXU (f32 would lower to ~3x-cost multi-pass)
        return ViT(patch_size=int(k["patch_size"]), hidden_dim=hd,
                   depth=int(k["depth"]), n_heads=heads,
                   mlp_dim=4 * hd, n_classes=int(self._n_classes),
                   dtype=self._dtype(),
                   remat=bool(k.get("remat", False)))

    def _prep(self, images: np.ndarray) -> np.ndarray:
        if self._prep_version == 1:
            # v1-checkpoint compatibility: params trained on [0, 1]
            # inputs must keep seeing [0, 1] at serving time
            x = images.astype(np.float32) / 255.0
        else:
            # center to [-1, 1]: with raw [0, 1] pixels the DC component
            # dominates every patch projection and a small ViT sits in a
            # uniform-logits plateau for its whole budget (measured:
            # chance accuracy at 15 epochs uncentered vs ~0.7 by epoch 8
            # centered)
            x = images.astype(np.float32) / 127.5 - 1.0
        if x.ndim == 3:
            x = x[..., None]
        # pos_embed is sized to the train-time patch count: conform queries
        # of other resolutions to the trained shape first
        x = conform_images(x, self._image_shape)
        p = int(self.knobs["patch_size"])
        # pad H/W up to patch multiples (e.g. 28x28 with p=16 → 32x32)
        ph = (-x.shape[1]) % p
        pw = (-x.shape[2]) % p
        if ph or pw:
            x = np.pad(x, ((0, 0), (0, ph), (0, pw), (0, 0)))
        return x

    def _dtype(self):
        return jnp.bfloat16 if self.knobs.get("bf16", True) else jnp.float32

    # ---- contract ----
    def train(self, dataset_path: str,
              ctx: Optional[TrainContext] = None) -> None:
        ctx = ctx or TrainContext()
        ds = load_image_classification_dataset(dataset_path)
        self._n_classes = ds.n_classes
        self._image_shape = ds.image_shape
        x = self._prep(ds.images)
        y = ds.labels

        module = self._module()
        devices = ctx.devices or jax.local_devices()
        mesh = make_mesh(devices)
        b_shard = batch_sharding(mesh)
        r_shard = replicated(mesh)

        batch_size = int(self.knobs["batch_size"])
        # static shapes: batch must divide the data axis
        n_data = len(devices)
        batch_size = max(n_data, batch_size - batch_size % n_data)
        dtype = self._dtype()

        if self._params is None:
            params = module.init(
                jax.random.PRNGKey(0),
                jnp.zeros((1, *x.shape[1:]), dtype))["params"]
        else:
            params = self._params
        if ctx.shared_params is not None and self.knobs.get("share_params") \
                and hasattr(ctx.shared_params, "get"):
            shared = ctx.shared_params.get("params")
            donor_prep = int(ctx.shared_params.get("meta", {})
                             .get("prep_version", 1))
            if shared is not None and donor_prep != self._prep_version:
                # input-contract mismatch: weights trained on v1 [0,1]
                # inputs warm-starting a v2 [-1,1] train would begin at
                # worse-than-random loss AND get re-stamped v2 on dump,
                # erasing the evidence — cold start is strictly better
                import logging

                logging.getLogger(__name__).warning(
                    "skipping warm start: donor checkpoint prep_version="
                    "%d != this train's %d (input normalization "
                    "contracts differ)", donor_prep, self._prep_version)
            elif shared is not None and same_tree_shapes(params, shared):
                params = jax.tree_util.tree_map(jnp.asarray, shared)

        epochs = max(1, round(int(self.knobs["max_epochs"])
                              * float(ctx.budget_scale)))
        if self.knobs.get("quick_train"):
            epochs = min(epochs, 2)

        # linear warmup + cosine decay (the standard ViT recipe): without
        # warmup, small ViTs sit in a uniform-logits plateau for most of a
        # short budget; with it they converge in a handful of epochs
        lr = float(self.knobs["learning_rate"])
        steps_per_epoch = max(1, (len(x) + batch_size - 1) // batch_size)
        total_steps = epochs * steps_per_epoch
        warmup = int(total_steps * float(self.knobs.get("warmup_frac", 0.1)))
        schedule = optax.warmup_cosine_decay_schedule(
            0.0, lr, max(warmup, 1), max(total_steps, 2))
        tx = optax.adamw(schedule,
                         weight_decay=float(self.knobs["weight_decay"]))
        params = jax.device_put(params, r_shard)
        opt_state = jax.device_put(tx.init(params), r_shard)

        # donate params/opt_state: the optimizer update writes in place
        # instead of copying the full trees every step (HBM traffic)
        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def train_step(params, opt_state, xb, yb, mask):
            def loss_fn(p):
                logits = module.apply({"params": p}, xb.astype(dtype))
                losses = optax.softmax_cross_entropy_with_integer_labels(
                    logits.astype(jnp.float32), yb)
                return jnp.sum(losses * mask) / jnp.maximum(
                    jnp.sum(mask), 1.0)

            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        def step(state, b):
            params, opt_state = state
            params, opt_state, loss = train_step(params, opt_state,
                                                 b["x"], b["y"], b["m"])
            return (params, opt_state), loss

        ctx.logger.define_plot("Loss over epochs", ["loss"], x_axis="epoch")
        # donation below invalidates buffers that may alias self._params
        # (warm start / re-train): drop the stale reference so a failure
        # mid-train can't leave the model holding deleted arrays
        self._params = None
        with mesh:
            for epoch in range(epochs):
                (params, opt_state), mean_loss = train_epoch(
                    step, (params, opt_state),
                    ({"x": b["x"], "y": b["y"],
                      "m": b["mask"].astype(np.float32)}
                     for b in batch_iterator({"x": x, "y": y}, batch_size,
                                             seed=epoch)),
                    sharding=b_shard)
                ctx.logger.log(epoch=epoch, loss=mean_loss)
                if ctx.checkpoint is not None:
                    # preemption safety: worker throttles + persists
                    self._params = params
                    ctx.checkpoint(self.dump_parameters,
                                   frac_done=(epoch + 1) / epochs)
                if ctx.should_continue is not None and \
                        not ctx.should_continue(epoch, -mean_loss):
                    break
        self._params = params
        self._fwd = None  # new params/arch → rebuild the cached jit

    def evaluate(self, dataset_path: str) -> float:
        ds = load_image_classification_dataset(dataset_path)
        probs = self._predict_probs(self._prep(ds.images))
        return float(np.mean(np.argmax(probs, -1) == ds.labels))

    def predict(self, queries: Sequence[Any]) -> List[Any]:
        x = self._prep(np.stack([np.asarray(q) for q in queries]))
        return [p.tolist() for p in self._predict_probs(x)]

    def _predict_probs(self, x: np.ndarray) -> np.ndarray:
        assert self._params is not None, "model is not trained/loaded"
        if self._fwd is None:  # cache: jit memoizes by function identity
            module = self._module()
            dtype = self._dtype()

            @jax.jit
            def forward(params, xb):
                logits = module.apply({"params": params}, xb.astype(dtype))
                return jax.nn.softmax(logits.astype(jnp.float32), -1)

            self._fwd = forward
        return bucketed_forward(self._fwd, self._params, x, bucket=64)

    def warmup(self) -> None:
        """Compile the serving forward (one zero query through the same
        bucketed path predict() uses) before traffic arrives."""
        if self._params is None or self._image_shape is None:
            return
        shape = list(self._image_shape)
        self.predict([np.zeros(shape, np.uint8)])

    def dump_parameters(self) -> Dict[str, Any]:
        assert self._params is not None, "model is not trained"
        return {
            "params": jax.tree_util.tree_map(np.asarray, self._params),
            "meta": {"n_classes": self._n_classes,
                     "image_shape": list(self._image_shape or []),
                     # input normalization the params were trained under
                     # (1 = [0,1], 2 = centered [-1,1]); a re-dumped v1
                     # load stays v1 — the version follows the weights
                     "prep_version": self._prep_version},
        }

    def load_parameters(self, params: Dict[str, Any]) -> None:
        self._n_classes = int(params["meta"]["n_classes"])
        self._image_shape = list(params["meta"]["image_shape"])
        # honor the checkpoint's input contract: _prep applies the
        # normalization these weights were trained under, so v1
        # checkpoints serve at full quality instead of silently seeing
        # shifted inputs (ADVICE r3)
        self._prep_version = int(params["meta"].get("prep_version", 1))
        self._params = jax.tree_util.tree_map(jnp.asarray, params["params"])
        self._fwd = None


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
            ViTBase16, TaskType.IMAGE_CLASSIFICATION, train_p, val_p,
            queries=[ds.images[0]],
            knobs={"patch_size": 4, "hidden_dim": 96, "depth": 2,
                   "n_heads": 4, "batch_size": 32, "max_epochs": 5,
                   "learning_rate": 1e-3, "weight_decay": 1e-4,
                   "warmup_frac": 0.1, "bf16": False,
                   "quick_train": False, "share_params": False})
        print("prediction:", int(np.argmax(preds[0])))
