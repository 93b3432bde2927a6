"""JaxFeedForward — the ``TfFeedForward``-equivalent template (config #1).

Parity target: the reference zoo's ``TfFeedForward`` FashionMNIST template
(SURVEY.md §2 "Model zoo", §6 config 1): a small dense net for image
classification with knobs over depth/width/lr/batch size. Rebuilt as a
flax.linen module with a fully ``jax.jit``-compiled train step (donated
optimizer state, static batch shapes) so the same code path runs CPU or a
TPU sub-mesh unchanged.

Knob application is *functional*: the train step is a pure function over
an explicit ``{"params", "opt"}`` state with the traceable knob
(``learning_rate``) arriving as a traced scalar operand — the SAME
functions back the sequential ``train()`` loop and the gang-compiled
tuning engine's vmapped lanes (``make_gang_spec``), so a 1-lane gang
trial reproduces a sequential trial bit-for-bit (tier-1 asserts it).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn

# NOTE: zoo templates use absolute imports — their module source is shipped
# to workers via serialize_model_class() and re-imported standalone, where
# relative imports have no parent package.
from rafiki_tpu.constants import TaskType
from rafiki_tpu.data import batch_iterator, \
    load_image_classification_dataset
from rafiki_tpu.model import (BaseModel, CategoricalKnob, FixedKnob,
                              FloatKnob, GangSpec, IntegerKnob, KnobConfig,
                              Knobs, PolicyKnob, TrainContext,
                              bucketed_forward, conform_images,
                              same_tree_shapes)


class _MLP(nn.Module):
    hidden_layer_count: int
    hidden_layer_units: int
    n_classes: int

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        x = x.reshape((x.shape[0], -1))
        for _ in range(self.hidden_layer_count):
            x = nn.Dense(self.hidden_layer_units)(x)
            x = nn.relu(x)
        return nn.Dense(self.n_classes)(x)


class JaxFeedForward(BaseModel):
    """Dense image classifier (FashionMNIST-class workloads)."""

    TASKS = (TaskType.IMAGE_CLASSIFICATION,)

    @staticmethod
    def get_knob_config() -> KnobConfig:
        return {
            "max_epochs": FixedKnob(5),
            "hidden_layer_count": IntegerKnob(1, 3, shape_relevant=True),
            "hidden_layer_units": IntegerKnob(16, 256, is_exp=True,
                                              shape_relevant=True),
            "learning_rate": FloatKnob(1e-4, 1e-1, is_exp=True,
                                       traceable=True),
            "batch_size": CategoricalKnob([32, 64, 128],
                                          shape_relevant=True),
            "quick_train": PolicyKnob("QUICK_TRAIN"),
            "share_params": PolicyKnob("SHARE_PARAMS"),
        }

    def __init__(self, **knobs: Any) -> None:
        super().__init__(**knobs)
        self._params: Optional[Any] = None
        self._n_classes: Optional[int] = None
        self._image_shape: Optional[Sequence[int]] = None
        self._fwd: Optional[Any] = None  # cached jitted forward

    # ---- internals ----
    def _module(self) -> _MLP:
        assert self._n_classes is not None
        return _MLP(hidden_layer_count=int(self.knobs["hidden_layer_count"]),
                    hidden_layer_units=int(self.knobs["hidden_layer_units"]),
                    n_classes=self._n_classes)

    @staticmethod
    def _to_float(images: np.ndarray) -> np.ndarray:
        return images.astype(np.float32) / 255.0

    @staticmethod
    def _lane_functions(module: "_MLP", sample_shape: Sequence[int]):
        """``(init_lane, train_step)`` — the functional training core
        shared by the sequential ``train()`` loop and the gang engine's
        vmapped lanes (1 lane == 1 sequential trial, bit-for-bit).

        ``hp`` carries the traceable knobs as traced scalars:
        ``optax.adam(lr)`` is exactly ``scale_by_adam`` followed by
        ``scale(-lr)``, so applying ``-lr`` to the adam-scaled updates
        keeps the math identical while letting lr differ per lane
        inside one compiled program."""
        tx = optax.scale_by_adam()

        def init_lane(rng: Any, hp: Dict[str, Any]) -> Dict[str, Any]:
            params = module.init(rng,
                                 jnp.zeros((1, *sample_shape)))["params"]
            return {"params": params, "opt": tx.init(params)}

        def train_step(state: Dict[str, Any], hp: Dict[str, Any],
                       batch: Dict[str, Any]):
            def loss_fn(p):
                logits = module.apply({"params": p}, batch["x"])
                losses = optax.softmax_cross_entropy_with_integer_labels(
                    logits, batch["y"])
                mask = batch["mask"].astype(jnp.float32)
                return jnp.sum(losses * mask) / jnp.maximum(
                    jnp.sum(mask), 1)

            loss, grads = jax.value_and_grad(loss_fn)(state["params"])
            updates, opt = tx.update(grads, state["opt"], state["params"])
            updates = jax.tree_util.tree_map(
                lambda u: -hp["learning_rate"] * u, updates)
            return {"params": optax.apply_updates(state["params"], updates),
                    "opt": opt}, loss

        return init_lane, train_step

    @classmethod
    def gang_epochs(cls, knobs: Knobs, budget_scale: float) -> int:
        """Epoch count ``train()`` would spend — the gang scheduler's
        per-lane budget (must mirror the sequential loop exactly)."""
        return max(1, round(int(knobs["max_epochs"]) * float(budget_scale)))

    # ---- contract ----
    def train(self, dataset_path: str,
              ctx: Optional[TrainContext] = None) -> None:
        ctx = ctx or TrainContext()
        ds = load_image_classification_dataset(dataset_path)
        self._n_classes = ds.n_classes
        self._image_shape = ds.image_shape
        x = self._to_float(ds.images)
        y = ds.labels

        module = self._module()
        batch_size = int(self.knobs["batch_size"])
        init_lane, train_step = self._lane_functions(module, x.shape[1:])
        hp = {"learning_rate":
              jnp.float32(float(self.knobs["learning_rate"]))}
        state = init_lane(jax.random.PRNGKey(0), hp)
        if self._params is not None:  # warm-started via load_parameters
            state = {"params": self._params, "opt": state["opt"]}
        if ctx.shared_params is not None and self.knobs.get("share_params"):
            shared = ctx.shared_params.get("params")
            if shared is not None and same_tree_shapes(state["params"],
                                                       shared):
                state = {"params": jax.tree_util.tree_map(jnp.asarray,
                                                          shared),
                         "opt": state["opt"]}
            # else: incompatible architecture → cold start

        # donate the state tree: in-place update, no per-step copies
        step = jax.jit(train_step, donate_argnums=(0,))
        epochs = self.gang_epochs(self.knobs, ctx.budget_scale)
        ctx.logger.define_plot("Loss over epochs", ["loss"], x_axis="epoch")
        # donation invalidates buffers that may alias self._params (warm
        # start / re-train): drop the stale reference first
        self._params = None
        for epoch in range(epochs):
            losses = []
            for batch in batch_iterator({"x": x, "y": y}, batch_size,
                                        seed=epoch):
                state, loss = step(state, hp, batch)
                losses.append(float(loss))
            mean_loss = float(np.mean(losses))
            ctx.logger.log(epoch=epoch, loss=mean_loss)
            if ctx.checkpoint is not None:
                # preemption safety: worker throttles + persists
                self._params = state["params"]
                ctx.checkpoint(self.dump_parameters,
                               frac_done=(epoch + 1) / epochs)
            if ctx.should_continue is not None and \
                    not ctx.should_continue(epoch, -mean_loss):
                break
        self._params = state["params"]
        self._fwd = None  # new params/arch → rebuild the cached jit

    @classmethod
    def make_gang_spec(cls, knobs: Knobs, train_dataset_path: str,
                       val_dataset_path: str) -> GangSpec:
        """Functional training recipe for the gang-compiled tuning
        engine: everything but ``learning_rate`` (the traceable knob) is
        burned in from ``knobs`` — proposals sharing this static bucket
        train as lanes of one vmapped step."""
        ds = load_image_classification_dataset(train_dataset_path)
        x = cls._to_float(ds.images)
        y = ds.labels
        module = _MLP(hidden_layer_count=int(knobs["hidden_layer_count"]),
                      hidden_layer_units=int(knobs["hidden_layer_units"]),
                      n_classes=ds.n_classes)
        batch_size = int(knobs["batch_size"])
        init_lane, train_step = cls._lane_functions(module, x.shape[1:])
        vds = load_image_classification_dataset(val_dataset_path)
        vx = conform_images(cls._to_float(vds.images), ds.image_shape)
        vy = vds.labels
        meta = {"n_classes": ds.n_classes,
                "image_shape": list(ds.image_shape)}

        def epoch_batches(epoch: int):
            return batch_iterator({"x": x, "y": y}, batch_size, seed=epoch)

        def eval_lane(state, hp, xb):
            # argmax(logits) == argmax(softmax(logits)) — matches
            # evaluate()'s accuracy exactly
            return jnp.argmax(module.apply({"params": state["params"]},
                                           xb), -1)

        def eval_batches():
            return batch_iterator({"x": vx, "y": vy}, 256, shuffle=False)

        def export_blob(lane_state, hp):
            return {"params": jax.tree_util.tree_map(
                        np.asarray, lane_state["params"]),
                    "meta": dict(meta)}

        def warm_lane(fresh, blob):
            shared = (blob or {}).get("params")
            if shared is None or not same_tree_shapes(fresh["params"],
                                                      shared):
                return fresh  # incompatible architecture → cold start
            return {"params": jax.tree_util.tree_map(jnp.asarray, shared),
                    "opt": fresh["opt"]}

        return GangSpec(hp_names=("learning_rate",), init_lane=init_lane,
                        train_step=train_step, epoch_batches=epoch_batches,
                        eval_lane=eval_lane, eval_batches=eval_batches,
                        export_blob=export_blob, warm_lane=warm_lane,
                        share_params_knob="share_params")

    def evaluate(self, dataset_path: str) -> float:
        ds = load_image_classification_dataset(dataset_path)
        x = conform_images(self._to_float(ds.images), self._image_shape)
        probs = self._predict_probs(x)
        return float(np.mean(np.argmax(probs, -1) == ds.labels))

    def predict(self, queries: Sequence[Any]) -> List[Any]:
        x = self._to_float(np.stack([np.asarray(q) for q in queries]))
        if x.ndim == 3:
            x = x[..., None]
        # the flatten→Dense input width is fixed at train time
        x = conform_images(x, self._image_shape)
        return [p.tolist() for p in self._predict_probs(x)]

    def _predict_probs(self, x: np.ndarray) -> np.ndarray:
        assert self._params is not None, "model is not trained/loaded"
        if self._fwd is None:  # cache: jit memoizes by function identity
            module = self._module()

            @jax.jit
            def forward(params, xb):
                return jax.nn.softmax(
                    module.apply({"params": params}, xb), -1)

            self._fwd = forward
        return bucketed_forward(self._fwd, self._params, x, bucket=256)

    def warmup(self) -> None:
        """Compile the serving forward before traffic arrives."""
        if self._params is None or self._image_shape is None:
            return
        self.predict([np.zeros(list(self._image_shape), np.uint8)])

    def dump_parameters(self) -> Dict[str, Any]:
        assert self._params is not None, "model is not trained"
        return {
            "params": jax.tree_util.tree_map(np.asarray, self._params),
            "meta": {"n_classes": self._n_classes,
                     "image_shape": list(self._image_shape or [])},
        }

    def load_parameters(self, params: Dict[str, Any]) -> None:
        self._n_classes = int(params["meta"]["n_classes"])
        self._image_shape = list(params["meta"]["image_shape"])
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
        generate_image_classification_dataset(train_p, 512, seed=0)
        ds = generate_image_classification_dataset(val_p, 128, seed=1)
        preds = test_model_class(
            JaxFeedForward, TaskType.IMAGE_CLASSIFICATION, train_p, val_p,
            queries=[ds.images[0], ds.images[1]])
        print("predictions:", [int(np.argmax(p)) for p in preds])
