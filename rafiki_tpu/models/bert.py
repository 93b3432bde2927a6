"""BERT-style text classification — BASELINE.md config #4.

Parity target: the reference's text templates (SURVEY.md §2 "Model zoo")
and benchmark config #4 ("BERT-base text-classification fine-tune under
the Advisor"). TPU-first design notes:

- The encoder's attention runs through the Pallas flash kernel with
  per-example ``kv_lens`` padding masks (``rafiki_tpu.ops.attention``) —
  pads never receive attention mass, matching real BERT semantics while
  keeping the batch a single static-shape MXU-friendly tensor.
- Tokenization is a deterministic hashed-vocabulary scheme (blake2b → id):
  this environment has zero egress, so there is no pretrained WordPiece
  vocab to download; hashing gives a stable open vocabulary with the same
  fixed-shape int32 batch interface a real tokenizer would produce.
- Sequences are bucketed to a knob-chosen max length; pre-LN blocks for
  optimization stability at AutoML-scale learning rates.
"""

from __future__ import annotations

import functools
import hashlib
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn

from rafiki_tpu.constants import TaskType
from rafiki_tpu.data import batch_iterator, \
    load_text_classification_dataset
from rafiki_tpu.model import (BaseModel, CategoricalKnob, FixedKnob,
                              FloatKnob, IntegerKnob, KnobConfig, PolicyKnob,
                              TrainContext, bucketed_forward,
                              same_tree_shapes, train_epoch)
from rafiki_tpu.ops.attention import flash_attention
from rafiki_tpu.parallel.sharding import (batch_sharding, make_mesh,
                                          replicated)

PAD_ID = 0
CLS_ID = 1
_RESERVED = 2  # ids below this are special tokens

_TOKEN_RE = re.compile(r"[a-z0-9]+")


class HashTokenizer:
    """Deterministic open-vocabulary tokenizer: lowercase word pieces →
    blake2b-hashed ids. Stable across processes (unlike Python ``hash``,
    which is salted per interpreter)."""

    def __init__(self, vocab_size: int = 1 << 15) -> None:
        if vocab_size <= _RESERVED:
            raise ValueError("vocab_size too small")
        self.vocab_size = vocab_size

    def token_id(self, token: str) -> int:
        h = hashlib.blake2b(token.encode("utf-8"), digest_size=8)
        return _RESERVED + int.from_bytes(h.digest(), "big") % (
            self.vocab_size - _RESERVED)

    def encode(self, text: str, max_len: int) -> Tuple[List[int], int]:
        """Returns (ids padded to ``max_len`` with a leading CLS, true
        length including CLS)."""
        ids = [CLS_ID]
        for tok in _TOKEN_RE.findall(text.lower()):
            if len(ids) >= max_len:
                break
            ids.append(self.token_id(tok))
        length = len(ids)
        ids = ids + [PAD_ID] * (max_len - length)
        return ids, length

    def encode_batch(self, texts: Sequence[str],
                     max_len: int) -> Tuple[np.ndarray, np.ndarray]:
        ids = np.zeros((len(texts), max_len), np.int32)
        lens = np.zeros((len(texts),), np.int32)
        for i, t in enumerate(texts):
            row, n = self.encode(t, max_len)
            ids[i] = row
            lens[i] = n
        return ids, lens


class _EncoderBlock(nn.Module):
    n_heads: int
    mlp_dim: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray, lens: jnp.ndarray) -> jnp.ndarray:
        b, s, d = x.shape
        dh = d // self.n_heads
        y = nn.LayerNorm(dtype=self.dtype)(x)
        qkv = nn.Dense(3 * d, name="qkv", dtype=self.dtype)(y)
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(t):
            return t.reshape(b, s, self.n_heads, dh).transpose(0, 2, 1, 3)

        o = flash_attention(heads(q), heads(k), heads(v), kv_lens=lens)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, d)
        x = x + nn.Dense(d, name="proj", dtype=self.dtype)(o)
        y = nn.LayerNorm(dtype=self.dtype)(x)
        y = nn.Dense(self.mlp_dim, dtype=self.dtype)(y)
        y = nn.gelu(y)
        return x + nn.Dense(d, dtype=self.dtype)(y)


class Bert(nn.Module):
    """Pre-LN transformer encoder over hashed token ids.

    BERT-base = hidden_dim=768, depth=12, n_heads=12, mlp_dim=3072.
    """

    vocab_size: int
    max_len: int
    hidden_dim: int = 768
    depth: int = 12
    n_heads: int = 12
    mlp_dim: int = 3072
    n_classes: int = 2
    dtype: Any = jnp.float32  # compute dtype; params stay f32

    @nn.compact
    def __call__(self, ids: jnp.ndarray, lens: jnp.ndarray) -> jnp.ndarray:
        x = nn.Embed(self.vocab_size, self.hidden_dim,
                     name="tok_embed", dtype=self.dtype)(ids)
        pos = self.param("pos_embed", nn.initializers.normal(0.02),
                         (1, self.max_len, self.hidden_dim))
        x = x + pos[:, :ids.shape[1], :].astype(self.dtype)
        for i in range(self.depth):
            x = _EncoderBlock(self.n_heads, self.mlp_dim, self.dtype,
                              name=f"block_{i}")(x, lens)
        x = nn.LayerNorm(name="final_norm")(x.astype(jnp.float32))
        # CLS pooling (position 0 is always the CLS token)
        return nn.Dense(self.n_classes, name="head")(x[:, 0])


class BertClassifier(BaseModel):
    """Text classification: hashed tokens → pre-LN encoder → CLS head,
    AdamW with linear warmup + cosine decay, DP over the trial sub-mesh."""

    TASKS = (TaskType.TEXT_CLASSIFICATION,)

    @staticmethod
    def get_knob_config() -> KnobConfig:
        return {
            "max_epochs": FixedKnob(8),
            "vocab_size": FixedKnob(1 << 15),
            # all hidden_dim choices divide by all n_heads choices
            "hidden_dim": CategoricalKnob([96, 192, 384, 768],
                                          shape_relevant=True),
            "depth": IntegerKnob(2, 12, shape_relevant=True),
            "n_heads": CategoricalKnob([4, 8, 12], shape_relevant=True),
            "max_len": CategoricalKnob([32, 64, 128], shape_relevant=True),
            "learning_rate": FloatKnob(1e-5, 1e-2, is_exp=True),
            "weight_decay": FloatKnob(1e-5, 1e-1, is_exp=True),
            "warmup_frac": FloatKnob(0.0, 0.2),
            "batch_size": CategoricalKnob([16, 32, 64, 128],
                                          shape_relevant=True),
            "bf16": CategoricalKnob([True, False]),
            "quick_train": PolicyKnob("QUICK_TRAIN"),
            "share_params": PolicyKnob("SHARE_PARAMS"),
        }

    def __init__(self, **knobs: Any) -> None:
        super().__init__(**knobs)
        self._params: Optional[Any] = None
        self._n_classes: Optional[int] = None
        self._fwd: Optional[Any] = None
        self.tokenizer = HashTokenizer(int(self.knobs.get("vocab_size",
                                                          1 << 15)))

    # ---- internals ----
    def _module(self) -> Bert:
        k = self.knobs
        hd = int(k["hidden_dim"])
        heads = int(k["n_heads"])
        if hd % heads:
            raise ValueError(f"hidden_dim={hd} not divisible by "
                             f"n_heads={heads}")
        return Bert(vocab_size=self.tokenizer.vocab_size,
                    max_len=int(k["max_len"]), hidden_dim=hd,
                    depth=int(k["depth"]), n_heads=heads, mlp_dim=4 * hd,
                    n_classes=int(self._n_classes), dtype=self._dtype())

    def _dtype(self):
        return jnp.bfloat16 if self.knobs.get("bf16", True) else jnp.float32

    def _encode(self, texts: Sequence[str]) -> Tuple[np.ndarray, np.ndarray]:
        return self.tokenizer.encode_batch(texts,
                                           int(self.knobs["max_len"]))

    # ---- contract ----
    def train(self, dataset_path: str,
              ctx: Optional[TrainContext] = None) -> None:
        ctx = ctx or TrainContext()
        ds = load_text_classification_dataset(dataset_path)
        self._n_classes = ds.n_classes
        ids, lens = self._encode(ds.texts)
        y = ds.labels

        module = self._module()
        devices = ctx.devices or jax.local_devices()
        mesh = make_mesh(devices)
        b_shard = batch_sharding(mesh)
        r_shard = replicated(mesh)

        n_data = len(devices)
        batch_size = int(self.knobs["batch_size"])
        batch_size = max(n_data, batch_size - batch_size % n_data)

        if self._params is None:
            params = module.init(
                jax.random.PRNGKey(0), jnp.zeros((1, ids.shape[1]),
                                                 jnp.int32),
                jnp.ones((1,), jnp.int32))["params"]
        else:
            params = self._params
        if ctx.shared_params is not None and self.knobs.get("share_params"):
            shared = ctx.shared_params.get("params")
            if shared is not None and same_tree_shapes(params, shared):
                params = jax.tree_util.tree_map(jnp.asarray, shared)

        epochs = max(1, round(int(self.knobs["max_epochs"])
                              * float(ctx.budget_scale)))
        if self.knobs.get("quick_train"):
            epochs = min(epochs, 2)
        steps_per_epoch = max(1, (len(ids) + batch_size - 1) // batch_size)
        total_steps = epochs * steps_per_epoch
        lr = float(self.knobs["learning_rate"])
        warmup = int(total_steps * float(self.knobs["warmup_frac"]))
        schedule = optax.warmup_cosine_decay_schedule(
            0.0, lr, max(warmup, 1), max(total_steps, 2))
        tx = optax.adamw(schedule,
                         weight_decay=float(self.knobs["weight_decay"]))

        params = jax.device_put(params, r_shard)
        opt_state = jax.device_put(tx.init(params), r_shard)

        # donate the param/opt trees: in-place update, no per-step copies
        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def train_step(params, opt_state, ib, lb, yb, mask):
            def loss_fn(p):
                logits = module.apply({"params": p}, ib, lb)
                losses = optax.softmax_cross_entropy_with_integer_labels(
                    logits.astype(jnp.float32), yb)
                return jnp.sum(losses * mask) / jnp.maximum(jnp.sum(mask),
                                                            1.0)

            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        def step(state, b):
            params, opt_state = state
            params, opt_state, loss = train_step(
                params, opt_state, b["ids"], b["lens"], b["y"], b["m"])
            return (params, opt_state), loss

        ctx.logger.define_plot("Loss over epochs", ["loss"], x_axis="epoch")
        # donation invalidates buffers that may alias self._params (warm
        # start / re-train): drop the stale reference first
        self._params = None
        with mesh:
            for epoch in range(epochs):
                (params, opt_state), mean_loss = train_epoch(
                    step, (params, opt_state),
                    ({"ids": b["ids"], "lens": b["lens"], "y": b["y"],
                      "m": b["mask"].astype(np.float32)}
                     for b in batch_iterator(
                         {"ids": ids, "lens": lens, "y": y}, batch_size,
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
        self._fwd = None

    def evaluate(self, dataset_path: str) -> float:
        ds = load_text_classification_dataset(dataset_path)
        probs = self._predict_probs(ds.texts)
        return float(np.mean(np.argmax(probs, -1) == ds.labels))

    def predict(self, queries: Sequence[Any]) -> List[Any]:
        texts = [q if isinstance(q, str) else str(q) for q in queries]
        return [p.tolist() for p in self._predict_probs(texts)]

    def _predict_probs(self, texts: Sequence[str]) -> np.ndarray:
        assert self._params is not None, "model is not trained/loaded"
        ids, lens = self._encode(texts)
        if self._fwd is None:
            module = self._module()

            @jax.jit
            def forward(params, ib, lb):
                logits = module.apply({"params": params}, ib, lb)
                return jax.nn.softmax(logits.astype(jnp.float32), -1)

            self._fwd = forward
        return bucketed_forward(self._fwd, self._params, ids, lens,
                                bucket=64)

    def warmup(self) -> None:
        """Compile the serving forward before traffic arrives."""
        if self._params is None:
            return
        self.predict(["warmup"])

    def dump_parameters(self) -> Dict[str, Any]:
        assert self._params is not None, "model is not trained"
        return {
            "params": jax.tree_util.tree_map(np.asarray, self._params),
            "meta": {"n_classes": self._n_classes},
        }

    def load_parameters(self, params: Dict[str, Any]) -> None:
        self._n_classes = int(params["meta"]["n_classes"])
        self._params = jax.tree_util.tree_map(jnp.asarray, params["params"])
        self._fwd = None


if __name__ == "__main__":  # reference-style self-test block
    import tempfile

    from rafiki_tpu.utils.platform import apply_platform_env

    apply_platform_env()  # the shared compile cache

    from rafiki_tpu.data import generate_text_classification_dataset
    from rafiki_tpu.model import test_model_class

    with tempfile.TemporaryDirectory() as d:
        train_p = f"{d}/train.jsonl"
        val_p = f"{d}/val.jsonl"
        generate_text_classification_dataset(train_p, 256, seed=0)
        generate_text_classification_dataset(val_p, 64, seed=1)
        preds = test_model_class(
            BertClassifier, TaskType.TEXT_CLASSIFICATION, train_p, val_p,
            queries=["tok1 tok2 tok3"],
            knobs={"max_epochs": 8, "vocab_size": 1 << 15, "hidden_dim": 96,
                   "depth": 2, "n_heads": 4, "max_len": 32,
                   "learning_rate": 1e-3, "weight_decay": 1e-4,
                   "warmup_frac": 0.1, "batch_size": 32, "bf16": False,
                   "quick_train": False, "share_params": False})
        print("prediction:", int(np.argmax(preds[0])))
