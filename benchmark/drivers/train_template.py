"""``kind: train_template`` — a model template trained by its own
``train(dataset_path, ctx)``, the call ``TrainWorker`` makes: the
template's data pipeline, its jitted and donated train step, its epoch
loop. The benchmark hands it a dataset file made from the seed, weights
made from the seed (the template's warm-start path: ``load_parameters``
before ``train``), and a ``TrainContext`` whose logger stamps every epoch's end and
whose ``should_continue`` ends the job at the first epoch boundary after
``--seconds``.

One object is trained: epochs 0..warmup-1 are set-up (compile, first steady
epoch), the window opens at the end of the last warm-up epoch, and the
first ``check_steps`` steps of epoch 0 — through the window's own step and
feed — are what the plain reference follows afterwards. To see those steps
the driver stands in for ``train_epoch`` as the template's module names it
and, in epoch 0 only, notes each of the first steps' loss, Adam's first
moment after step 1 and the parameters after the last of them; from epoch
1 on the program's own function runs untouched.
"""

from __future__ import annotations

import importlib
import os
import time
from typing import Any, Dict, List

import numpy as np

from benchmark import harness, traffic_gen, weights


class _Recorder:
    """Stands where the template's module has ``train_epoch``."""

    def __init__(self, original, n_steps: int) -> None:
        self.original = original
        self.n_steps = n_steps
        self.epoch = 0
        self.losses: List[Any] = []
        self.mu_after_first = None
        self.params_after = None

    def __call__(self, step, state, batches, **kw):
        import jax
        import jax.numpy as jnp

        if self.epoch > 0:
            self.epoch += 1
            return self.original(step, state, batches, **kw)
        seen = {"n": 0}

        def noted(st, b):
            i = seen["n"]
            seen["n"] += 1
            st, loss = step(st, b)
            if i < self.n_steps:
                self.losses.append(loss)
                if i == 0:
                    adam = next(s for s in jax.tree_util.tree_leaves(
                        st[1], is_leaf=lambda s: hasattr(s, "mu"))
                        if hasattr(s, "mu"))
                    self.mu_after_first = jax.tree_util.tree_map(
                        jnp.copy, adam.mu)
                if i == self.n_steps - 1:
                    self.params_after = jax.tree_util.tree_map(
                        jnp.copy, st[0])
            return st, loss

        self.epoch += 1
        return self.original(noted, state, batches, **kw)


def reference_steps(cfg, traffic, knobs, data, seed, abstract,
                    total_steps: int, quant=None, rows=None
                    ) -> Dict[str, Any]:
    """The plain reference through the first ``check_steps`` steps on the
    template's own feed (epoch 0 is one permutation from seed 0).
    ``quant`` makes it the control; ``rows`` plants the fault "half of the
    batch left out, the mean taken over the rest"."""
    ref = harness.load_reference(cfg)
    n, bs = len(data["labels"]), int(knobs["batch_size"])
    idx = np.random.default_rng(0).permutation(n)
    take = bs if rows is None else int(rows)
    batches = [(data["images"][idx[i * bs:i * bs + take]],
                data["labels"][idx[i * bs:i * bs + take]])
               for i in range(int(traffic["check_steps"]))]
    params0 = weights.make_weights(abstract, seed)
    hyper = {"learning_rate": float(knobs["learning_rate"]),
             "weight_decay": float(knobs["weight_decay"]),
             "warmup_steps": max(int(total_steps * float(
                 knobs.get("warmup_frac", 0.1))), 1),
             "total_steps": max(total_steps, 2)}
    out = ref.train_steps(params0, batches, cfg, hyper, quant=quant)
    return {"losses": out["losses"], "grads": out["first_grads"],
            "grad_norms": ref.leaf_norms(out["first_grads"]),
            "change": ref.tree_sub(out["params"], params0),
            "params0": params0}


def gaps_to_reference(cfg, ref_out: Dict[str, Any], losses, grad_norms,
                      change) -> Dict[str, Any]:
    """The three numbers compared: every step's loss, the first
    gradient's norm and the parameters' change after the steps — the last
    two by the worst leaf, the gap between the two norms against the
    reference's norm of that leaf or of the median leaf."""
    ref = harness.load_reference(cfg)
    loss_gap = max(abs(float(a) - b) / abs(b)
                   for a, b in zip(losses, ref_out["losses"]))
    grad_gap, grad_leaf = ref.worst_leaf_gap(np.asarray(grad_norms),
                                             ref_out["grad_norms"])
    # elements whose gradient is nought to rounding (a key's bias under
    # softmax, inside the fused qkv bias) move under Adam by round-off
    # alone: out of the change, by a rule on the reference's gradient —
    # under a thousandth of the median leaf's RMS element — not by name
    thr = 1e-3 * float(np.median(ref.leaf_rms(ref_out["grads"])))
    d_ref, d_other, left_out = ref.masked_change_norms(
        ref_out["grads"], ref_out["change"], change, thr)
    delta_gap, delta_leaf = ref.worst_leaf_gap(d_other, d_ref)
    paths = weights._paths(ref_out["params0"])
    return {"step_loss_gap": loss_gap, "first_grad_norm_gap": grad_gap,
            "param_change_norm_gap": delta_gap,
            "detail": {
                "losses": [float(x) for x in losses],
                "reference_losses": ref_out["losses"],
                "first_grad_worst_leaf": paths[grad_leaf],
                "param_change_worst_leaf": paths[delta_leaf],
                "leaves": len(paths), "elements_left_out": left_out,
                "median_leaf_grad_norm": float(np.median(
                    ref_out["grad_norms"])),
                "median_leaf_change_norm": float(np.median(d_ref))}}


def program_gaps(cfg, ref_out, rec: _Recorder) -> Dict[str, Any]:
    ref = harness.load_reference(cfg)
    change = ref.tree_sub(rec.params_after, ref_out["params0"])
    return gaps_to_reference(
        cfg, ref_out, rec.losses,
        ref.leaf_norms(rec.mu_after_first) / (1.0 - ref.ADAM_B1), change)


COMPARED = ("step_loss_gap", "first_grad_norm_gap", "param_change_norm_gap")


def compare_with_reference(cfg, traffic, knobs, data, seed, abstract,
                           rec: _Recorder, total_steps: int,
                           limits: Dict[str, float], control=None
                           ) -> List[Dict[str, Any]]:
    """``control`` (a precision) puts the control in the program's place:
    the same steps followed by the reference computed in that precision."""
    ref_out = reference_steps(cfg, traffic, knobs, data, seed, abstract,
                              total_steps)
    if control:
        o = reference_steps(cfg, traffic, knobs, data, seed, abstract,
                            total_steps, quant=control)
        got = gaps_to_reference(cfg, ref_out, o["losses"], o["grad_norms"],
                                o["change"])
    else:
        got = program_gaps(cfg, ref_out, rec)
    harness.emit("reference", steps=int(traffic["check_steps"]),
                 control=control, **{k: got[k] for k in COMPARED},
                 **got["detail"])
    return [{"name": k, "value": got[k], "limit": limits[k],
             "ok": bool(np.isfinite(got[k]) and got[k] <= limits[k])}
            for k in COMPARED if k in limits]


def train_once(ctx: Dict[str, Any], seed: int, seconds: float,
               tracer=None) -> Dict[str, Any]:
    """One ``train`` call from the seed: data, weights, warm-up epochs,
    window. Returns what ``run`` and ``calibrate`` read afterwards."""
    import jax
    import jax.numpy as jnp

    from rafiki_tpu.model import TrainContext

    cfg, traffic = ctx["config"], ctx["traffic"]
    phases, monitor = ctx["phases"], ctx["monitor"]
    knobs = dict(cfg["knobs"])

    data = traffic_gen.image_classification(traffic, seed)
    path = os.path.join(ctx["work_dir"], "train.npz")
    np.savez(path, **data)  # uncompressed: set-up, not the program's time
    phases.mark("dataset_from_seed")

    models = importlib.import_module("rafiki_tpu.models.vit")
    model = getattr(models, cfg["template"])(**knobs)
    # the tree's names and shapes from the program's public flax module at
    # the configuration's sizes (what the template builds from its knobs);
    # the weights go in through the template's own warm-start path
    hw, ch = int(traffic["image_size"]), int(traffic["n_channels"])
    dtype = jnp.bfloat16 if knobs.get("bf16", True) else jnp.float32
    module = models.ViT(
        patch_size=int(cfg["patch_size"]), hidden_dim=int(cfg["hidden_size"]),
        depth=int(cfg["num_hidden_layers"]),
        n_heads=int(cfg["num_attention_heads"]),
        mlp_dim=int(cfg["intermediate_size"]),
        n_classes=int(traffic["n_classes"]), dtype=dtype,
        remat=bool(knobs.get("remat", False)))
    abstract = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, hw, hw, ch), dtype))["params"])
    params = weights.make_weights(abstract, seed)
    jax.block_until_ready(params)
    model.load_parameters({"params": params, "meta": {
        "n_classes": int(traffic["n_classes"]),
        "image_shape": [hw, hw, ch], "prep_version": 2}})
    del params
    phases.mark("weights_from_seed")

    n, bs = int(traffic["n_examples"]), int(knobs["batch_size"])
    steps_per_epoch = -(-n // bs)
    warm = int(traffic["warmup_epochs"])
    budget_scale = 1e5  # epochs never run out; the schedule stays flat
    total_steps = max(1, round(int(knobs["max_epochs"]) * budget_scale)) \
        * steps_per_epoch

    rec = _Recorder(models.train_epoch, int(traffic["check_steps"]))
    models.train_epoch = rec
    stamps: List[float] = []
    losses: List[float] = []
    st = {"t_open": None, "setup_s": None, "closed_at": None,
          "trace_from": None}

    def on_record(r) -> None:
        if r.kind != "values" or "loss" not in r.data:
            return
        now = time.monotonic()
        stamps.append(now)
        losses.append(float(r.data["loss"]))
        if len(stamps) == warm:  # the window opens here
            st["t_open"] = now
            st["setup_s"] = phases.since_start()
            phases.mark("train_warm_epochs")
            monitor.fence()

    def should_continue(epoch: int, _score: float) -> bool:
        if st["t_open"] is None:
            return True
        if st["closed_at"] is None:
            if time.monotonic() - st["t_open"] < seconds:
                return True
            st["closed_at"] = len(stamps)  # epochs done at the close
            monitor.unfence()
            if tracer is None:
                return False
            st["trace_from"] = len(stamps)
            tracer.start()
            return True
        if len(stamps) - st["trace_from"] < int(traffic["trace_epochs"]):
            return True
        tracer.stop()
        return False

    tctx = TrainContext(budget_scale=budget_scale,
                        should_continue=should_continue)
    tctx.logger.sink = on_record
    try:
        model.train(path, tctx)
    finally:
        models.train_epoch = rec.original
    os.remove(path)
    del model  # the trained parameters go with it
    return {"rec": rec, "st": st, "stamps": stamps, "losses": losses,
            "data": data, "abstract": abstract, "total_steps": total_steps,
            "knobs": knobs, "n": n, "bs": bs, "warm": warm,
            "steps_per_epoch": steps_per_epoch}


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    cfg, traffic, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    t = train_once(ctx, seed, float(ctx["seconds"]), ctx["tracer"])
    memory = harness.memory_peak()
    rec, st, stamps, losses = t["rec"], t["st"], t["stamps"], t["losses"]
    data, abstract, knobs = t["data"], t["abstract"], t["knobs"]
    n, bs, warm = t["n"], t["bs"], t["warm"]
    steps_per_epoch, total_steps = t["steps_per_epoch"], t["total_steps"]
    phases = ctx["phases"]

    closed = st["closed_at"]
    epochs = closed - warm
    window_s = stamps[closed - 1] - st["t_open"]
    samples = epochs * n
    win_losses = losses[warm:closed]
    bad_epochs = sum(1 for l in win_losses if not np.isfinite(l))
    end_to_end = {"setup_s": st["setup_s"],
                  "train_samples_per_s": samples / window_s}
    epoch_s = np.diff([st["t_open"]] + stamps[warm:closed])

    limits = cfg["limits"]
    checks = compare_with_reference(cfg, traffic, knobs, data, seed,
                                    abstract, rec, total_steps, limits,
                                    ctx.get("control"))
    checks.append({"name": "epoch_losses_finite", "value": bad_epochs,
                   "limit": 0, "ok": bad_epochs == 0})
    phases.mark("window_and_reference")
    return {
        "attempted": epochs * steps_per_epoch,
        "failed": bad_epochs * steps_per_epoch,
        "end_to_end": end_to_end, "checks": checks, "memory": memory,
        "counters": {"epochs_in_window": epochs,
                     "steps_in_window": epochs * steps_per_epoch,
                     "steps_per_epoch": steps_per_epoch,
                     "epoch_loss_first": losses[0],
                     "epoch_loss_last_of_window": win_losses[-1]},
        "window": {"window_s": window_s, "epochs": epochs,
                   "samples": samples,
                   "epoch_s_median": float(np.median(epoch_s)),
                   "epoch_s_max": float(np.max(epoch_s)),
                   "generator_lateness_s": 0.0},
        "window_s": window_s, "samples": samples, "batch": bs,
        "traced": {"batch": bs},
    }


def calibrate(ctx: Dict[str, Any], seeds: List[int], control: str) -> None:
    """The readings the limits are set from, in one process: for every seed
    the program's three gaps; with ``control``, also the control's (the
    reference in that precision, put in the program's place) and those of
    the fault "half of the batch left out"."""
    cfg, traffic = ctx["config"], ctx["traffic"]
    for seed in seeds:
        t = train_once(ctx, seed, 0.0)
        ref_out = reference_steps(cfg, traffic, t["knobs"], t["data"],
                                  seed, t["abstract"], t["total_steps"])
        line = {"program": program_gaps(cfg, ref_out, t["rec"])}
        if control:
            for name, kw in [(f"control_{q}", {"quant": q})
                             for q in control.split(",")] + [
                                 ("half_batch", {"rows": t["bs"] // 2})]:
                o = reference_steps(cfg, traffic, t["knobs"], t["data"],
                                    seed, t["abstract"], t["total_steps"],
                                    **kw)
                line[name] = gaps_to_reference(
                    cfg, ref_out, o["losses"], o["grad_norms"], o["change"])
        harness.emit("calibrate", seed=seed, **{
            k: {c: v[c] for c in COMPARED} for k, v in line.items()},
            detail=line["program"]["detail"])
